"""Planner and executor (``exec/executor.py``): the capacity ``scale`` at
which the query's range-exchange stage settled (the ``scale`` of the
attempt that did not overflow), median over the window's queries.  1 is
the planned capacity; each unit more is one more input partition's worth
of rows in every chip's sort program.  Source: program counter."""

import statistics

from perfbench.layers.exchange_imbalance import settled_range_stages


def read(run):
    vals = [int(e["scale"]) for e in settled_range_stages(run)
            if "scale" in e]
    return float(statistics.median(vals)) if vals else None
