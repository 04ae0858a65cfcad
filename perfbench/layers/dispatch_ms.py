"""Planner and executor (``exec/recovery.py``, ``exec/executor.py``):
host milliseconds the executor itself takes to launch a query's stage
programs: the self time of the ``run`` span and of the ``stage`` spans
beneath it, which is everything under ``run`` but the wait for the
device (``settle``) and compiling (``stage.compile``); per query, median
over the window.  Source: program span."""

from perfbench import program_spans as ps


def read(run):
    def mine(r, _qrows):
        return r.name == "run" or r.kind == "stage"
    return ps.ms(ps.self_seconds_of(run, mine))
