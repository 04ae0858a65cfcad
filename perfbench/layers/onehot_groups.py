"""Kernels (``ops/kernels.py``: ``_group_aggregate_smallkey``): group-bys
a query ran on the one-hot MXU branch — one integer key, or short keys
(booleans, integers of at most 16 bits, one-byte strings) in one
mixed-radix slot, whose live span fits the slots.  Every stage's program
counts the group-bys whose run-time span check chose that branch into
the stage info vector, and its settled ``stage_done`` event carries
``onehot_groups``.  This is the sum over a query's stages, median over
the window's queries: 1 in TPC-H Q1 and Q6, 0 where every group-by
sorted.  ``None`` where no event carries the attribute (an older
program).  Source: program counter."""

import statistics


def read(run):
    n = []
    for q in run["queries"]:
        got = {e["stage"]: e["onehot_groups"] for e in q["events"]
               if e.get("event") == "stage_done" and "onehot_groups" in e
               and not e.get("overflow")}
        if got:
            n.append(sum(got.values()))
    return float(statistics.median(n)) if n else None
