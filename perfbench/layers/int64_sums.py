"""Kernels (``ops/kernels.py``: ``group_aggregate``): integer sums a
query accumulated in 64 bits: the sum over its stages' settled
``stage_done`` events of ``int64_sums`` (aggregates of kind ``sum64`` in the stage's
group-bys, from the plan), median over the window's queries.  1 in a
query with one ``SUM`` over an integer column; 0 would mean the sum ran
in its column's own 32 bits.  ``None`` where no event carries the
attribute (an older program, or a query with no group-by).  Source:
program counter."""

import statistics


def read(run):
    n = []
    for q in run["queries"]:
        got = {e["stage"]: e["int64_sums"] for e in q["events"]
               if e.get("event") == "stage_done" and "int64_sums" in e
               and not e.get("overflow")}
        if got:
            n.append(sum(got.values()))
    return float(statistics.median(n)) if n else None
