"""Kernels (``ops/kernels.py``: ``hash_join``): join stages a query whose
program holds the general hash join: ``stage_done`` events whose
``join_kernel`` is not ``lookup`` (``hash``: the general body alone;
``checked``: both kernels and a run-time duplicate check), median over
the window's queries.  0 where every join's build side is a table whose
store carries the key it is joined on (``to_store(unique=)``): those
stages hold the lookup kernel alone.  ``None`` where no event carries the
attribute (an older program, or a query with no join).  Source: program
counter."""

import statistics


def read(run):
    n = [sum(1 for e in joins if e["join_kernel"] != "lookup")
         for joins in _join_events(run)]
    return float(statistics.median(n)) if n else None


def _join_events(run):
    """Per query that has them: the ``stage_done`` event of each join
    stage's settled attempt (an overflow replays the stage: the last
    counts)."""
    per = [list({e["stage"]: e for e in q["events"]
                 if e.get("event") == "stage_done" and "join_kernel" in e
                 and not e.get("overflow")}.values())
           for q in run["queries"]]
    return [joins for joins in per if joins]
