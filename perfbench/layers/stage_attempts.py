"""Planner and executor (``plan/``, ``exec/``): ``stage_done`` events per
query from the ``Context``'s event log, median over the window.  What is
above the plan's stage count is capacity retries.  Source: program
counter (an exact count)."""

import statistics


def read(run):
    n = [sum(1 for e in q["events"] if e.get("event") == "stage_done")
         for q in run["queries"]]
    return float(statistics.median(n)) if n else None
