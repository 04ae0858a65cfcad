"""Kernels (``ops/kernels.py``: ``_gather_live``): the share of their
capacity that a query's bounded gathers fetched.  A sort too wide to
carry its values is an index sort and one packed gather, and a gather
whose caller holds the count of its live rows fetches those alone, a
chunk a trip; every stage's program counts the rows its bounded gathers
fetched (``gather_rows``) and the rows they would have fetched unbounded
(``gather_rows_cap``, their capacities) into the stage info vector, and
its settled ``stage_done`` event carries both.  This is the sum of the
one over the sum of the other over a query's stages, median over the
window's queries: 1.0 where every such sort is full (a whole-table sort),
near 0 where a 12 M-row capacity holds a few groups or joined rows.
``None`` where no stage reports the two (an older program), or none of a
query's programs holds a bounded gather.  Source: program counter."""

import statistics


def read(run):
    shares = []
    for q in run["queries"]:
        got = {e["stage"]: (e["gather_rows"], e["gather_rows_cap"])
               for e in q["events"]
               if e.get("event") == "stage_done" and "gather_rows_cap" in e
               and not e.get("overflow")}
        cap = sum(c for _, c in got.values())
        if cap:
            shares.append(sum(f for f, _ in got.values()) / cap)
    return float(statistics.median(shares)) if shares else None
