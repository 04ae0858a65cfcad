"""Exchange (``parallel/shuffle.py``): how unevenly the range exchange
left the rows over the partitions.  Largest / mean of ``rows`` of the
settled attempt (the last ``stage_done``, the one that did not overflow)
of the query's range-exchange stage, median over the window's queries.
1.0 is an even split; a partitioner that keeps equal keys together reads
the hot key's share times the partitions.  A stage is a range-exchange
stage if its ``stage_done`` carries ``range_lanes`` or, on a program
from before that counter, is labelled ``orderby`` or ``rangepartition``.
Source: program counter."""

import statistics


def settled_range_stages(run):
    """Per query of the window, the ``stage_done`` event of the settled
    attempt of its (last) range-exchange stage."""
    out = []
    for q in run.get("queries") or []:
        hit = [e for e in q.get("events") or []
               if e.get("event") == "stage_done"
               and not e.get("overflow")
               and ("range_lanes" in e
                    or e.get("label") in ("orderby", "rangepartition"))]
        if hit:
            out.append(hit[-1])
    return out


def read(run):
    vals = []
    for e in settled_range_stages(run):
        rows = e.get("rows") or []
        if rows and sum(rows) > 0:
            vals.append(max(rows) * len(rows) / sum(rows))
    return float(statistics.median(vals)) if vals else None
