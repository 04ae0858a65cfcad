"""The program's own spans (``dryad_tpu/obs/trace.py``), as the per-layer
readers take them.  The program emits a span as an event of the
``Context``'s sink when it ends, and ``run.py`` keeps the events of each
query of the window in ``run["queries"][i]["events"]``, in timed runs
too.  A span's ``t0`` is the host's wall clock and ``dur_s`` a monotonic
duration; a reader here wants seconds only, so the two are never mixed
with a query's own ``t0``/``t1`` (``perf_counter``).

    rows(run)            [Row(query, name, kind, t0, t1, span, parent, attrs)]
    seconds(run, name)   per-query sum of the spans of a name (or names),
                         median over the window's queries
    self_seconds(...)    a span's duration less the union of its children
    untraced(run)        a query's wall time less the union of all program
                         spans in it, median over queries
    ms(seconds)          milliseconds, keeping ``None``

A program that has no such span (an older commit) gives ``None``, never an
error.
"""

from __future__ import annotations

import statistics
from typing import NamedTuple, Optional


class Row(NamedTuple):
    query: int
    name: str
    kind: str
    t0: float
    t1: float
    span: Optional[str]
    parent: Optional[str]
    attrs: dict

    @property
    def seconds(self):
        return self.t1 - self.t0


def rows(run):
    out = []
    for q in run.get("queries") or []:
        for e in q.get("events") or []:
            if e.get("event") == "span" and e.get("t0") is not None:
                t0 = float(e["t0"])
                out.append(Row(q["i"], e.get("name", ""), e.get("kind", ""),
                               t0, t0 + float(e.get("dur_s", 0.0)),
                               e.get("span"), e.get("parent"),
                               e.get("attrs") or {}))
    return out


def ms(seconds):
    return None if seconds is None else seconds * 1e3


def by_query(run):
    """{query index: its rows}, every query of the window present."""
    out = {q["i"]: [] for q in run.get("queries") or []}
    for r in rows(run):
        out[r.query].append(r)
    return out


def union_seconds(intervals):
    """Seconds covered by the union of (t0, t1) intervals."""
    total, end = 0.0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_seconds(row, qrows):
    """``row``'s duration less what its children cover of it."""
    if row.span is None:
        return row.seconds
    kids = [(max(r.t0, row.t0), min(r.t1, row.t1)) for r in qrows
            if r.parent == row.span and r.t1 > row.t0 and r.t0 < row.t1]
    return row.seconds - union_seconds(kids)


def median_per_query(run, per_query):
    """Median over the window's queries of ``per_query(rows of a query)``,
    leaving out the queries for which it gives ``None``."""
    vals = [v for v in (per_query(qr) for qr in by_query(run).values())
            if v is not None]
    return statistics.median(vals) if vals else None


def _named(qrows, names):
    if isinstance(names, str):
        names = (names,)
    return [r for r in qrows if r.name in names]


def seconds(run, names):
    def one(qrows):
        hit = _named(qrows, names)
        return sum(r.seconds for r in hit) if hit else None
    return median_per_query(run, one)


def self_seconds_of(run, pick):
    """Per-query sum of the self time of the rows ``pick(row, qrows)``
    accepts, median over queries."""
    def one(qrows):
        hit = [r for r in qrows if pick(r, qrows)]
        return sum(self_seconds(r, qrows) for r in hit) if hit else None
    return median_per_query(run, one)


def descends_from(row, qrows, name):
    """Whether a span named ``name`` is among ``row``'s ancestors."""
    by_id = {r.span: r for r in qrows}
    seen = set()
    parent = row.parent
    while parent in by_id and parent not in seen:
        seen.add(parent)
        if by_id[parent].name == name:
            return True
        parent = by_id[parent].parent
    return False


def untraced(run):
    walls = {q["i"]: q["t1"] - q["t0"] for q in run.get("queries") or []}
    got = by_query(run)
    vals = [walls[i] - union_seconds([(r.t0, r.t1) for r in qr])
            for i, qr in got.items() if qr]
    return statistics.median(vals) if vals else None
