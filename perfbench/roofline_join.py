"""The bytes a query's joins have to move, from shapes alone: the
configuration's schemas, the reference's specification of the query and
the rows each join returned in the reference (never anything of the
program).  A join has to read each of its two inputs once, at the columns
the query names, and write its matched rows once, at the columns the
rest of the query still reads:

* a stored table enters its join with all its stored rows (its filter has
  to see each) at the columns the specification names of it: filter,
  keys, group-by, aggregates;
* what is joined so far enters the next join with the rows the last join
  returned, at the columns carried on.

Bound by bytes, like every count of ``roofline.py``."""

from __future__ import annotations

from perfbench import roofline


def _names(e):
    if isinstance(e, str):
        yield e
    elif isinstance(e, list):
        for a in e[1:]:
            yield from _names(a)


def _grouped_and_summed(spec):
    """The columns the group-by and the aggregates read."""
    used = set(spec.get("group_by", []))
    for _fn, *arg in spec["aggregates"].values():
        if arg:
            used |= set(_names(arg[0]))
    return used


def named_columns(spec, schemas):
    """{table: the columns of it that the specification names}."""
    used = _grouped_and_summed(spec)
    for f in spec.get("filters", {}).values():
        # ["str==", column, "TEXT"]: the text is no column
        used |= {n for n in _names(f) if any(n in s
                                             for s in schemas.values())}
    for lt, lk, rt, rk in spec["joins"]:
        used |= {lk, rk}
    return {t: [c for c in schemas[t] if c in used]
            for t in spec["tables"]}


def join_bytes(spec, schemas, stored_rows, join_rows):
    """Least bytes of all the joins of one query.  ``stored_rows``:
    {table: rows}; ``join_rows``: rows each join returned, in order."""
    named = named_columns(spec, schemas)

    def width(cols):
        return roofline.device_row_bytes(
            {c: s[c] for s in schemas.values() for c in cols if c in s})

    def carried(joined, k):
        """Columns of the joined tables that the query reads after join
        ``k``: later keys, group-by, aggregates."""
        later = _grouped_and_summed(spec)
        for lt, lk, rt, rk in spec["joins"][k + 1:]:
            later |= {lk, rk}
        return [c for t in joined for c in named[t] if c in later]

    total, joined = 0, []
    for k, (lt, lk, rt, rk) in enumerate(spec["joins"]):
        if joined:
            total += join_rows[k - 1] * width(carried(joined, k - 1))
        new = [t for t in (lt, rt) if t not in joined]
        total += sum(stored_rows[t] * width(named[t]) for t in new)
        joined += new
        total += join_rows[k] * width(carried(joined, k))
    return total
