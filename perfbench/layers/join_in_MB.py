"""Planner and executor (``sql/lower.py``, ``plan/planner.py``,
``exec/executor.py``): megabytes a query's join stages are handed: the sum
over its ``stage`` spans of the attribute ``join_in_bytes`` (capacity x
row bytes of a join's two inputs as the program holds them, after each
leg's projection and filter: static, from the traced shapes), median over
the window's queries.  It falls when the lowering prunes a column or the
planner picks a smaller capacity.  ``None`` where no span carries the
attribute (an older program, or a query with no join).  Source: program
counter."""

from perfbench import program_spans as ps


def read(run):
    def one(qrows):
        got = [r.attrs["join_in_bytes"] for r in qrows
               if r.kind == "stage" and "join_in_bytes" in r.attrs]
        return sum(got) / 1e6 if got else None
    return ps.median_per_query(run, one)
