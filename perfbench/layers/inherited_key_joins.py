"""Front end (``sql/binder.py``: ``_mark_unique``): joins a query ran with
the lookup kernel because their build side — what was joined so far —
kept a key through the joins before it (``o_orderkey`` of ``orders``
after ``orders`` probed a keyed ``customer``): the attribute
``inherited_unique_joins`` of the ``sql.lower`` span, median over the
window's queries.  ``None`` where no span carries the attribute (an older
program).  Source: program counter."""

from perfbench import program_spans as ps


def read(run):
    def one(qrows):
        got = [r.attrs["inherited_unique_joins"] for r in qrows
               if r.name == "sql.lower" and "inherited_unique_joins"
               in r.attrs]
        return float(sum(got)) if got else None
    return ps.median_per_query(run, one)
