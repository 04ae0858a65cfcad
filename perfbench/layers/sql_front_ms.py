"""Front end (``sql/``): host milliseconds of ``sql.query``'s own parse,
bind and lowering: the self time of the program's ``sql.parse``,
``sql.bind`` and ``sql.lower`` spans beneath ``sql.query`` (the catalog's
``from_store``, an eager store read, nests in ``sql.lower`` and is left
out); per query, median over the window.  Source: program span."""

from perfbench import program_spans as ps

NAMES = ("sql.parse", "sql.bind", "sql.lower")


def read(run):
    def mine(r, qrows):
        return r.name in NAMES and ps.descends_from(r, qrows, "sql.query")
    return ps.ms(ps.self_seconds_of(run, mine))
