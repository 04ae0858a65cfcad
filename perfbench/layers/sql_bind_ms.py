"""Front end (``sql/``): host milliseconds of ``sql.compile_query`` (parse
and bind), median over the window's queries.  Source: host clock."""

import statistics


def read(run):
    s = run["spans"].seconds("sql_bind")
    return statistics.median(s) * 1e3 if s else None
