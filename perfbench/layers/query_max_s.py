"""Whole query: the slowest query of the window.  Source: host clock."""


def read(run):
    q = [x["t1"] - x["t0"] for x in run["queries"]]
    return max(q) if q else None
