"""Kernels (``ops/kernels.py``: ``hash_join``, ``_lookup_join``): device
milliseconds per query of the join stages' programs.  The executor names
a stage program ``jit_stage_<label>_<op kinds>``, so a join stage's holds
``join``: summed durations of the ``XLA Modules`` events of those names
on the busiest device over the traced queries.  The scans, filters and
exchanges of the stage's legs run in the same program and are in it.
``None`` off a real device or where no such program ran.  Source: device
trace."""

PREFIX = "jit_stage_"


def seconds_per_query(run):
    t = run["trace"]
    if not t or not t["real_device"] or not t["n_queries"]:
        return None
    secs = [s for n, s in t["modules"]
            if n.startswith(PREFIX) and "join" in n]
    return sum(secs) / t["n_queries"] if secs else None


def read(run):
    s = seconds_per_query(run)
    return None if s is None else s * 1e3
