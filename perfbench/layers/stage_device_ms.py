"""Planner and executor (``exec/executor.py``): device milliseconds per
query of the stage programs, which the executor names ``jit_stage_<label>
_<op kinds>``: summed durations of the ``XLA Modules`` events of those
names on the busiest device over the traced queries.  What else a query
puts on the device (slices of the fetch, the shrink before a collect) is
not in it.  ``None`` off a real device.  Source: device trace."""

PREFIX = "jit_stage_"


def read(run):
    t = run["trace"]
    if not t or not t["real_device"] or not t["n_queries"]:
        return None
    secs = [s for n, s in t["modules"] if n.startswith(PREFIX)]
    if not secs:
        return None
    return sum(secs) / t["n_queries"] * 1e3
