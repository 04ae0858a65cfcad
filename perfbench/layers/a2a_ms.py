"""Exchange (``parallel/shuffle.py``): summed device milliseconds of the
collective operations (``all-to-all`` and its kin by name) per query on
the device that spent most in them, median over the traced queries.  A
plain time.  Source: device trace."""

import statistics


def read(run):
    t = run["trace"]
    if not t or not t["real_device"]:
        return None
    c = t["collective_per_query_s"]
    if not c or max(c) <= 0:
        return None
    return statistics.median(c) * 1e3
