"""Device: 1 - the union of the busiest device's operation intervals over
the traced window, which is a few steady queries.  Source: device
trace."""


def read(run):
    t = run["trace"]
    if not t or not t["real_device"] or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s_busiest"] / t["window_s"])
