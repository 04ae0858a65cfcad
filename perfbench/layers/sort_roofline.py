"""Kernels: the share of its roofline at which a query's sort runs.  Least
time: every row one device sorts read once and written once (``roofline.
sort_bytes``) over the chip's HBM bytes/s; time: the seconds the busiest
device ran operations within one query (median over the traced queries),
which is the sort stage's programs and whatever else the query put on the
device, so the share cannot be flattered by leaving work out.  Bound by
bytes.  Source: device trace."""

import statistics

from perfbench import roofline


def read(run):
    t = run["trace"]
    if not t or not t["real_device"]:
        return None
    busy = [b for b in t["busy_per_query_s"] if b > 0]
    if not busy:
        return None
    row = roofline.device_row_bytes(run["cfg"]["schema"])
    least = roofline.least_seconds(roofline.sort_bytes(
        run["state"]["rows"] // run["chips"], row), run["device_kind"])
    return 100.0 * least / statistics.median(busy)
