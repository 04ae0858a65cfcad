"""Kernels: the share of its roofline at which a query's scan, filter and
group-by run.  Least time: the columns the query names (the traffic
file's ``reference`` lists them), read once (``roofline.scan_bytes``),
over the chip's HBM bytes/s; time: the seconds the busiest device ran
operations within one query, median over the traced queries.  Bound by
bytes.  Source: device trace."""

import statistics

from perfbench import roofline
from perfbench.ref import relational


def read(run):
    t = run["trace"]
    if not t or not t["real_device"]:
        return None
    busy = [b for b in t["busy_per_query_s"] if b > 0]
    if not busy:
        return None
    spec = run["traffic"]["reference"]
    schema = run["cfg"]["schema"]
    cols = [c for c in schema if c in relational.columns_used(spec)]
    least = roofline.least_seconds(roofline.scan_bytes(
        run["state"]["rows"] // run["chips"], schema, cols),
        run["device_kind"])
    return 100.0 * least / statistics.median(busy)
