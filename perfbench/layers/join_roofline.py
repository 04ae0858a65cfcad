"""Kernels: the share of its roofline at which a query's joins run.  Least
time: each join's two inputs read once at the columns the query names and
its matched rows written once (``roofline_join.join_bytes``: from the
configuration's schemas, the traffic's specification and the rows each
join returned in the reference, which the reference leaves in the
specification as ``join_rows_found`` when it checks an answer), over the
chip's HBM bytes/s; time: ``join_device_ms``.  Bound by bytes.  Source:
device trace."""

from perfbench import roofline, roofline_join
from perfbench.layers import join_device_ms


def read(run):
    secs = join_device_ms.seconds_per_query(run)
    cfg, spec = run["cfg"], run["traffic"].get("reference", {})
    found = spec.get("join_rows_found")
    if not secs or not found or "schemas" not in cfg:
        return None
    scale = run["state"]["rows"] / sum(cfg["tables"].values())
    stored = {t: int(n * scale) for t, n in cfg["tables"].items()}
    least = roofline.least_seconds(roofline_join.join_bytes(
        spec, cfg["schemas"], stored, found), run["device_kind"])
    return 100.0 * least / secs
