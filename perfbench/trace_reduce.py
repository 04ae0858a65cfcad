"""From a profiler trace to numbers: device busy and idle, the device
operations that took most time, collective time, and the idle gaps laid to
the harness's own host spans.

``load`` turns an ``.xplane.pb`` into a plain structure (the same that
``selfcheck_trace.json`` holds, so the reduction is checked without a
profiler):

    {"planes": [{"name": ..., "lines": [{"name": ...,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

``reduce`` reads only that.  A device is a plane ``/device:TPU:<n>``; its
operations are the events of its ``XLA Ops`` line (``XLA Modules`` where
there is none).  In a CPU rehearsal there is no device plane and the XLA
CPU client's worker threads stand in, for the code path's sake only.
Host spans are the events ``perfbench:<name>`` of a host plane: the
harness keeps them on the wall clock and ``add_host_spans`` lays them into
the loaded trace as the plane ``/host:perfbench``, by the instant from
which the profiler counts (``profile_start_time``).
"""

from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "perfbench:"
COLLECTIVE_MARKS = ("all-to-all", "all-gather", "all-reduce",
                    "collective-permute", "reduce-scatter")


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    """The lines ``reduce`` reads, and of a host thread only the harness's
    own annotations (a host plane can hold millions of other events); and
    the wall-clock instant, in ns, from which the trace counts."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    planes = []
    start_ns = None
    for pl in pd.planes:
        for k, v in pl.stats:
            if k == "profile_start_time":
                start_ns = int(v)
    for pl in pd.planes:
        lines = []
        for ln in pl.lines:
            kind = keep_line(pl.name, ln.name)
            if not kind:
                continue
            ev = [[short_name(e.name), float(e.start_ns),
                   float(e.duration_ns)] for e in ln.events
                  if kind == "all" or e.name.startswith(SPAN_PREFIX)]
            if ev:
                lines.append({"name": ln.name, "events": ev})
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes, "profile_start_unix_ns": start_ns}


def add_host_spans(trace, rows):
    """Lay the harness's spans, ``(query, name, t0, t1)`` in seconds of
    the wall clock, into the trace as a host plane of its own."""
    base = trace.get("profile_start_unix_ns")
    if base is None:
        raise ValueError("the trace does not say when it started "
                         "(no profile_start_time)")
    ev = [[SPAN_PREFIX + n, t0 * 1e9 - base, (t1 - t0) * 1e9]
          for _q, n, t0, t1 in rows]
    trace["planes"].append({"name": "/host:perfbench",
                            "lines": [{"name": "spans", "events": ev}]})
    return trace


_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_KIND = re.compile(r"kind=(k[A-Za-z]+)")


def short_name(name):
    """On the TPU an operation's event carries its whole HLO line,
    ``%fusion.3 = u32[...]{...} fusion(...), kind=kLoop, calls=...``: keep
    the name, the opcode and the fusion's kind."""
    if not name.startswith("%") or " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    op = _OPCODE.search(" " + rest)
    kind = _KIND.search(rest)
    out = head[1:]
    if op and not out.startswith(op.group(1)):
        out += " " + op.group(1)
    if kind:
        out += " " + kind.group(1)
    return out


def dump(loaded, xplane_path, out_path):
    """Write the loaded lines and an index of every plane and line of the
    file (names and event counts) as gzipped JSON, to look at by hand."""
    import gzip
    import json
    import jax
    pd = jax.profiler.ProfileData.from_file(xplane_path)
    index = [[pl.name, [[ln.name, sum(1 for _ in ln.events)]
                        for ln in pl.lines]] for pl in pd.planes]
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with gzip.open(out_path, "wt") as f:
        json.dump({"xplane_bytes": os.path.getsize(xplane_path),
                   "index": index, "planes": loaded["planes"]}, f)


def keep_line(plane, line):
    """``"all"`` for a line whose every event ``reduce`` reads, ``"spans"``
    for one of which it reads the harness's annotations, else nothing."""
    if plane.startswith("/device:"):
        return "all" if line in ("XLA Ops", "XLA Modules") else None
    if plane.startswith("/host:"):
        return "all" if line.startswith(("tf_XLAPjRtCpuClient",
                                         "tf_XLAEigen")) else "spans"
    return None


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(merged, lo, hi):
    return [[max(s, lo), min(e, hi)] for s, e in merged
            if e > lo and s < hi]


def _total(merged):
    return sum(e - s for s, e in merged)


def _device_events(trace):
    """{device name: [[name, start, dur], ...]} of operations."""
    devs = {}
    for pl in trace["planes"]:
        if not pl["name"].startswith("/device:"):
            continue
        by = {ln["name"]: ln["events"] for ln in pl["lines"]}
        ev = by.get("XLA Ops") or by.get("XLA Modules")
        if ev:
            devs[pl["name"]] = ev
    if devs:
        return devs, True
    for pl in trace["planes"]:           # CPU rehearsal stand-in
        if not pl["name"].startswith("/host:"):
            continue
        ev = [e for ln in pl["lines"]
              if ln["name"].startswith(("tf_XLAPjRtCpuClient",
                                        "tf_XLAEigen"))
              for e in ln["events"]
              if e[2] > 0 and not e[0].startswith(("ThreadpoolListener",
                                                   "end: "))]
        if ev:
            devs["/host:CPU-as-device"] = ev
    return devs, False


def _modules(trace):
    out = {}
    for pl in trace["planes"]:
        if pl["name"].startswith("/device:"):
            for ln in pl["lines"]:
                if ln["name"] == "XLA Modules":
                    out[pl["name"]] = ln["events"]
    return out


def host_spans(trace):
    """[(name without prefix, start, end)] of the harness's annotations."""
    out = []
    for pl in trace["planes"]:
        if not pl["name"].startswith("/host:"):
            continue
        for ln in pl["lines"]:
            for name, s, d in ln["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((name[len(SPAN_PREFIX):], s, s + d))
    return sorted(out, key=lambda t: t[1])


def reduce(trace, chips=None):
    """The summary every per-layer reader of the trace takes its number
    from.  Times in seconds."""
    devs, real = _device_events(trace)
    spans = host_spans(trace)
    queries = [(s, e) for n, s, e in spans if n == "query"]
    if not queries:
        raise ValueError("trace holds no perfbench:query span")
    lo, hi = queries[0][0], max(e for _, e in queries)
    names = sorted(devs)
    if chips:
        names = names[:chips]
    per_dev = {}
    for d in names:
        merged = _clip(_union([(s, s + du) for _, s, du in devs[d]
                               if du > 0]), lo, hi)
        ops = {}
        for n, s, du in devs[d]:
            if s + du <= lo or s >= hi or du <= 0:
                continue
            ops[n] = ops.get(n, 0.0) + du
        per_dev[d] = {
            "merged": merged, "busy_s": _total(merged) / 1e9,
            "ops": ops,
            "busy_per_query_s": [
                _total(_clip(merged, qs, qe)) / 1e9 for qs, qe in queries],
            "collective_per_query_s": [
                sum(du for n, s, du in devs[d]
                    if qs <= s < qe and any(m in n
                                            for m in COLLECTIVE_MARKS))
                / 1e9 for qs, qe in queries]}
    if not per_dev:
        raise ValueError("trace holds no device operation")
    busiest = max(per_dev, key=lambda d: per_dev[d]["busy_s"])
    b = per_dev[busiest]
    top = sorted(b["ops"].items(), key=lambda kv: -kv[1])[:10]
    mods = _modules(trace).get(busiest, [])
    mod_s = {}
    for n, s, du in mods:
        if lo <= s < hi:
            mod_s[n] = mod_s.get(n, 0.0) + du / 1e9
    return {
        "real_device": real, "devices": names, "busiest": busiest,
        "window_s": (hi - lo) / 1e9, "n_queries": len(queries),
        "busy_s_mean": sum(p["busy_s"] for p in per_dev.values())
        / len(per_dev),
        "busy_s_busiest": b["busy_s"],
        "busy_per_query_s": b["busy_per_query_s"],
        "collective_per_query_s": max(
            (p["collective_per_query_s"] for p in per_dev.values()),
            key=sum),
        "device_ops": [[n, du / 1e9] for n, du in top],
        "modules": sorted(([n, s] for n, s in mod_s.items()),
                          key=lambda kv: -kv[1])[:10],
        "idle_gaps": _gaps(b["merged"], lo, hi, spans),
    }


def _gaps(merged, lo, hi, spans):
    """Idle seconds of the busiest device by the innermost harness span
    the host was in, largest first."""
    edges = [lo] + [t for iv in merged for t in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    inner = [(n, s, e) for n, s, e in spans if n != "query"]
    by = {}
    for gs, ge in gaps:
        left = ge - gs
        for n, s, e in inner:
            ov = min(ge, e) - max(gs, s)
            if ov > 0:
                by[n] = by.get(n, 0.0) + ov
                left -= ov
        if left > 0:
            by["between_spans"] = by.get("between_spans", 0.0) + left
    return [[n, s / 1e9] for n, s in
            sorted(by.items(), key=lambda kv: -kv[1])[:10]]
