"""Device seconds by the kernel scope an operation ran under.

The program puts the body of every kernel in a ``jax.named_scope`` of one
fixed vocabulary (``dryad_tpu/ops/kernels.py``'s docstring), and a device
operation's ``op_name`` is the path of scopes it was traced under.  On the
TPU that path is the ``tf_op`` stat (``<op_name>:``) of the operation's
event METADATA; ``jax.profiler.ProfileData`` hands out an event's own stats
only, so ``load`` reads the ``.xplane.pb`` itself, through a schema of the
few ``XSpace`` fields it needs built at run time (protobuf alone, no
TensorFlow), and reads each op name once.  It returns what
``trace_reduce.load`` returns for the device lines, with a list ``scopes``
beside ``events`` on the ``XLA Ops`` line.

``reduce`` sums **self** time — an op's duration less the union of the ops
nested in it on its line (a ``conditional`` or a ``while`` holds the ops of
its body, and the ``XLA Ops`` line lists both) — of the ops of the
``jit_stage_*`` programs that start inside the traced window, on one
device:

* a kernel scope (``KERNEL``): the innermost one on the op's path wins, so
  a gather inside a sort is ``row_gather`` and the sort's own ops
  ``index_sort``;
* a phase scope (``PHASE``): every op under it, at any depth;
* ``"(unscoped)"``: the ops under none of the ten; ``"(all)"``: every op.

A scope is a path component matched exactly (never ``jit(sort)`` or
``jit(_take)``); the path's last component names the op itself and is
never read as a scope.  A scope no op ran under has no key.
"""

from __future__ import annotations

import bisect
import functools
import os
import sys

from perfbench import trace_reduce

KERNEL = ("index_sort", "row_gather", "search", "prefix_sum",
          "group_aggregate", "compact", "hash_join", "lookup_join")
PHASE = ("exchange_pack", "exchange_unpack")
UNSCOPED = "(unscoped)"
ALL = "(all)"
STAGE_PREFIX = "jit_stage_"
OP_NAME_STAT = "tf_op"
_SCOPES = frozenset(KERNEL + PHASE)


def scopes_of(op_name):
    """The vocabulary's components of an op name, outermost first."""
    if not op_name:
        return []
    path = op_name.rsplit(":", 1)[0] if op_name.endswith(":") else op_name
    return [c for c in path.split("/")[:-1] if c in _SCOPES]


@functools.lru_cache(maxsize=1)
def _xspace_class():
    """``XSpace`` with the fields ``load`` reads; every other field is
    skipped by the parser as unknown.  Field numbers are those of
    ``tsl/profiler/protobuf/xplane.proto``."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(
        name="perfbench_xplane.proto", package="perfbench_xplane",
        syntax="proto2")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, num, typ, rep in fields:
            fd = m.field.add(name=fname, number=num,
                             label=F.LABEL_REPEATED if rep
                             else F.LABEL_OPTIONAL)
            if isinstance(typ, str):
                fd.type = F.TYPE_MESSAGE
                fd.type_name = ".perfbench_xplane." + typ
            else:
                fd.type = typ
    msg("XStat", ("metadata_id", 1, F.TYPE_INT64, False),
        ("uint64_value", 3, F.TYPE_UINT64, False),
        ("int64_value", 4, F.TYPE_INT64, False),
        ("str_value", 5, F.TYPE_STRING, False),
        ("ref_value", 7, F.TYPE_UINT64, False))
    msg("XEventMetadata", ("id", 1, F.TYPE_INT64, False),
        ("name", 2, F.TYPE_STRING, False), ("stats", 5, "XStat", True))
    msg("XStatMetadata", ("id", 1, F.TYPE_INT64, False),
        ("name", 2, F.TYPE_STRING, False))
    msg("XEvent", ("metadata_id", 1, F.TYPE_INT64, False),
        ("offset_ps", 2, F.TYPE_INT64, False),
        ("duration_ps", 3, F.TYPE_INT64, False))
    msg("XLine", ("name", 2, F.TYPE_STRING, False),
        ("timestamp_ns", 3, F.TYPE_INT64, False),
        ("events", 4, "XEvent", True))
    msg("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False),
        ("value", 2, "XEventMetadata", False))
    msg("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False),
        ("value", 2, "XStatMetadata", False))
    msg("XPlane", ("name", 2, F.TYPE_STRING, False),
        ("lines", 3, "XLine", True),
        ("event_metadata", 4, "EventMetadataEntry", True),
        ("stat_metadata", 5, "StatMetadataEntry", True),
        ("stats", 6, "XStat", True))
    msg("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("perfbench_xplane.XSpace"))


def _stat_value(st, stat_names):
    if st.HasField("str_value"):
        return st.str_value
    if st.HasField("ref_value"):
        return stat_names.get(st.ref_value)
    if st.HasField("int64_value"):
        return st.int64_value
    if st.HasField("uint64_value"):
        return st.uint64_value
    return None


def load(path):
    """The device planes' ``XLA Ops`` and ``XLA Modules`` lines, as
    ``trace_reduce.load`` gives them (an event's start is its line's
    ``timestamp_ns`` plus its offset), each op's scopes in ``scopes``
    and the op names of the ops under no scope in ``unscoped_paths``;
    and ``profile_start_unix_ns``."""
    with open(path, "rb") as f:
        space = _xspace_class().FromString(f.read())
    planes, start_ns = [], None
    for pl in space.planes:
        stat_names = {e.key: e.value.name for e in pl.stat_metadata}
        for st in pl.stats:
            if stat_names.get(st.metadata_id) == "profile_start_time":
                start_ns = int(_stat_value(st, stat_names))
        if not pl.name.startswith("/device:"):
            continue
        md = {e.key: e.value for e in pl.event_metadata}
        names, scopes = {}, {}
        lines = []
        for ln in pl.lines:
            if ln.name not in ("XLA Ops", "XLA Modules"):
                continue
            base = float(ln.timestamp_ns)
            ev, sc, paths = [], [], {}
            for e in ln.events:
                mid = e.metadata_id
                if mid not in names:
                    m = md.get(mid)
                    names[mid] = trace_reduce.short_name(m.name if m else "")
                    op = None
                    for st in (m.stats if m else ()):
                        if stat_names.get(st.metadata_id) == OP_NAME_STAT:
                            op = _stat_value(st, stat_names)
                    scopes[mid] = scopes_of(op)
                    if op and not scopes[mid]:
                        paths[names[mid]] = op.rstrip(":")
                ev.append([names[mid], base + e.offset_ps / 1e3,
                           e.duration_ps / 1e3])
                sc.append(scopes[mid])
            line = {"name": ln.name, "events": ev}
            if ln.name == "XLA Ops":
                line["scopes"] = sc
                line["unscoped_paths"] = paths
            lines.append(line)
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes, "profile_start_unix_ns": start_ns}


def self_ns(events):
    """Each event's duration less the union of the events nested in it
    (events ``[name, start, dur]`` of one line)."""
    out = [max(du, 0.0) for _, _, du in events]
    order = sorted((i for i in range(len(events)) if events[i][2] > 0),
                   key=lambda i: (events[i][1], -events[i][2]))
    stack = []                  # [index, end, covered up to]
    for i in order:
        s = events[i][1]
        e = s + events[i][2]
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            top = stack[-1]
            lo = max(s, top[2])
            hi = min(e, top[1])
            if hi > lo:
                out[top[0]] -= hi - lo
                top[2] = hi
        stack.append([i, e, s])
    return out


def reduce(trace, device, unscoped=None):
    """``{scope: seconds}`` of self time on ``device`` over the window of
    the trace's ``perfbench:query`` spans (see the module docstring);
    ``unscoped``, a dict, is given the unscoped ops' seconds by name
    (with the op's path where the trace has one)."""
    spans = trace_reduce.host_spans(trace)
    queries = [(s, e) for n, s, e in spans if n == "query"]
    if not queries:
        raise ValueError("trace holds no perfbench:query span")
    lo, hi = queries[0][0], max(e for _, e in queries)
    plane = next((p for p in trace["planes"] if p["name"] == device), None)
    by = {ln["name"]: ln for ln in (plane or {}).get("lines", ())}
    ops, mods = by.get("XLA Ops"), by.get("XLA Modules")
    if not ops or "scopes" not in ops or not mods:
        return {}
    stage = sorted((s, s + du) for n, s, du in mods["events"]
                   if n.startswith(STAGE_PREFIX))
    starts = [s for s, _ in stage]
    paths = ops.get("unscoped_paths", {})
    out = {}
    for (n, s, du), sc, own in zip(ops["events"], ops["scopes"],
                                   self_ns(ops["events"])):
        if du <= 0 or not lo <= s < hi:
            continue
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or s >= stage[k][1]:
            continue
        own /= 1e9
        keys = [ALL] + [c for c in PHASE if c in sc]
        kernel = next((c for c in reversed(sc) if c in KERNEL), None)
        if kernel:
            keys.append(kernel)
        elif not sc:
            keys.append(UNSCOPED)
            if unscoped is not None:
                key = f"{n} {paths[n]}" if n in paths else n
                unscoped[key] = unscoped.get(key, 0.0) + own
        for key in keys:
            out[key] = out.get(key, 0.0) + own
    return out


def _trace_dir(run):
    """The harness traces into ``<workdir>/trace``; every kind keeps its
    tables at ``<workdir>/<table>``."""
    tables = (run.get("state") or {}).get("tables") or {}
    for path in tables.values():
        if isinstance(path, str):
            return os.path.join(os.path.dirname(path), "trace")
    return None


def for_run(run):
    """``reduce`` of the run's traced window on its busiest device, read
    once a run; ``None`` off a real device or where the trace cannot be
    read (said on standard error: a reader never fails a run)."""
    t = run.get("trace")
    if not t or not t.get("real_device") or not t.get("n_queries"):
        return None
    if "scope_self_s" not in run:
        run["scope_self_s"] = None
        try:
            d = _trace_dir(run)
            if d is not None:
                loaded = load(trace_reduce.find_xplane(d))
                rows = [r for r in run["spans"].rows
                        if 0 <= r[0] < t["n_queries"]]
                trace_reduce.add_host_spans(loaded, rows)
                left = {}
                run["scope_self_s"] = reduce(loaded, t["busiest"], left)
                _report(run["scope_self_s"], left, t["n_queries"])
        except Exception as e:          # noqa: BLE001 — see docstring
            print(f"perfbench: kernel scopes not read: {e!r}",
                  file=sys.stderr)
    return run["scope_self_s"]


def _report(got, left, n):
    def ms(d, k=None):
        return {a: round(v / n * 1e3, 3) for a, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:k]}
    print(f"perfbench: scope self ms a query {ms(got)}; unscoped ops "
          f"{ms(left, 8)}", file=sys.stderr)


def ms_per_query(run, scope):
    """Self milliseconds a traced query under ``scope``, or ``None``."""
    got = for_run(run)
    if not got or scope not in got:
        return None
    return got[scope] / run["trace"]["n_queries"] * 1e3


def named_share(run):
    """The share of the stage programs' self time under any scope of the
    vocabulary, or ``None`` where no op ran under one."""
    got = for_run(run)
    if not got or not got.get(ALL) or \
            not any(k in got for k in KERNEL + PHASE):
        return None
    return 1.0 - got.get(UNSCOPED, 0.0) / got[ALL]
