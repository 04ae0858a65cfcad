"""User-facing lazy Dataset API + execution context.

The counterpart of the reference's `DryadLinqContext` (DryadLinqContext.cs:566)
and the `IQueryable` operator surface (DryadLinqQueryable.cs — Select/Where/
GroupBy/Join/OrderBy/Distinct/Union/.../HashPartition/RangePartition/Apply/
DoWhile/Take/Submit).  A Dataset wraps a logical expr node; terminal calls
(`collect`, `count`, ...) plan + execute.

`Context(local_debug=True)` is the reference's LocalDebug: terminal calls
route through the sequential oracle instead of the mesh executor — the same
semantics contract the reference tests rely on (SURVEY.md §4).
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Mapping, Optional, Sequence, Tuple

import numpy as np

from dryad_tpu import oracle as _oracle
from dryad_tpu.exec.data import PData, pdata_from_host, pdata_to_host
from dryad_tpu.exec.executor import Executor
from dryad_tpu.obs import trace
from dryad_tpu.parallel.mesh import make_mesh
from dryad_tpu.plan import expr as E
from dryad_tpu.plan.expr import Decomposable  # noqa: F401 (re-export)
from dryad_tpu.plan.planner import plan_query


def _const_key_like(cols):
    """A zero int32 key column matching the batch's row count (used by the
    whole-dataset ``aggregate`` terminal to form one global group)."""
    import jax.numpy as jnp

    v = next(iter(cols.values()))
    if hasattr(v, "lengths"):
        n = v.lengths.shape[0]
    elif hasattr(v, "shape"):
        n = v.shape[0]
    else:
        n = len(v)
    return jnp.zeros((n,), jnp.int32)


def _add_agg_key(cols):
    """Module-level (importable, hence cluster-shippable) agg-key mapper."""
    return dict(cols, __agg_key=_const_key_like(cols))

__all__ = ["Context", "Dataset"]


# ---------------------------------------------------------------------------
# stable query fingerprints (re-streaming cache keys, exec/ooc cache tier)

# fallback salt for values with no restart-stable identity (device data,
# opaque closures): cache entries keyed through it stay valid within
# THIS process — warm do_while iterations still hit — but a restarted
# job re-streams cold (conservative, never stale)
import itertools as _itertools
import uuid as _uuid

_PROCESS_SALT = _uuid.uuid4().hex

# id() reuse guard for the process-salt fingerprint fallback: a cached
# dataset keyed by id(obj) whose object is GC'd could alias a NEW object
# allocated at the same address — a stale HIT, the one thing the salt
# contract forbids.  Pin a monotonic sequence to each object via weakref
# instead; un-weakrefable objects get a fresh sequence per call (pure
# miss every time, never stale).
_LOCAL_ID_SEQ = _itertools.count()
_LOCAL_IDS: Dict[int, Any] = {}     # id -> (weakref, seq)


def _local_identity(v) -> str:
    import weakref
    ent = _LOCAL_IDS.get(id(v))
    if ent is not None and ent[0]() is v:
        return f"local:{_PROCESS_SALT}:{ent[1]}"
    seq = next(_LOCAL_ID_SEQ)
    try:
        def _drop(ref, k=id(v)):
            cur = _LOCAL_IDS.get(k)
            if cur is not None and cur[0] is ref:
                del _LOCAL_IDS[k]
        _LOCAL_IDS[id(v)] = (weakref.ref(v, _drop), seq)
    except TypeError:
        pass
    return f"local:{_PROCESS_SALT}:{seq}"


def _code_const_fp(c) -> str:
    """repr() of a const, except nested code objects (whose repr embeds
    a memory address — it would silently defeat restart-stable keys for
    any callable with an inner def/lambda/comprehension) recurse into
    bytecode + consts, and frozensets repr in sorted order (their
    iteration order is PYTHONHASHSEED-dependent)."""
    import types
    if isinstance(c, types.CodeType):
        inner = ",".join(_code_const_fp(x) for x in c.co_consts)
        return f"code({c.co_name},{c.co_code.hex()},[{inner}])"
    if isinstance(c, frozenset):
        return "frozenset{" + ",".join(sorted(map(repr, c))) + "}"
    if isinstance(c, tuple):
        return "(" + ",".join(_code_const_fp(x) for x in c) + ")"
    return repr(c)


def _stable_fn_fp(fn) -> Optional[str]:
    """Restart-stable identity of a user callable: module/qualname +
    bytecode + consts + hashable closure/default values.  None when the
    callable's behavior depends on values we cannot hash byte-exactly
    (bound objects, large arrays) — callers fall back to the process
    salt, which can only cause a cache MISS, never a stale hit."""
    import hashlib
    code = getattr(fn, "__code__", None)
    if code is None:
        return None
    parts = [getattr(fn, "__module__", "") or "", fn.__qualname__,
             code.co_code.hex()]
    try:
        parts.append(_code_const_fp(code.co_consts))
    except Exception:
        return None
    captured = []
    if getattr(fn, "__closure__", None):
        try:
            captured.extend(c.cell_contents for c in fn.__closure__)
        except ValueError:          # empty cell
            return None
    captured.extend(getattr(fn, "__defaults__", None) or ())
    for v in captured:
        if isinstance(v, (int, float, complex, str, bytes, bool,
                          type(None))):
            parts.append(repr(v))
        elif isinstance(v, np.ndarray) and v.nbytes <= (1 << 20):
            parts.append(hashlib.sha256(
                np.ascontiguousarray(v).tobytes()).hexdigest())
        else:
            return None
    return hashlib.sha256("|".join(parts).encode()).hexdigest()


def _stable_value_fp(v) -> str:
    import hashlib
    if callable(v):
        return _stable_fn_fp(v) or _local_identity(v)
    if isinstance(v, E.Decomposable):
        return "dec(" + ",".join(
            _stable_value_fp(getattr(v, part))
            for part in ("seed", "merge", "finalize")) + ")"
    if isinstance(v, np.ndarray):
        if v.nbytes <= (1 << 20):
            return hashlib.sha256(
                np.ascontiguousarray(v).tobytes()).hexdigest()
        return _local_identity(v)
    if isinstance(v, dict):
        return "{" + ",".join(
            f"{k!r}:{_stable_value_fp(v[k])}" for k in sorted(v)) + "}"
    if isinstance(v, (list, tuple)):
        return "[" + ",".join(_stable_value_fp(x) for x in v) + "]"
    if isinstance(v, (int, float, complex, str, bytes, bool,
                      type(None))):
        return repr(v)
    import dataclasses as _dc
    if _dc.is_dataclass(v) and not isinstance(v, type):
        inner = ",".join(
            f"{f.name}={_stable_value_fp(getattr(v, f.name))}"
            for f in _dc.fields(v))
        return f"{type(v).__name__}({inner})"
    return _local_identity(v)


def _stable_source_fp(data) -> str:
    """Content identity of a Source node's data.  Store-backed streams
    carry a fingerprint over path + per-partition checksums (set by
    ChunkSource.from_store / from_text), so changed SOURCE BYTES change
    the cache key; everything else degrades to the process salt."""
    from dryad_tpu.exec.stream_exec import StreamSource
    cs = data.cs if isinstance(data, StreamSource) else data
    fp = getattr(cs, "fingerprint", None)
    if fp:
        return fp
    spec = getattr(data, "spec", None)
    if isinstance(spec, dict) and spec.get("kind") == "store_stream":
        try:
            from dryad_tpu.io.store import store_meta
            meta = store_meta(spec["path"])
            import hashlib
            return hashlib.sha256(repr(
                ("store", spec["path"], meta.get("counts"),
                 meta.get("checksums"))).encode()).hexdigest()
        except Exception:
            pass
    return _local_identity(data)


def _stable_node_fp(root: E.Node) -> str:
    """Restart-stable structural fingerprint of a query DAG — the
    re-streaming cache key (exec/ooc cache tier).  Walks the logical
    nodes parents-first and hashes type + every dataclass field
    (callables by bytecode+captures, sources by content identity);
    anything unhashable folds in the per-process salt, so an uncertain
    key can only MISS across restarts, never serve a stale entry."""
    import dataclasses as _dc
    import hashlib
    parts = []
    ids: Dict[int, int] = {}
    for i, n in enumerate(E.walk(root)):
        ids[n.id] = i
        fields = []
        for f in _dc.fields(n):
            if f.name in ("parents", "host"):
                continue
            v = getattr(n, f.name)
            if f.name == "data":
                fields.append(f"data={_stable_source_fp(v)}"
                              if v is not None else "data=None")
            else:
                fields.append(f"{f.name}={_stable_value_fp(v)}")
        parents = ",".join(str(ids[p.id]) for p in n.parents)
        parts.append(f"{type(n).__name__}({parents})[{';'.join(fields)}]")
    return hashlib.sha256("\n".join(parts).encode()).hexdigest()


class Context:
    """Owns the mesh + executor and creates root Datasets."""

    def __init__(self, mesh=None, local_debug: bool = False,
                 event_log: Optional[Callable[[dict], None]] = None,
                 spill_dir: Optional[str] = None,
                 cluster=None, fn_table: Optional[Mapping[str, Any]] = None,
                 config=None, install_trace: bool = True):
        from dryad_tpu.utils.config import JobConfig
        self.cluster = cluster
        self.fn_table = dict(fn_table or {})
        self.local_debug = local_debug
        self.spill_dir = spill_dir
        self.config = config or JobConfig()
        from dryad_tpu.utils.compile_cache import enable_persistent_cache
        enable_persistent_cache(self.config.compilation_cache_dir)
        # route driver-side spans (IO provider reads, job submission)
        # into this context's event stream (obs/trace.py).  The sink is
        # process-global and the LATEST Context owns it — including a
        # log-less Context, which detaches the previous sink: a later
        # job's spans must never leak into an earlier job's JSONL.
        # ``install_trace=False`` opts out of that latest-owner model
        # entirely: the multi-tenant service daemon builds Contexts for
        # plan/lint work with fully explicit per-job sinks, and must not
        # detach whatever sink the embedding process installed.
        if install_trace:
            from dryad_tpu.obs import trace as _trace
            _trace.install(event_log)
        # job-history archiving (obs/history.py): JobConfig.history_dir
        # makes the attached EventLog archive this job's {events, plan,
        # metrics, bundles} on close; an explicit EventLog(history_dir=)
        # wins over the config knob
        if (self.config.history_dir and event_log is not None
                and getattr(event_log, "history_dir", "absent") is None):
            event_log.history_dir = self.config.history_dir
        if cluster is not None:
            # multi-process mode (runtime.LocalCluster): the driver owns no
            # devices; plans + deferred sources ship to the worker gang
            # (LocalJobSubmission.cs:97-302 parity).  Workers build a 2-D
            # (dcn, dp) mesh with dcn = the process boundary.
            self.mesh = None
            self.nparts = cluster.nparts
            self.hosts = (cluster.n_processes
                          if cluster.n_processes > 1 else 1)
            self.levels = (("dp", "dcn") if self.hosts > 1 else ())
            self.executor = None
            self._event_log = event_log
            self._token_seq = 0
            # token -> producing plan node: a gang restart wipes resident
            # state, so a query touching a lost token re-materializes it
            # from lineage and retries (replay-based fault tolerance,
            # SURVEY.md §3.5)
            self._resident_producers: Dict[str, Any] = {}
            return
        self._event_log = event_log
        self.mesh = mesh if mesh is not None else make_mesh()
        self.nparts = self.mesh.devices.size
        # multi-level meshes trigger hierarchical aggregation plans; the
        # planner's level chain is the mesh's axes innermost-first
        # (2-D: dp -> dcn; 3-D: dp -> host -> dcn)
        self.hosts = (self.mesh.devices.shape[0]
                      if len(self.mesh.axis_names) >= 2 else 1)
        self.levels = (tuple(reversed(self.mesh.axis_names))
                       if len(self.mesh.axis_names) >= 2 else ())
        self.executor = Executor(self.mesh, event_log=event_log,
                                 config=self.config)

    # -- pre-submit static analysis (dryad_tpu/analysis) --------------------

    def _pre_submit_lint(self, node, cluster: bool, graph=None):
        """JobConfig.lint gate: verify the plan + lint its UDFs BEFORE any
        executor/cluster work starts (the reference's phase-1 static
        validation point, DryadLinqQueryGen.cs).  "warn" logs findings to
        the EventLog; "error" refuses to submit on error-severity
        findings (analysis.LintError).

        With ``graph`` (the already-planned StageGraph — planning is
        deterministic, so it matches what the executor runs) the static
        COST pass also runs (analysis/cost.py): per-stage row/byte
        predictions from real source statistics, DTA2xx OOM/spill
        forecasts against ``JobConfig.device_hbm_bytes``, and a
        ``cost_report`` event whose machine-readable payload the
        executor cross-checks at runtime (``cost_model_miss``).
        Returns the CostReport (or None)."""
        mode = getattr(self.config, "lint", "off")
        if mode == "off":
            return None
        from dryad_tpu.analysis import LintError, check_plan
        report = check_plan(node, cluster=cluster, fn_table=self.fn_table)
        cost_rep = None
        if graph is not None:
            from dryad_tpu.analysis.cost import (cost_diagnostics,
                                                 estimate_graph)
            try:
                cost_rep = estimate_graph(graph, self.nparts,
                                          config=self.config)
                report.diagnostics.extend(
                    cost_diagnostics(cost_rep, self.config))
            except Exception as e:
                # the cost model must never turn a runnable job into a
                # crashed one — skip it loudly (DTA200) and submit
                cost_rep = None
                report.add("DTA200", "info",
                           f"cost analyzer failed ({e!r}) — cost pass "
                           f"skipped", node="cost")
        report.dedup()
        ev = self._event_log
        if ev is not None:
            for d in report:
                ev({"event": "lint_finding", "code": d.code,
                    "severity": d.severity, "message": d.message,
                    "node": d.node,
                    "span": str(d.span) if d.span else None})
            if cost_rep is not None:
                ev({"event": "cost_report",
                    "report": cost_rep.to_payload()})
        if mode == "error" and report.errors:
            raise LintError(report)
        return cost_rep

    # -- cluster submission -------------------------------------------------

    def _cluster_run(self, node, collect: bool = True,
                     store_path: Optional[str] = None,
                     store_partitioning: Optional[Dict[str, Any]] = None,
                     keep_token: Optional[str] = None,
                     want_reply: bool = False,
                     store_compression: Optional[str] = None,
                     lint: bool = True):
        """Plan, serialize, and submit one query to the worker gang.
        Returns the host table (default) or, with ``want_reply``, worker
        0's full reply (resident-cache metadata included).  Queued token
        releases from dropped cached Datasets piggyback on every job."""
        from dryad_tpu.runtime import ClusterJobError, WorkerFailure
        from dryad_tpu.runtime.shiplan import serialize_for_cluster
        graph = plan_query(node, self.nparts, hosts=self.hosts,
                           levels=self.levels,
                           config=self.config)
        if lint:
            # plan first so the lint gate's cost pass sees the lowered
            # graph (pure host work — still zero cluster resources)
            self._pre_submit_lint(node, cluster=True, graph=graph)
        plan_json, specs = serialize_for_cluster(graph, self.fn_table)
        # route worker events to THIS context's logger for the duration of
        # the job (several Contexts may share one cluster)
        prev_log = self.cluster.event_log
        self.cluster.event_log = self._event_log
        replayed = False
        try:
            for heal in range(8):   # bound resident-healing retries
                try:
                    reply = self.cluster.execute(
                        plan_json, specs, collect=collect,
                        store_path=store_path,
                        store_partitioning=store_partitioning,
                        config=self.config,
                        timeout=self.config.cluster_job_timeout_s,
                        keep_token=keep_token,
                        store_compression=store_compression)
                    break
                except WorkerFailure:
                    # a wedged/dead worker tore the gang down (straggler
                    # watchdog or process death): the job is
                    # deterministic from its sources — replay ONCE on a
                    # fresh gang (lineage replay, SURVEY.md §3.5; any
                    # resident references heal below on the retry)
                    if replayed or heal == 7:
                        raise
                    replayed = True
                except ClusterJobError as e:
                    tok = self._lost_resident_token(e)
                    if tok is None or heal == 7:
                        raise
                    # a gang restart wiped this resident: re-materialize
                    # it from its producing plan, then retry the query
                    # (recursively heals chained residents)
                    self._cluster_run(self._resident_producers[tok],
                                      collect=False, keep_token=tok)
        finally:
            self.cluster.event_log = prev_log
        return reply if want_reply else reply.get("table")

    def _lost_resident_token(self, err) -> Optional[str]:
        """Healable token from a lost-resident job error, if its producer
        is registered.  The token arrives as STRUCTURED data on the
        exception (ClusterJobError.missing_token, set from the worker
        reply's ``missing_token`` field — runtime/worker.py
        _tag_missing_token), never parsed out of traceback text."""
        tok = getattr(err, "missing_token", None)
        if tok is not None and tok in self._resident_producers:
            return tok
        return None

    # -- cluster-resident intermediates ------------------------------------

    def _fresh_token(self, tag: str) -> str:
        self._token_seq += 1
        return f"__{tag}_{id(self):x}_{self._token_seq}"

    def _resident_dataset(self, token: str, capacity: int,
                          partitioning: E.Partitioning =
                          E.Partitioning.none(),
                          producer: Any = None) -> "Dataset":
        """Dataset over a cluster-resident intermediate: queries ship only
        the token.  When the Dataset's source node is garbage-collected,
        the token is queued on the CLUSTER's release list (piggybacked on
        the next job from ANY Context — the gang holds the device memory,
        so the queue must outlive this Context).  ``producer`` (the plan
        node that computed it) makes the resident survive gang restarts:
        a token miss re-materializes from lineage."""
        import weakref

        from dryad_tpu.runtime.sources import DeferredSource
        node = E.Source(parents=(), data=DeferredSource(
            {"kind": "resident", "token": token, "capacity": capacity}),
            _npartitions=self.nparts, _partitioning=partitioning)
        if producer is not None:
            self._resident_producers[token] = producer
            weakref.finalize(node, self._resident_producers.pop, token,
                             None)
        weakref.finalize(node, self.cluster.pending_release.append, token)
        return Dataset(self, node)

    # -- re-streaming cache plumbing (exec/ooc cache tier) ------------------

    def _ooc_cache_root(self) -> str:
        """Root directory for re-streaming cache entries:
        ``JobConfig.ooc_cache_dir`` (persistent — a restarted job with an
        intact cache dir skips the cold pass) or a lazily created
        per-Context temp dir removed at Context GC.  A REMOTE
        ``ooc_cache_dir`` (scheme://) falls through to the temp dir:
        entry sidecars are written with local file semantics, and
        naively os.makedirs-ing the URL would split-brain the entry
        (data remote, sidecar in a literal local 'scheme:/...' dir)."""
        if self.config.ooc_cache_dir and "://" not in \
                self.config.ooc_cache_dir:
            os.makedirs(self.config.ooc_cache_dir, exist_ok=True)
            return self.config.ooc_cache_dir
        root = getattr(self, "_ooc_cache_tmp", None)
        if root is None:
            import shutil
            import tempfile
            import weakref
            root = tempfile.mkdtemp(prefix="dryad-ooc-cache-",
                                    dir=self.spill_dir)
            weakref.finalize(self, shutil.rmtree, root,
                             ignore_errors=True)
            self._ooc_cache_tmp = root
        return root

    def _cache_event(self):
        """Event sink for ooc cache lifecycle records: forwards to the
        Context's log AND keeps the live ``dryad_ooc_cache_hits_total``
        counter current (the derived mirror counts the same events)."""
        sink = self._event_log

        def ev(e):
            kind = e.get("event")
            if kind in ("ooc_cache_hit", "ooc_cache_write"):
                from dryad_tpu.obs.metrics import (REGISTRY,
                                                   family_counter)
                family_counter(
                    REGISTRY,
                    "ooc_cache_hits" if kind == "ooc_cache_hit"
                    else "ooc_cache_writes").inc()
            if sink is not None:
                sink(e)
        return ev

    # -- dataset constructors ---------------------------------------------

    def from_columns(self, columns: Mapping[str, Any],
                     capacity: int | None = None,
                     str_max_len: int | None = None) -> "Dataset":
        """Create a partitioned dataset from host columns (FromEnumerable,
        DryadLinqContext.cs:1210)."""
        str_max_len = str_max_len or self.config.string_max_len
        if self.cluster is not None:
            from dryad_tpu.runtime.sources import (DeferredSource,
                                                   columns_spec)
            spec = columns_spec(columns, self.nparts, capacity=capacity,
                                str_max_len=str_max_len)
            node = E.Source(parents=(), data=DeferredSource(spec),
                            _npartitions=self.nparts, host=dict(columns))
            return Dataset(self, node)
        pdata = pdata_from_host(columns, self.mesh, nparts=self.nparts,
                                capacity=capacity, str_max_len=str_max_len)
        node = E.Source(parents=(), data=pdata, _npartitions=self.nparts,
                        host=dict(columns))
        return Dataset(self, node)

    def from_pdata(self, pdata: PData,
                   host: Optional[Mapping[str, Any]] = None,
                   partitioning: E.Partitioning = E.Partitioning.none()
                   ) -> "Dataset":
        node = E.Source(parents=(), data=pdata, _npartitions=self.nparts,
                        _partitioning=partitioning, host=host)
        return Dataset(self, node)

    def read_text(self, path, column: str = "line",
                  max_line_len: int | None = None) -> "Dataset":
        """Read text as one record per line (FromStore for LineRecord,
        DryadLinqContext.cs:1176 + LineRecord.cs).  ``path`` may be a single
        file, a glob pattern, a directory, or a list of those — multi-file
        inputs are enumerated and packed in parallel (DrPartitionFile
        input-partition enumeration, DataPath.cs:124).  Line splitting +
        padding runs in the native IO engine when built."""
        from dryad_tpu.io.providers import expand_paths, read_text_files
        max_line_len = max_line_len or self.config.text_max_line_len
        paths = expand_paths(path)
        if self.cluster is not None:
            from dryad_tpu.runtime.sources import DeferredSource, text_spec
            spec = text_spec(paths, self.nparts, column=column,
                             max_line_len=max_line_len)
            node = E.Source(parents=(), data=DeferredSource(spec),
                            _npartitions=self.nparts)
            return Dataset(self, node)
        from dryad_tpu.exec.data import pdata_from_packed_strings
        data, lens, _ = read_text_files(paths, max_line_len)
        pdata = pdata_from_packed_strings(data, lens, self.mesh,
                                          column=column)
        host = {column: [bytes(r[:l]) for r, l in
                         zip(data, lens)]} if self.local_debug else None
        return self.from_pdata(pdata, host=host)

    # -- streamed (out-of-core) sources ------------------------------------

    def from_stream(self, source) -> "Dataset":
        """Wrap an exec.ooc.ChunkSource as a streamed Dataset: the query
        plans with one logical partition and executes over chunk streams
        (exec/stream_exec.py) — device working set stays O(chunk_rows)
        no matter the total data size (the reference's transparent
        bounded-memory channels, channelbufferqueue.cpp:777)."""
        from dryad_tpu.exec.stream_exec import StreamSource
        if self.cluster is not None:
            # FromEnumerable parity (DryadLinqContext.cs:1210): a
            # driver-side generator cannot execute on workers, so the
            # client SPOOLS the stream into a store the workers can
            # reach (JobConfig.cluster_stream_spool_dir — shared fs or
            # hdfs://; s3:// is rejected, no atomic chunk-stream commit;
            # default: a driver temp dir, valid for single-machine
            # clusters) and the gang streams the store through the full
            # planned surface (runtime/stream_plan.py).
            import tempfile
            import uuid

            from dryad_tpu.exec.ooc import write_chunks_to_store
            root = (self.config.cluster_stream_spool_dir
                    or tempfile.mkdtemp(prefix="dryad-spool-"))
            path = os.path.join(root, f"stream-{uuid.uuid4().hex[:10]}")                 if "://" not in root else                 root.rstrip("/") + f"/stream-{uuid.uuid4().hex[:10]}"
            write_chunks_to_store(path, iter(source), source.schema)
            return self.read_store_stream(path,
                                          chunk_rows=source.chunk_rows)
        node = E.Source(parents=(), data=StreamSource(source),
                        _npartitions=1)
        return Dataset(self, node)

    def read_store_stream(self, path: str,
                          chunk_rows: int | None = None,
                          columns: Sequence[str] | None = None):
        """Stream a persisted store through the plain Dataset API —
        the >HBM path (1 TB TeraSort north star, BASELINE.md config 2).
        ``columns``: stream the named stored columns only (in-process; a
        cluster's workers stream whole partitions).

        On a cluster Context this is an ORDINARY Dataset too: the query
        plans through the normal lowering (exchanges included) and the
        gang executes it as chunk waves + per-device bucket streams
        (runtime/stream_plan.py) — the full operator surface, not a
        restricted mini-API (VERDICT r3 item 3)."""
        cr = chunk_rows or self._auto_chunk_rows(path) \
            or self.config.ooc_chunk_rows
        if self.cluster is not None:
            from dryad_tpu.runtime.sources import DeferredSource
            spec = {"kind": "store_stream", "path": path,
                    "chunk_rows": cr, "capacity": cr}
            node = E.Source(parents=(), data=DeferredSource(spec),
                            _npartitions=self.nparts)
            return Dataset(self, node)
        from dryad_tpu.exec.ooc import ChunkSource
        cs = ChunkSource.from_store(path, cr, columns=columns)
        return self.from_stream(cs)

    def _auto_chunk_rows(self, store_path: str) -> int | None:
        """Measured chunk sizing (JobConfig.ooc_chunk_autotune): row
        width from the store's schema, link rate + dispatch floor from a
        one-time probe (exec/autotune)."""
        if not getattr(self.config, "ooc_chunk_autotune", False):
            return None
        try:
            from dryad_tpu.exec.autotune import pick_chunk_rows
            from dryad_tpu.io.store import part_layout, store_meta
            layout = part_layout(store_meta(store_path)["schema"])
            # 32-bit lanes a row: a string's bytes pack four to a lane
            lanes = sum(-(-leaf.row_bytes // 4) if leaf.str_part == 0
                        else max(1, leaf.dtype.itemsize // 4)
                        * (leaf.row_bytes // leaf.dtype.itemsize)
                        for leaf in layout)
            return pick_chunk_rows(sum(leaf.row_bytes for leaf in layout),
                                   self.config, row_lanes=lanes)
        except Exception:
            return None   # sizing is a heuristic; never fail the query

    def read_text_stream(self, path, column: str = "line",
                         chunk_rows: int | None = None,
                         max_line_len: int | None = None) -> "Dataset":
        """Stream text files line by line (never holds a file in memory)."""
        from dryad_tpu.exec.ooc import ChunkSource
        from dryad_tpu.io.providers import expand_paths
        cs = ChunkSource.from_text(
            expand_paths(path),
            chunk_rows or self.config.ooc_chunk_rows,
            max_line_len or self.config.text_max_line_len, column)
        return self.from_stream(cs)

    def read(self, uri: str, **kw) -> "Dataset":
        """URI-scheme dispatch (DataProvider.cs / concreterchannel.cpp:44-49):
        ``file://`` text, ``store://`` partitioned store, ``http://``
        ranged reads, ``s3://`` objects, ``hdfs://`` WebHDFS
        (io/webhdfs.py — DrHdfsClient.cpp role), plus any scheme
        registered via io.providers.register_provider."""
        from dryad_tpu.io.providers import open_uri
        return open_uri(self, uri, **kw)

    def from_store(self, path: str, capacity: int | None = None,
                   columns: Sequence[str] | None = None) -> "Dataset":
        """Load a persisted dataset (FromStore, DryadLinqContext.cs:1176).
        Persisted partitioning metadata is honored for shuffle elimination
        (AssumeHashPartition parity, DryadLinqQueryable.cs:3408).
        ``path`` may be local, ``s3://``, or ``hdfs://`` (io/store.py
        scheme dispatch); the same goes for ``read_store_stream`` and
        ``to_store``.

        ``columns`` (stored column names; None = all): only those columns
        are fetched from the store, verified (by their leaf digests),
        stacked and put on the device (``io/store.read_parts``), in-core
        and streamed; a partitioning claim survives only if all its keys
        are among them.  Left whole: a cluster's ``DeferredSource`` —
        its workers read whole partitions, and what a query does not name
        is pruned on the device as before."""
        from dryad_tpu.io.store import read_store, store_meta
        # the read is eager, so it is a query of its own in the trace
        with trace.span("from_store", "query", source=path):
            meta = store_meta(path)
            auto = self.config.ooc_auto_stream_rows
            if (auto and self.cluster is None
                    and sum(meta.get("counts", [])) >= auto):
                # size-threshold streaming: a big store never tries to fit
                # in HBM (VERDICT r2 next-round item 1)
                return self.read_store_stream(path, columns=columns)
            pmeta = meta.get("partitioning", {"kind": "none"})
            part = E.Partitioning(pmeta.get("kind", "none"),
                                  tuple(pmeta.get("keys", ())))
            # re-blocking across a different mesh size destroys hash
            # placement; so does leaving a key of it unread
            if meta["npartitions"] != self.nparts or (
                    columns is not None and self.cluster is None
                    and not set(part.keys) <= set(columns)):
                part = E.Partitioning.none()
            if self.cluster is not None:
                from dryad_tpu.runtime.sources import (DeferredSource,
                                                       store_spec)
                spec = store_spec(path, self.nparts, meta,
                                  capacity=capacity)
                node = E.Source(parents=(), data=DeferredSource(spec),
                                _npartitions=self.nparts,
                                _partitioning=part)
                return Dataset(self, node)
            pdata = read_store(path, self.mesh, capacity=capacity,
                               verify=self.config.store_verify_checksums,
                               columns=columns)
            return self.from_pdata(pdata, partitioning=part)

    # -- iteration ---------------------------------------------------------

    def do_while(self, init: "Dataset",
                 body: Callable[["Dataset"], "Dataset"],
                 n_iters: int,
                 cond: Optional[Callable[[Dict[str, Any]], bool]] = None
                 ) -> "Dataset":
        """Iterative DAG execution (reference DoWhile,
        DryadLinqQueryable.cs:1281, VisitDoWhile DryadLinqQueryGen.cs:3353).

        The loop body is planned ONCE over a placeholder; each iteration
        binds the previous iteration's materialized output, so XLA programs
        are compiled once and reused (shapes are stable).  ``cond`` (host
        predicate on the collected current table) can stop early.
        """
        if n_iters > self.config.max_loop_iterations:
            raise ValueError(
                f"n_iters={n_iters} exceeds JobConfig.max_loop_iterations="
                f"{self.config.max_loop_iterations}; raise the knob "
                f"explicitly for longer loops")
        if self.cluster is not None:
            # iterate by re-submitting the planned body with the previous
            # iteration's output held CLUSTER-RESIDENT under a token —
            # only the plan + token cross the driver socket per iteration,
            # never the table (the reference keeps loop-carried data as
            # cluster-resident temp outputs read in place,
            # GraphManager/vertex/DrVertex.h:325-351; VERDICT r2 item 4).
            # The body plan's fingerprints are identical every round, so
            # workers (persistent executors, runtime/exec_common.py)
            # compile each stage once.  ``cond`` still collects the table
            # each round — it is a host predicate on the full table.
            import dataclasses as _dc

            from dryad_tpu.runtime import ClusterJobError, WorkerFailure
            from dryad_tpu.runtime.sources import DeferredSource

            ph = E.Placeholder(parents=(), name="__loop",
                               _npartitions=self.nparts)
            body_node = body(Dataset(self, ph)).node

            def subst(node, token, cap):
                if isinstance(node, E.Placeholder) and node.name == "__loop":
                    return E.Source(parents=(), data=DeferredSource(
                        {"kind": "resident", "token": token,
                         "capacity": cap}), _npartitions=self.nparts)
                new_parents = tuple(subst(p, token, cap)
                                    for p in node.parents)
                if new_parents == node.parents:
                    return node
                return _dc.replace(node, parents=new_parents)

            def run_loop():
                token = self._fresh_token("loop")
                try:
                    reply = self._cluster_run(init.node, collect=False,
                                              keep_token=token,
                                              want_reply=True)
                    cap = reply["resident_capacity"]
                    for it in range(n_iters):
                        # the body plan is structurally identical every
                        # round (subst only swaps the placeholder for the
                        # resident token): lint it ONCE, not per iteration
                        reply = self._cluster_run(
                            subst(body_node, token, cap),
                            collect=cond is not None, keep_token=token,
                            want_reply=True, lint=it == 0)
                        cap = reply["resident_capacity"]
                        if cond is not None and not cond(reply["table"]):
                            break
                    return token, cap
                except BaseException:
                    # the abandoned token must not pin a dataset-sized
                    # PData in surviving workers
                    self.cluster.pending_release.append(token)
                    raise

            try:
                token, cap = run_loop()
            except WorkerFailure:
                # a gang restart loses resident state; the loop is
                # deterministic from its sources — replay once from init
                # (lineage replay, SURVEY.md §3.5).  Deterministic job
                # errors (bad UDF etc.) propagate — re-running cannot fix
                # them.
                token, cap = run_loop()
            except ClusterJobError as e:
                # structured lost-resident tag (never message text)
                if e.missing_token is None:
                    raise
                token, cap = run_loop()
            return self._resident_dataset(token, cap)
        if self.local_debug:
            cur_host = _oracle.run_oracle(init.node)
            ph = E.Placeholder(parents=(), name="__loop",
                               _npartitions=self.nparts)
            body_node = body(Dataset(self, ph)).node
            for _ in range(n_iters):
                cur_host = _oracle.run_oracle(
                    body_node, bindings={"__loop": cur_host})
                if cond is not None and not cond(cur_host):
                    break
            node = E.Source(parents=(), data=None,
                            _npartitions=self.nparts, host=cur_host)
            return Dataset(self, node)
        probe_ph = E.Placeholder(parents=(), name="__loop",
                                 _npartitions=self.nparts, capacity=1)
        if (init._streaming()
                or body(Dataset(self, probe_ph))._streaming()):
            # streamed (>RAM) loop body on the single-process path: the
            # loop STATE is a small host table (ranks / centroids); the
            # body references stream sources (edges at 10x HBM) and
            # re-executes through the streamed engine every superstep —
            # re-reading its >RAM inputs from the store or, with
            # .cache(), the local re-streaming chunk cache.  This is the
            # iteration story Known-limit #3 was missing: loop-invariant
            # >HBM inputs now iterate with device working set
            # O(chunk_rows).
            cur_host = init.collect()
            for _ in range(n_iters):
                prev = self.from_columns(cur_host)
                cur_host = body(prev).collect()
                if cond is not None and not cond(cur_host):
                    break
            return self.from_columns(cur_host)
        cur = init._materialize()
        ph = E.Placeholder(parents=(), name="__loop", _npartitions=self.nparts,
                           capacity=cur.capacity)
        body_ds = body(Dataset(self, ph))
        graph = plan_query(body_ds.node, self.nparts,
                           hosts=self.hosts, levels=self.levels)
        for _ in range(n_iters):
            nxt = self.executor.run(graph, bindings={"__loop": cur})
            if nxt.capacity != cur.capacity:
                raise ValueError(
                    "do_while body must preserve per-partition capacity "
                    f"({cur.capacity} -> {nxt.capacity}); use explicit "
                    "capacities on flat_map/join ops inside the loop")
            cur = nxt
            if cond is not None and not cond(pdata_to_host(cur)):
                break
        return self.from_pdata(cur, host=None)


class Dataset:
    """A lazy, partitioned, columnar dataset (the IQueryable)."""

    def __init__(self, ctx: Context, node: E.Node):
        self.ctx = ctx
        self.node = node

    # -- row-local operators ----------------------------------------------

    def select(self, fn: Callable[[Dict[str, Any]], Dict[str, Any]],
               label: str = "select") -> "Dataset":
        """Columnwise projection: fn(cols) -> new cols (replaces columns)."""
        return Dataset(self.ctx, E.Map(parents=(self.node,), fn=fn,
                                       label=label))

    def where(self, fn: Callable[[Dict[str, Any]], Any],
              label: str = "where") -> "Dataset":
        return Dataset(self.ctx, E.Filter(parents=(self.node,), fn=fn,
                                          label=label))

    def split_words(self, column: str, out_capacity: int,
                    max_token_len: int | None = None,
                    delims: bytes | None = None,
                    lower: bool = False,
                    max_tokens_per_row: int | None = None) -> "Dataset":
        """Tokenizing SelectMany (the WordCount flat-map).  Token length
        and delimiter defaults come from JobConfig (token_max_len,
        token_delims + punctuation)."""
        cfg = self.ctx.config
        if max_token_len is None:
            max_token_len = cfg.token_max_len
        if delims is None:
            delims = cfg.token_delims
        return Dataset(self.ctx, E.FlatTokens(
            parents=(self.node,), column=column, out_capacity=out_capacity,
            max_token_len=max_token_len, delims=delims, lower=lower,
            max_tokens_per_row=max_tokens_per_row))

    def apply_per_partition(self, fn, label: str = "apply",
                            preserves_partitioning: bool = False,
                            host_fn=None) -> "Dataset":
        """Arbitrary Batch -> Batch function per partition
        (ApplyPerPartition, DryadLinqQueryable.cs:1084).  Provide host_fn
        (table -> table) to make it interpretable by the oracle."""
        return Dataset(self.ctx, E.ApplyPerPartition(
            parents=(self.node,), fn=fn, label=label,
            preserves_partitioning=preserves_partitioning, host_fn=host_fn))

    def apply_with_partition_index(self, fn, label: str = "apply_idx"
                                   ) -> "Dataset":
        """fn(batch, partition_index) -> Batch (ApplyWithPartitionIndex,
        DryadLinqQueryable.cs:1356)."""
        return Dataset(self.ctx, E.ApplyPerPartition(
            parents=(self.node,), fn=fn, label=label, with_index=True))

    def flat_map(self, fn, out_capacity: int,
                 label: str = "flat_map") -> "Dataset":
        """Generic SelectMany: fn(cols) -> (out_cols [cap, m, ...],
        mask [cap, m]); flattened row-major."""
        return Dataset(self.ctx, E.FlatMap(
            parents=(self.node,), fn=fn, out_capacity=out_capacity,
            label=label))

    def zip_with(self, other: "Dataset", suffix: str = "_r") -> "Dataset":
        """Positional pairing by global row index (LINQ Zip).  Sides with
        differing per-partition counts are realigned via an exchange."""
        return Dataset(self.ctx, E.Zip(parents=(self.node, other.node),
                                       suffix=suffix))

    def sliding_window(self, w: int) -> "Dataset":
        """Windows of w consecutive rows (SlidingWindow,
        DryadLinqQueryable.cs:1318); columns gain a window axis."""
        return Dataset(self.ctx, E.SlidingWindow(parents=(self.node,), w=w))

    def with_row_index(self, column: str = "row_index") -> "Dataset":
        """Add a global row-index column (Long*/indexed operator parity)."""
        return Dataset(self.ctx, E.WithRowIndex(parents=(self.node,),
                                                column=column))

    def skip(self, n: int) -> "Dataset":
        return Dataset(self.ctx, E.SkipTake(parents=(self.node,), op="skip",
                                            n=n))

    def take_while(self, fn) -> "Dataset":
        return Dataset(self.ctx, E.SkipTake(parents=(self.node,),
                                            op="take_while", fn=fn))

    def skip_while(self, fn) -> "Dataset":
        return Dataset(self.ctx, E.SkipTake(parents=(self.node,),
                                            op="skip_while", fn=fn))

    def fork_by(self, fn) -> Tuple["Dataset", "Dataset"]:
        """Split one scan into (matching, non-matching) branches (Fork,
        DryadLinqQueryable.cs:3717); the shared parent is materialized once
        (Tee)."""
        t = self.where(fn, label="fork_t")
        f = self.where(lambda c, _fn=fn: ~_fn(c), label="fork_f")
        return t, f

    def fork(self, *predicates) -> Tuple["Dataset", ...]:
        """n-way Fork (reference Fork, DryadLinqQueryable.cs:3717-3852 is
        n-way): one branch per predicate over a single shared scan (the
        parent is Tee-materialized once by the planner's consumer count).
        Branches may overlap or under-cover; pair with fork_on for
        disjoint key-value splits."""
        return tuple(self.where(p, label=f"fork_{i}")
                     for i, p in enumerate(predicates))

    def fork_on(self, column: str, values: Sequence[Any]
                ) -> Tuple["Dataset", ...]:
        """n-way Fork by key value (the reference's Fork(keySelector,
        keys) overload): branch i holds rows where ``column == values[i]``.
        """
        import jax.numpy as jnp

        return tuple(
            self.where(lambda c, _v=v: c[column] == jnp.asarray(_v),
                       label=f"fork_{column}_{i}")
            for i, v in enumerate(values))

    def assume_hash_partition(self, keys: Sequence[str]) -> "Dataset":
        """Declare existing hash placement (AssumeHashPartition,
        DryadLinqQueryable.cs:3408) — skips the shuffle for matching keys."""
        return Dataset(self.ctx, E.AssumePartitioning(
            parents=(self.node,), kind="hash", keys=tuple(keys)))

    def assume_range_partition(self, keys: Sequence[str]) -> "Dataset":
        return Dataset(self.ctx, E.AssumePartitioning(
            parents=(self.node,), kind="range", keys=tuple(keys)))

    def assume_order_by(self, keys: Sequence[str]) -> "Dataset":
        """Declare (without sorting) that the data is globally sorted
        ascending by ``keys`` — partitions hold disjoint ascending key
        ranges (AssumeOrderBy, DryadLinqQueryable.cs:3639).  A subsequent
        ``order_by`` whose ascending keys are a prefix of ``keys`` skips
        the range exchange and only sorts locally."""
        return self.assume_range_partition(keys)

    def take(self, n: int) -> "Dataset":
        return Dataset(self.ctx, E.Take(parents=(self.node,), n=n))

    def with_capacity(self, capacity: int) -> "Dataset":
        """Coerce per-partition capacity (shape-stabilize do_while bodies)."""
        return Dataset(self.ctx, E.WithCapacity(parents=(self.node,),
                                                capacity=capacity))

    def cross_apply(self, other: "Dataset", fn, host_fn=None,
                    label: str = "cross_apply") -> "Dataset":
        """fn(left_batch, right_batch) with ``other`` broadcast to every
        partition; host_fn(table_l, table_r) is the oracle equivalent."""
        return Dataset(self.ctx, E.CrossApply(
            parents=(self.node, other.node), fn=fn, host_fn=host_fn,
            label=label))

    # -- shuffling operators ----------------------------------------------

    def group_by(self, keys: Sequence[str],
                 aggs: Dict[str, Tuple[str, Optional[str]]]) -> "Dataset":
        """GroupBy + decomposable aggregates: aggs maps output column ->
        (kind, value_column), kind in sum/count/min/max/mean/any/all/sum64.

        ``sum`` keeps its column's type: over an ``int32`` column it is an
        ``int32`` that wraps past 2**31 (the planner's own merges of
        counts and of any/all ride it, and a caller's ``select`` may do
        arithmetic on the result).  ``sum64`` over an integer column of at
        most 32 bits is exact to 64 bits: the result is a
        ``data.columnar.Int64Column`` (two 32-bit words; the package runs
        without x64), collected as numpy ``int64``; it can be an
        ``order_by`` key, be stored, and be summed again with ``sum``.
        SQL's ``SUM`` over integers is ``sum64``.

        Supported-workload assumption: groups are identified by a 64-bit
        key hash (ops/hashing.py).  Keys that collide in all 64 bits are
        merged — vanishingly unlikely for organic data (~n^2/2^64) but
        possible for adversarially constructed keys; this differs from the
        reference's GroupBy, which compares real keys
        (DryadLinqVertex.cs:510).  ``join`` verifies true keys; ``group_by``
        / ``distinct`` / semi-joins do not.

        An agg value may also be a ``Decomposable(seed, merge, finalize)``
        for user-defined aggregation (IDecomposable.cs:34 parity) — see
        ``dryad_tpu.Decomposable``.

        NaN caveat: ``min``/``max`` over float columns containing NaN are
        LOWERING-DEPENDENT.  The segmented-scan path accumulates with
        jnp.minimum/jnp.maximum (NaN propagates into the group result);
        the boundary-carry fast path rides the value through a sort lane
        ordered by IEEE totalOrder (-NaN below -inf, +NaN above +inf),
        so a NaN may or may not surface depending on its sign bit.
        Neither matches a NaN-IGNORING host nanmin/nanmax — filter NaNs
        first when their handling matters."""
        return Dataset(self.ctx, E.GroupByAgg(
            parents=(self.node,), keys=tuple(keys), aggs=dict(aggs)))

    def group_apply(self, keys: Sequence[str], fn,
                    group_capacity: int, max_groups: int | None = None,
                    out_rows: int = 1, out_capacity: int | None = None
                    ) -> "Dataset":
        """GroupBy yielding group CONTENTS to an arbitrary per-group fn —
        the reference's general GroupBy result selector
        (DryadLinqVertex.cs:510-753): any non-decomposable per-group
        computation (median, mode, custom reductions) is expressible here.

        ``fn(cols, count) -> (out_cols, mask)``: cols are one group's
        columns as [group_capacity, ...] arrays (rows >= count are
        unspecified — mask by count); out_cols are [out_rows, ...] and
        mask is [out_rows] bool.  Group keys are attached to the output
        automatically.  ``group_capacity`` bounds a single group's rows
        (overflow triggers a measured-need retry); ``max_groups`` bounds
        per-partition distinct keys (default: the input capacity).  The
        dense regroup materializes max_groups x group_capacity cells per
        column — size both knobs for the workload."""
        return Dataset(self.ctx, E.GroupApply(
            parents=(self.node,), keys=tuple(keys), fn=fn,
            group_capacity=group_capacity, max_groups=max_groups,
            out_rows=out_rows, out_capacity=out_capacity))

    def group_top_k(self, keys: Sequence[str], k: int, by: str,
                    descending: bool = True) -> "Dataset":
        """Per-group top-k rows by ``by`` (all columns kept; ties keep
        original order).  Structured — no callable, ships to clusters
        without fn_table registration."""
        return Dataset(self.ctx, E.GroupTopK(
            parents=(self.node,), keys=tuple(keys), k=k, by=by,
            descending=descending))

    def group_median(self, keys: Sequence[str], by: str,
                     out: str | None = None) -> "Dataset":
        """One row per group: keys + the LOWER median of ``by`` (element
        (n-1)//2 of the ascending order — always an actual group element,
        unlike numpy's interpolated even-size median)."""
        return Dataset(self.ctx, E.GroupRankSelect(
            parents=(self.node,), keys=tuple(keys), by=by, rank="median",
            out=out))

    def aggregate(self, dec: "E.Decomposable"):
        """Whole-dataset user-defined aggregation (the reference's
        user-combinable Aggregate operator, DryadLinqQueryable.cs
        *AsQuery aggregates + IDecomposable.cs:34): runs the decomposable
        protocol over ONE global group and returns the finalized value(s).
        """
        const = self.select(_add_agg_key, label="agg-key")
        out = const.group_by(["__agg_key"], {"agg": dec}).collect()
        res = {k: v for k, v in out.items() if k != "__agg_key"}
        if set(res.keys()) == {"agg"}:
            v = np.asarray(res["agg"])
            return v[0] if v.shape and v.shape[0] == 1 else v
        return {k: (np.asarray(v)[0] if np.asarray(v).shape
                    and np.asarray(v).shape[0] == 1 else np.asarray(v))
                for k, v in res.items()}

    def join(self, other: "Dataset", left_keys: Sequence[str],
             right_keys: Sequence[str] | None = None,
             expansion: float | None = None,
             broadcast: bool = False, how: str = "inner",
             right_unique: bool | str = False) -> "Dataset":
        """Equi-join.  ``how`` in inner/left/right/full: "left" keeps
        unmatched left rows with right columns zero-filled; "right" keeps
        unmatched right rows (left non-key columns zero-filled, left key
        columns carrying the right key values); "full" keeps both.
        Broadcast is only honored for inner/left (a replicated right side
        cannot detect its unmatched rows without duplication).

        ``right_unique=True`` (inner/left only) declares the right side
        unique-keyed (lookup/dimension table) and routes matching through
        the gather-free merge-fill kernel (ops/kernels._lookup_join).
        Uniqueness itself is runtime-verified (duplicates fall back to
        the general kernel in the same compiled program).  When both
        sides' key columns pack to the SAME lane layout (same dtype /
        string max_len — the common case), matches are byte-verified
        against the carried key lanes, exactly like the default path;
        when the layouts differ (e.g. an i32 key joined to an i64
        column) verification falls back to the 64-bit key hash pair —
        two distinct keys agreeing in all 64 hash bits would mis-join,
        a ~n^2/2^64 probability budget (the same one group_by/distinct
        document).  Keep right_unique off for adversarially constructed
        keys with mismatched key dtypes.

        ``right_unique="verified"``: the right side's join columns hold
        a key that WAS verified over its rows — a store written with
        ``to_store(unique=...)``, which is how the SQL lowering comes to
        pass it (sql/lower.py).  The stage's program is then the lookup
        kernel alone: no duplicate check, no second kernel.  On rows
        that do repeat a key the result is undefined; on data of
        unknown uniqueness use ``True``."""
        return Dataset(self.ctx, E.Join(
            parents=(self.node, other.node), left_keys=tuple(left_keys),
            right_keys=tuple(right_keys or left_keys),
            expansion=expansion or self.ctx.config.join_expansion,
            broadcast_right=broadcast, how=how,
            right_unique=right_unique))

    def group_join(self, other: "Dataset", left_keys: Sequence[str],
                   aggs: Dict[str, Any],
                   right_keys: Sequence[str] | None = None,
                   expansion: float = 1.0) -> "Dataset":
        """GroupJoin (reference DryadLinqQueryable GroupJoin /
        DLinqGroupByNode): each left row is paired with the AGGREGATE of
        its matching right group.  Lowered as right.group_by(keys, aggs)
        followed by a left-outer join, so empty groups appear with
        zero/neutral aggregate values (include a ("count", None) agg to
        distinguish empties).  aggs values may be builtin kinds or
        Decomposables."""
        rkeys = list(right_keys or left_keys)
        agg = other.group_by(rkeys, aggs)
        return self.join(agg, left_keys, rkeys, expansion=expansion,
                         how="left")

    def order_by(self, keys: Sequence[Tuple[str, bool]]) -> "Dataset":
        """Global sort; keys = [(column, descending), ...]."""
        return Dataset(self.ctx, E.OrderBy(parents=(self.node,),
                                           keys=tuple(keys)))

    def distinct(self, keys: Sequence[str] = ()) -> "Dataset":
        """Distinct rows (by ``keys``, or all columns when empty).  Rows are
        deduplicated by 64-bit key hash — see the supported-workload
        assumption documented on :meth:`group_by`."""
        return Dataset(self.ctx, E.Distinct(parents=(self.node,),
                                            keys=tuple(keys)))

    def union(self, other: "Dataset") -> "Dataset":
        return Dataset(self.ctx, E.SetOp(parents=(self.node, other.node),
                                         op="union"))

    def intersect(self, other: "Dataset") -> "Dataset":
        return Dataset(self.ctx, E.SetOp(parents=(self.node, other.node),
                                         op="intersect"))

    def except_(self, other: "Dataset") -> "Dataset":
        return Dataset(self.ctx, E.SetOp(parents=(self.node, other.node),
                                         op="except"))

    def concat(self, other: "Dataset") -> "Dataset":
        return Dataset(self.ctx, E.Concat(parents=(self.node, other.node)))

    def hash_partition(self, keys: Sequence[str]) -> "Dataset":
        """Explicit repartition (HashPartition, DryadLinqQueryable.cs:275)."""
        return Dataset(self.ctx, E.HashRepartition(parents=(self.node,),
                                                   keys=tuple(keys)))

    def range_partition(self, keys: Sequence[str]) -> "Dataset":
        return Dataset(self.ctx, E.RangeRepartition(parents=(self.node,),
                                                    keys=tuple(keys)))

    def broadcast(self) -> "Dataset":
        """Replicate to every partition (small datasets)."""
        return Dataset(self.ctx, E.Broadcast(parents=(self.node,)))

    def cache(self) -> "Dataset":
        """Materialize NOW and reuse the result in later queries — the
        reference's materialized-temp-table pattern (ToStore + FromStore
        around loop-invariant subqueries; temp outputs committed at
        DrVertex.h:325).  Essential under ``do_while``: the loop body
        re-executes everything it references each iteration, so hoist
        loop-invariant joins/aggregations with ``.cache()`` first.

        Streamed / edge-scale data takes the store-backed RE-STREAMING
        cache tier (``JobConfig.ooc_restream_cache``, default on): the
        cold pass writes a local chunked cache — io/store layout, the
        spill-sidecar chunk format with its per-chunk fingerprints —
        keyed by the producing query's stable fingerprint, and warm
        passes (iteration 2..N of ``do_while`` bodies, or a restarted
        job with an intact ``ooc_cache_dir``) re-stream from local
        sequential reads instead of ranged hdfs://, s3://, or http://
        fetches.  A corrupt or stale entry falls back to a clean
        re-stream — never wrong rows."""
        if self.ctx.local_debug:
            t = _oracle.run_oracle(self.node)
            node = E.Source(parents=(), data=None,
                            _npartitions=self.ctx.nparts, host=t)
            return Dataset(self.ctx, node)
        cfg = self.ctx.config
        diag = None
        if not self._streaming():
            # DTA204: cache() of edge-scale data.  With the re-streaming
            # tier ON this is informational (the cache lowers to the
            # local chunked store below); with the tier OFF it warns —
            # the result pins device memory for the Context's lifetime.
            diag = self._cache_cost_diag()
        part = self.node.partitioning
        if self.ctx.cluster is not None:
            if cfg.ooc_restream_cache and (
                    self._stream_sourced()
                    or (diag is not None and diag.severity == "info")):
                return self._cache_restream_cluster()
            # materialize cluster-resident: later queries ship only the
            # token, and the partitioning claim SURVIVES (hash-partitioned
            # cache feeds shuffle-free joins/groupbys) — VERDICT r2 item 4
            token = self.ctx._fresh_token("cache")
            reply = self.ctx._cluster_run(self.node, collect=False,
                                          keep_token=token,
                                          want_reply=True)
            if reply.get("salted"):
                part = E.Partitioning.none()
            return self.ctx._resident_dataset(
                token, reply["resident_capacity"], partitioning=part,
                producer=self.node)
        if self._streaming():
            if cfg.ooc_restream_cache:
                return self._cache_restream_local()
            # legacy (ooc_restream_cache=False — the A/B lever):
            # materialize once to an unvalidated temp store, stream
            # reads from there; the dir lives as long as the Context
            import shutil
            import tempfile
            import weakref
            d = tempfile.mkdtemp(prefix="dryad-cache-",
                                 dir=self.ctx.spill_dir)
            weakref.finalize(self.ctx, shutil.rmtree, d,
                             ignore_errors=True)
            target = d + "/data"
            self.to_store(target)
            return self.ctx.read_store_stream(target)
        if diag is not None and diag.severity == "info" \
                and cfg.ooc_restream_cache:
            # edge-scale in-memory cache(): pin a LOCAL store instead of
            # device HBM — later queries stream it (the DTA204 story)
            return self._cache_restream_inmem()
        pd = self._materialize()
        if getattr(self, "_last_salted", False):
            part = E.Partitioning.none()
        return self.ctx.from_pdata(pd, partitioning=part)

    def _stream_sourced(self) -> bool:
        """True when any source is a stream (local StreamSource OR a
        cluster ``store_stream`` deferred source) — the streamed-data
        half of the re-streaming cache tier's applicability test."""
        from dryad_tpu.analysis.plan_rules import _is_stream_source
        return any(isinstance(n, E.Source) and n.data is not None
                   and _is_stream_source(n.data)
                   for n in E.walk(self.node))

    def _cache_cost_diag(self):
        """The DTA204 edge-scale-cache diagnostic for this query (None
        when not edge-scale or not computable).  Best effort — a
        cost-model failure must never block a cache().  Also emits the
        lint_finding when a sink is attached and lint is on."""
        cfg = self.ctx.config
        if not getattr(cfg, "device_hbm_bytes", 0):
            return None
        has_sink = (getattr(cfg, "lint", "off") != "off"
                    and self.ctx._event_log is not None)
        if not (cfg.ooc_restream_cache or has_sink):
            # neither a lowering decision nor a finding to surface:
            # skip the (planning + eval_shape) estimate entirely
            return None
        try:
            from dryad_tpu.analysis.cost import (cache_diagnostic,
                                                 estimate_query)
            rep = estimate_query(self.node, self.ctx.nparts,
                                 hosts=self.ctx.hosts,
                                 levels=self.ctx.levels,
                                 config=self.ctx.config)
            d = cache_diagnostic(rep, self.ctx.config)
        except Exception:
            return None
        if d is not None and has_sink:
            self.ctx._event_log(
                {"event": "lint_finding", "code": d.code,
                 "severity": d.severity, "message": d.message,
                 "node": d.node,
                 "span": str(d.span) if d.span else None})
        return d

    # -- re-streaming cache tier (exec/ooc.py cache machinery) --------------

    def _cache_restream_local(self) -> "Dataset":
        """Streamed cache(): fingerprinted local chunk cache.  Cold =
        one pass through the streamed engine writing the entry
        (``ooc_cache_write``); warm — including a fresh process with an
        intact ``ooc_cache_dir`` — skips the pass entirely and every
        later iteration re-streams local sequential reads
        (``ooc_cache_hit`` per pass)."""
        from dryad_tpu.exec import ooc
        root = self.ctx._ooc_cache_root()
        key = _stable_node_fp(self.node)
        ev = self.ctx._cache_event()
        warm = ooc.cached_chunk_source(root, key)
        if warm is None:
            cs = self._stream_run()
            sc = ooc.write_chunk_cache(root, key, cs)
            ev({"event": "ooc_cache_write",
                "path": ooc.cache_entry_paths(root, key)[0],
                "rows": sc["rows"], "bytes": sc["bytes"]})
            chunk_rows, schema = sc["chunk_rows"], cs.schema
        else:
            chunk_rows = int(warm[1]["chunk_rows"])
            schema = warm[0].schema
        src = ooc.cache_source(root, key, chunk_rows, schema,
                               make_producer=self._stream_run,
                               on_event=ev)
        return self.ctx.from_stream(src)

    def _cache_restream_inmem(self) -> "Dataset":
        """Edge-scale in-memory cache(): materialize once to a local
        partitioned store (per-chunk fingerprints) and hand back a
        streamed read over it — the result no longer pins HBM for the
        Context's lifetime."""
        from dryad_tpu.exec import ooc
        cfg = self.ctx.config
        root = self.ctx._ooc_cache_root()
        key = _stable_node_fp(self.node)
        ev = self.ctx._cache_event()
        warm = ooc.cached_chunk_source(root, key)
        if warm is None:
            entry, data, _side = ooc.cache_entry_paths(root, key)
            os.makedirs(entry, exist_ok=True)
            self.to_store(data)
            sc = ooc.adopt_chunk_cache(root, key, cfg.ooc_chunk_rows)
            ev({"event": "ooc_cache_write", "path": entry,
                "rows": sc["rows"], "bytes": sc["bytes"]})
            chunk_rows = sc["chunk_rows"]
            schema = ooc.cached_chunk_source(root, key)[0].schema
        else:
            chunk_rows = int(warm[1]["chunk_rows"])
            schema = warm[0].schema

        def producer():
            # fallback after a mid-stream invalidation: re-materialize
            # in memory and slice to chunks (it fit on device anyway)
            t = pdata_to_host(self._materialize())
            return ooc.ChunkSource.from_arrays(
                t, chunk_rows, str_max_len=cfg.string_max_len)

        src = ooc.cache_source(root, key, chunk_rows, schema,
                               make_producer=producer, on_event=ev)
        return self.ctx.from_stream(src)

    def _cache_restream_cluster(self) -> "Dataset":
        """Cluster cache() of streamed / edge-scale data: the gang
        writes the entry's data store in parallel (one writer per
        worker) instead of pinning a dataset-sized resident, and later
        queries stream the store.  Needs a worker-reachable local/shared
        filesystem root (``ooc_cache_dir`` > ``cluster_stream_spool_dir``
        > driver temp — valid for single-machine clusters)."""
        from dryad_tpu.exec import ooc
        cfg = self.ctx.config
        root = cfg.ooc_cache_dir or cfg.cluster_stream_spool_dir
        if root is None:
            root = self.ctx._ooc_cache_root()
        elif "://" in root:
            # remote roots have no sidecar file semantics — fall back
            # to the driver-local temp root (single-machine clusters)
            root = self.ctx._ooc_cache_root()
        else:
            os.makedirs(root, exist_ok=True)
        key = _stable_node_fp(self.node)
        ev = self.ctx._cache_event()
        entry, data, _side = ooc.cache_entry_paths(root, key)
        warm = ooc.cached_chunk_source(root, key)
        if warm is None:
            os.makedirs(entry, exist_ok=True)
            part = self.node.partitioning
            self.ctx._cluster_run(
                self.node, collect=False, store_path=data,
                store_partitioning={"kind": part.kind,
                                    "keys": list(part.keys)})
            sc = ooc.adopt_chunk_cache(root, key, cfg.ooc_chunk_rows)
            ev({"event": "ooc_cache_write", "path": entry,
                "rows": sc["rows"], "bytes": sc["bytes"]})
        else:
            sc = warm[1]
            ev({"event": "ooc_cache_hit", "path": entry,
                "rows": sc.get("rows"), "bytes": sc.get("bytes")})
        return self.ctx.read_store_stream(
            data, chunk_rows=int(sc["chunk_rows"]))

    # -- terminals ---------------------------------------------------------

    def _streaming(self) -> bool:
        from dryad_tpu.exec.stream_exec import StreamSource
        return any(isinstance(n, E.Source)
                   and isinstance(n.data, StreamSource)
                   for n in E.walk(self.node))

    def _stream_run(self):
        """Plan with ONE logical partition and execute over chunk streams
        (exec/stream_exec.py); returns the lazy output ChunkSource."""
        from dryad_tpu.exec.stream_exec import run_stream_graph
        graph = plan_query(self.node, 1, hosts=1, config=self.ctx.config)
        self.ctx._pre_submit_lint(self.node, cluster=False, graph=graph)
        return run_stream_graph(graph, self.ctx.config,
                                spill_dir=self.ctx.spill_dir,
                                event_log=self.ctx.executor._event
                                if self.ctx.executor else None)

    def _materialize(self) -> PData:
        with trace.span("plan", "plan") as sp:
            graph = plan_query(self.node, self.ctx.nparts,
                               hosts=self.ctx.hosts,
                               levels=self.ctx.levels,
                               config=self.ctx.config)
            sp.set(stages=len(graph.stages))
        with trace.span("lint", "plan"):
            cost_rep = self.ctx._pre_submit_lint(self.node, cluster=False,
                                                 graph=graph)
        pd = self.ctx.executor.run(graph, spill_dir=self.ctx.spill_dir,
                                   cost_report=cost_rep)
        # runtime hot-key salting — and adaptive broadcast flips
        # (dryad_tpu/adapt) — change the OUTPUT PLACEMENT: any
        # partitioning claim persisted from this materialization
        # (cache/to_store) must drop or a later shuffle-elided read
        # would silently mis-group
        self._last_salted = (any(st._salted for st in graph.stages)
                             or getattr(self.ctx.executor,
                                        "_last_run_placement_changed",
                                        False))
        return pd

    def collect(self) -> Dict[str, Any]:
        """Execute and pull all rows to host (Submit + read output)."""
        if self.ctx.local_debug:
            return _oracle.run_oracle(self.node)
        if self.ctx.cluster is not None:
            out = self.ctx._cluster_run(self.node)
        elif self._streaming():
            from dryad_tpu.exec.stream_exec import chunks_to_table
            out = chunks_to_table(self._stream_run())
        else:
            from dryad_tpu.exec.data import (batch_nbytes,
                                             maybe_shrink_for_collect)
            # the terminal call is the root of the query's spans: plan,
            # lint, run and the fetch share its trace id
            with trace.span("collect", "query") as sp:
                pd = self._materialize()
                with trace.span("collect.fetch", "io") as fsp:
                    pd = maybe_shrink_for_collect(pd,
                                                  config=self.ctx.config)
                    fsp.set(bytes=batch_nbytes(pd.batch))
                    out = pdata_to_host(pd)
                sp.set(rows=next((len(v) for v in out.values()), 0),
                       sink="host")
        if isinstance(self.node, E.Take):
            n = self.node.n
            out = {k: v[:n] for k, v in out.items()}
        return out

    def to_store(self, path: str, compression: str | None = None,
                 unique: Sequence[str] | None = None) -> None:
        """Execute and persist (ToStore + Submit,
        DryadLinqQueryable.cs:3909,4032).  ``compression="gzip"`` enables
        the per-partition compression transform (reference
        GzipCompressionChannelTransform.cpp).

        ``unique=[...]`` declares one key of one or more columns: no two
        rows agree on all of them.  The write verifies it over every row
        (io/store.check_unique) and refuses with ``StoreKeyError`` where
        it does not hold; ``meta.json`` then carries it, and a SQL join
        whose build side is this table on these columns runs the lookup
        kernel alone (sql/lower.py).  In-memory writes only."""
        from dryad_tpu.io.store import write_store
        part = self.node.partitioning
        if compression is None:
            compression = self.ctx.config.store_compression
        if compression not in (None, "gzip"):
            raise ValueError(f"unknown compression {compression!r}")
        if unique and (self.ctx.cluster is not None or self._streaming()):
            raise NotImplementedError(
                "to_store(unique=...) verifies the key over rows held on "
                "this process's devices: not on a cluster or a streamed "
                "source")
        if self.ctx.cluster is not None:
            # parallel output: every worker writes its own partitions
            # (compression included); process 0 merges meta + commits
            self.ctx._cluster_run(
                self.node, collect=False, store_path=path,
                store_partitioning={"kind": part.kind,
                                    "keys": list(part.keys)},
                store_compression=compression)
            return
        if self._streaming():
            from dryad_tpu.exec.ooc import write_chunks_to_store
            cs = self._stream_run()
            write_chunks_to_store(
                path, iter(cs), cs.schema,
                partitioning={"kind": part.kind, "keys": list(part.keys)},
                compression=compression)
            return
        with trace.span("to_store", "query") as sp:
            pd = self._materialize()
            if getattr(self, "_last_salted", False):
                part = E.Partitioning.none()
            sp.set(sink=path, rows=write_store(
                path, pd, partitioning={"kind": part.kind,
                                        "keys": list(part.keys)},
                compression=compression, unique=unique))

    def count(self) -> int:
        if self.ctx.local_debug:
            t = _oracle.run_oracle(self.node)
            for v in t.values():
                return len(v)
            return 0
        if self.ctx.cluster is not None:
            # counts-only reduction: no row data crosses the control plane
            return self.ctx._cluster_run(self.node, collect="count")
        if self._streaming():
            return sum(c.n for c in self._stream_run())
        return self._materialize().total_rows()

    def _scalar(self, kind: str, column: str):
        """Terminal scalar aggregate (Count/Sum/Min/Max/Average/Any/All,
        DryadLinqQueryable.cs *AsQuery aggregates): per-partition partials
        on device, combined host-side."""
        import numpy as np

        from dryad_tpu import oracle as orc
        if self.ctx.local_debug:
            t = _oracle.run_oracle(self.node)
            return orc._agg(kind, list(t[column]))
        if self.ctx.cluster is not None:
            # ship a const-key group-by so only ONE aggregated row crosses
            # the control plane (not the whole table)
            const = self.select(_add_agg_key, label="agg-key")
            agg_node = E.GroupByAgg(parents=(const.node,),
                                    keys=("__agg_key",),
                                    aggs={"out": (kind, column)})
            t = self.ctx._cluster_run(agg_node)
            v = np.asarray(t["out"])
            return v[0] if v.shape and v.shape[0] == 1 else v
        if self._streaming():
            from dryad_tpu.exec.stream_exec import stream_scalar
            return stream_scalar(self._stream_run(), kind, column)
        pd = self._materialize()
        import jax
        import jax.numpy as jnp

        from dryad_tpu.ops.kernels import scalar_aggregate

        @jax.jit
        def partials(batch):
            return jax.vmap(lambda b: scalar_aggregate(
                b, {"out": (kind, column), "cnt": ("count", None)}))(batch)

        out = partials(pd.batch)
        vals = np.asarray(out["out"])
        cnts = np.asarray(out["cnt"])
        nonempty = cnts > 0
        if kind == "sum":
            return vals.sum(axis=0)
        if kind == "min":
            return vals[nonempty].min(axis=0) if nonempty.any() else None
        if kind == "max":
            return vals[nonempty].max(axis=0) if nonempty.any() else None
        if kind == "mean":
            total = cnts.sum()
            if total == 0:
                return None
            w = (vals.T * cnts).T.sum(axis=0) / total
            return w
        if kind == "any":
            return bool(vals[nonempty].any())
        if kind == "all":
            return bool(vals[nonempty].all()) if nonempty.any() else True
        raise ValueError(kind)

    def sum(self, column: str):
        return self._scalar("sum", column)

    def min(self, column: str):
        return self._scalar("min", column)

    def max(self, column: str):
        return self._scalar("max", column)

    def mean(self, column: str):
        return self._scalar("mean", column)

    def any(self, column: str) -> bool:
        return self._scalar("any", column)

    def all(self, column: str) -> bool:
        return self._scalar("all", column)

    def first(self) -> Dict[str, Any]:
        t = self.take(1).collect()
        return {k: v[0] for k, v in t.items()}

    # -- static analysis ---------------------------------------------------

    def check(self, cluster: Optional[bool] = None,
              cost: bool = False):
        """Statically verify this query — plan rules + UDF determinism/
        shippability lint — WITHOUT executing anything (the reference's
        phase-1 validation, DryadLinqQueryGen.cs, as a user call).
        Returns an ``analysis.DiagnosticReport`` with every finding at
        once (stable DTA0xx/DTA1xx codes, source spans).  ``cluster``
        forces the cluster-shipping rules on/off; default: whether this
        Context targets a cluster.  ``cost=True`` adds the DTA2xx
        resource findings (analysis/cost.py abstract interpretation —
        still zero execution: schemas propagate via jax.eval_shape)."""
        from dryad_tpu.analysis import check_plan
        if cluster is None:
            cluster = self.ctx.cluster is not None
        report = check_plan(self.node, cluster=cluster,
                            fn_table=self.ctx.fn_table)
        if cost:
            from dryad_tpu.analysis.cost import (cost_diagnostics,
                                                 estimate_query)
            rep = estimate_query(self.node, self.ctx.nparts,
                                 hosts=self.ctx.hosts,
                                 levels=self.ctx.levels,
                                 config=self.ctx.config)
            report.diagnostics.extend(
                cost_diagnostics(rep, self.ctx.config))
            report.dedup()
        return report

    def cost(self):
        """The static cost pass alone: a machine-readable
        ``analysis.cost.CostReport`` (per-stage row intervals, exact
        byte predictions, per-device working-set bounds) for the plan
        this query would execute.  Zero execution."""
        from dryad_tpu.analysis.cost import estimate_query
        return estimate_query(self.node, self.ctx.nparts,
                              hosts=self.ctx.hosts,
                              levels=self.ctx.levels,
                              config=self.ctx.config)

    def analyze(self):
        """EXPLAIN ANALYZE: execute this query ONCE under an explicit
        event capture and return the measured per-stage actuals
        annotated against the static cost model
        (:class:`~dryad_tpu.obs.analyze.AnalyzeReport` — rows, output
        bytes, wall/compile split, retries/replays/spills, compile-cache
        hits, adaptive rewrites, and predicted-vs-actual deltas with the
        runtime cross-check's ``cost_model_miss`` verdicts inline).

        The capture is an explicit opt-in consumer (its own
        ``EventLog(level=2)``), independent of ``DRYAD_LOGGING_LEVEL``
        — asking for ANALYZE *is* asking for the telemetry.  The
        pre-submit lint gate applies exactly as in ``collect()`` (a
        plan ``lint="error"`` refuses to submit raises LintError here
        too — ANALYZE executes, so it must not bypass the gate); the
        cost pass itself still runs under ``lint="off"`` and can never
        block the run (on failure the report simply carries no
        predictions).
        In-process mesh execution only — cluster/local_debug/streamed
        runs record their streams to JSONL, which ``python -m
        dryad_tpu.obs analyze`` annotates post-hoc."""
        if self.ctx.local_debug or self.ctx.executor is None:
            raise ValueError(
                "EXPLAIN ANALYZE needs an in-process mesh Context "
                "(local_debug and cluster contexts do not execute "
                "through the instrumented executor — record a JSONL "
                "and use `python -m dryad_tpu.obs analyze` instead)")
        if self._streaming():
            raise ValueError(
                "EXPLAIN ANALYZE does not cover streamed (>RAM) plans "
                "— per-stage HBM actuals do not apply; use `python -m "
                "dryad_tpu.obs analyze` over the recorded stream")
        from dryad_tpu.obs.analyze import analyze_events
        from dryad_tpu.utils.events import EventLog
        graph = plan_query(self.node, self.ctx.nparts,
                           hosts=self.ctx.hosts, levels=self.ctx.levels,
                           config=self.ctx.config)
        # the SAME gate _materialize runs: lint="error" findings refuse
        # to submit (LintError), "warn" logs them to the attached
        # context log, and the gate's cost pass feeds the annotation
        cost_rep = self.ctx._pre_submit_lint(self.node, cluster=False,
                                             graph=graph)
        cap = EventLog(level=2)
        if cost_rep is None:
            # lint="off" (or the gate's cost pass failed): ANALYZE
            # still wants predictions, but the model must never block
            # it — on failure the report carries actuals only
            try:
                from dryad_tpu.analysis.cost import estimate_graph
                cost_rep = estimate_graph(graph, self.ctx.nparts,
                                          config=self.ctx.config)
            except Exception:
                cost_rep = None
        if cost_rep is not None:
            cap({"event": "cost_report",
                 "report": cost_rep.to_payload()})
        self.ctx.executor.run(graph, spill_dir=self.ctx.spill_dir,
                              cost_report=cost_rep, event_log=cap)
        cap.close()
        rep = analyze_events(cap.events)
        if self.ctx._event_log is not None:
            # the annotation is job telemetry too: a context with a
            # JSONL attached records the machine-readable report
            self.ctx._event_log({"event": "analyze_report",
                                 "report": rep.to_payload()})
        return rep

    def explain(self, verify: bool = False, cost: bool = False,
                analyze: bool = False) -> str:
        text = plan_query(self.node, self.ctx.nparts,
                          hosts=self.ctx.hosts,
                          levels=self.ctx.levels,
                          config=self.ctx.config).explain()
        cost_rep = self.cost() if cost else None
        if verify:
            # the ONE cost pass feeds both sections: the diagnostics
            # include the DTA2xx resource findings, so an EXPLAIN COST
            # on a provably >HBM plan SHOWS its DTA201 rejection
            report = self.check()
            if cost_rep is not None:
                from dryad_tpu.analysis.cost import cost_diagnostics
                report.diagnostics.extend(
                    cost_diagnostics(cost_rep, self.ctx.config))
                report.dedup()
            text += "\n\ndiagnostics:\n" + report.render()
        if cost_rep is not None:
            text += "\n\npredicted cost:\n" + cost_rep.render()
        if analyze:
            # EXPLAIN ANALYZE: the plan above, then what actually
            # happened when it ran (measured actuals vs the model)
            text += ("\n\nEXPLAIN ANALYZE (executed):\n"
                     + self.analyze().render())
        return text
