"""Streamed (>HBM) execution of PLANNED StageGraphs over the worker gang.

VERDICT r3 item 3: the cluster streamed path used to be a hand-mirrored
mini-API (ClusterStream) accepting only chunk-local ops + three terminals —
every new operator needed a third implementation.  This module replaces it:
plain Dataset plans (the SAME planner lowering the in-memory cluster path
uses, exchanges included) execute over per-device chunk streams:

* each mesh device streams its own subset of the source store's
  partitions (partition p -> device p mod P);
* a leg's trailing chunk-local (and partial-safe: group/distinct) ops fuse
  INTO the jitted wave program; whole-stream leg ops (take/skip/row_index/
  sort/...) apply per-device through exec/stream_exec's machinery first;
* a leg's exchange runs as lockstep chunk WAVES over the mesh (hash /
  range / broadcast — including the hierarchical per-axis hops), received
  rows spilling into per-device bucket stores between waves;
* stage BODY ops then run per device over its bucket stream through the
  single-partition streamed executor — joins materialize their
  (bucket-aligned) right side exactly like the one-process path;
* terminals reuse the parallel collect / parallel store writers; loop
  state (do_while) materializes cluster-resident under keep_token.

The reference's channels stream every operator identically
(DryadVertex/.../channelinterface.h:212 makes no operator distinction);
this gives the TPU gang the same property through ONE lowering.

Mirrored-determinism contract as runtime/exec_common.py: every process
derives the same wave count (a tiny continuation allgather), the same
bounds, and the same retry decisions (needs are pmax'd in-program).
"""

from __future__ import annotations

import tempfile
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dryad_tpu.analysis.diagnostics import DiagnosticError
from dryad_tpu.plan.stages import Exchange, Stage, StageOp

__all__ = ["execute_stream_plan", "has_stream_sources", "StreamPlanError"]


class StreamPlanError(DiagnosticError):
    """Streamed-plan contract violation.  Every raise carries the stable
    diagnostic code of the dryad_tpu/analysis rule that catches the same
    condition pre-submit (DTA002/003), or a DTA9xx runtime-only code
    for data-dependent overflows and internal invariants — see
    analysis/diagnostics.CODES; tests/test_analysis.py asserts the
    mapping has no drift."""


# leg-op kinds safe to apply PER CHUNK inside the wave program: chunk-local
# ops, plus partial aggregations whose merge happens post-exchange
_WAVE_FUSABLE = {"fn", "filter", "mean_fin", "flat_tokens", "flat_map",
                 "apply", "recap", "group", "dgroup_partial",
                 "dgroup_local", "distinct"}

# whole-group kinds (group_apply/group_rank) stream through
# exec/ooc.streaming_group_whole — post-exchange bucket streams are
# key-aligned, so each device materializes complete groups; zip pairs
# per-device streams positionally (the in-memory executor's
# per-partition zip semantics); global take coordinates across the gang
# through one mirrored host allgather (_global_take).  Nothing is
# unsupported here anymore (channelinterface.h:212 — reference channels
# stream EVERY operator).
_UNSUPPORTED: Dict[str, str] = {}


class _StreamSpec:
    """Planner/graph-visible marker for a streamed store source."""

    def __init__(self, spec: Dict[str, Any]):
        self.spec = spec

    @property
    def capacity(self) -> int:
        return self.spec["chunk_rows"]


def has_stream_sources(source_specs: Dict[str, Dict[str, Any]]) -> bool:
    return any(s.get("kind") == "store_stream"
               for s in source_specs.values())


# ---------------------------------------------------------------------------
# per-stage results: one re-iterable ChunkSource per LOCAL device


class _DevStreams:
    def __init__(self, streams: List[Any]):
        self.streams = streams  # [dpp] ChunkSources, device-aligned

    @property
    def schema(self):
        return self.streams[0].schema

    @property
    def chunk_rows(self):
        return self.streams[0].chunk_rows


def _source_streams(spec: Dict[str, Any], mesh, config) -> _DevStreams:
    """Store partitions -> per-local-device chunk streams (partition p is
    served by global device p mod P; device-aligned so output partition
    ids line up with bucket ids)."""
    import jax

    from dryad_tpu.exec import ooc
    from dryad_tpu.io.store import store_meta

    path = spec["path"]
    chunk_rows = spec["chunk_rows"]
    P = mesh.devices.size
    nprocs = jax.process_count()
    dpp = P // nprocs
    start = jax.process_index() * dpp
    meta = store_meta(path)
    streams = []
    for d in range(dpp):
        g = start + d
        parts = [p for p in range(meta["npartitions"]) if p % P == g]
        streams.append(ooc.ChunkSource.from_store(path, chunk_rows,
                                                  partitions=parts))
    return _DevStreams(streams)


def _resident_streams(pd, mesh, config) -> _DevStreams:
    """Device-resident PData -> per-device host chunk streams (loop state
    and other in-HBM inputs joining a streamed plan)."""
    import jax

    from dryad_tpu.exec.ooc import ChunkSource
    from dryad_tpu.runtime.stream_cluster import (_read_local_shards,
                                                  local_batch_chunks)

    nprocs = jax.process_count()
    dpp = pd.nparts // nprocs
    start = jax.process_index() * dpp
    local = _read_local_shards(pd.batch, start, dpp)
    schema, chunks = local_batch_chunks(local)
    cap = max(pd.capacity, 1)
    return _DevStreams([
        ChunkSource((lambda c=c: iter([c])), schema, cap) for c in chunks])


# ---------------------------------------------------------------------------
# wave exchange


def _wave_chunk_op(b, op: StageOp, scale: int):
    """One wave-fusable op applied to a per-device chunk batch."""
    import jax.numpy as jnp

    from dryad_tpu.exec import stream_exec
    from dryad_tpu.ops import kernels

    k, p = op.kind, op.params
    no = jnp.zeros((), jnp.int32)
    if k in stream_exec._LOCAL_KINDS:
        return stream_exec._local_op(b, op, scale)
    if k == "group":
        return kernels.group_aggregate(b, list(p["keys"]),
                                       dict(p["aggs"])), no
    if k == "dgroup_partial":
        return kernels.group_decompose_partial(
            b, list(p["keys"]), p["decs"], p["box"]), no
    if k == "dgroup_local":
        return kernels.group_decompose_local(
            b, list(p["keys"]), p["decs"], p["box"]), no
    if k == "distinct":
        return kernels.distinct(b, list(p["keys"]) or None), no
    raise StreamPlanError(f"op {k!r} cannot ride a wave program",
                          code="DTA901", span=op.span)


def _build_wave_fn(mesh, leg_ops: List[StageOp], ex: Exchange,
                   chunk_rows: int, scale: int, slack: int,
                   slot_rows: int | None = None):
    """One jitted shard_map program: per-chunk leg ops + the leg's
    exchange; need channels pmax'd in-program (mirrored retries)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dryad_tpu.parallel import shuffle
    from dryad_tpu.runtime.stream_cluster import _expand, _squeeze

    axes = tuple(mesh.axis_names)
    out_cap = max(1, ex.out_capacity) * scale

    def per_shard(batch, bounds):
        b = _squeeze(batch)
        need_local = jnp.zeros((), jnp.int32)
        for op in leg_ops:
            b, need = _wave_chunk_op(b, op, scale)
            need_local = jnp.maximum(need_local, need)
        if ex.kind == "hash":
            out, nr, nsl, slot = shuffle.hash_exchange(
                b, list(ex.keys), out_cap, send_slack=slack, axes=axes,
                axis=ex.axis, slot_rows=slot_rows)
        elif ex.kind == "range":
            # the one range rule, handed the one lane the streamed
            # sample pass has (``bounds`` [P-1] over the primary's first
            # lane, in its direction): rows equal in it stay together
            out, nr, nsl, slot = shuffle.range_exchange(
                b, ex.sort_keys()[:1], bounds[:, None], out_cap,
                tiebreak=False, send_slack=slack, axes=axes,
                slot_rows=slot_rows)
        elif ex.kind == "broadcast":
            out, nr, nsl = shuffle.broadcast_gather(b, out_cap, axes=axes)
            slot = jnp.zeros((), jnp.int32)
        else:
            raise StreamPlanError(f"exchange kind {ex.kind!r}",
                                  code="DTA902")
        exch_scale = (-(-nr // jnp.int32(max(1, ex.out_capacity)))
                      ).astype(jnp.int32)
        need_scale = jnp.maximum(need_local, exch_scale)
        need_scale = jax.lax.pmax(need_scale, axes)
        info = jnp.stack([need_scale, jnp.asarray(nsl, jnp.int32),
                          out.count.astype(jnp.int32),
                          jnp.asarray(slot, jnp.int32)])
        return _expand(out), info[None]

    in_specs = (P(axes), P())
    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(axes), P(axes)), check_vma=False)
    return jax.jit(fn)


def _compact_fn_for(stage: Stage):
    """Associative bucket-compaction callable from the stage's FIRST body
    group op (merging already-merged partials is sound: the merge specs
    are associative — sum of sums, min of mins, decomposable merge)."""
    from dryad_tpu.ops import kernels

    for op in stage.body:
        if op.kind == "group":
            keys, aggs = list(op.params["keys"]), dict(op.params["aggs"])
            return lambda b: kernels.group_aggregate(b, keys, aggs)
        if op.kind == "dgroup_merge":
            keys = list(op.params["keys"])
            decs, box = op.params["decs"], op.params["box"]
            return lambda b: kernels.group_decompose_merge(
                b, keys, decs, box, False)
        if op.kind == "distinct":
            keys = list(op.params["keys"]) or None
            return lambda b: kernels.distinct(b, keys)
    return None


def _run_leg_waves(dev: _DevStreams, leg_ops: List[StageOp], ex: Exchange,
                   mesh, config, bounds_arr, compact_fn, job_root: str,
                   stats=None) -> _DevStreams:
    """Lockstep chunk waves for one leg's exchange; returns per-device
    bucket streams holding ALL received rows (spilled to disk for
    unbounded kinds, RAM + compaction for group partials)."""
    import jax
    import jax.numpy as jnp

    from dryad_tpu.exec import ooc
    from dryad_tpu.exec.ooc import ChunkSource
    from dryad_tpu.runtime.stream_cluster import (_host_allgather,
                                                  _read_local_shards,
                                                  local_batch_chunks)

    nprocs = jax.process_count()
    dpp = mesh.devices.size // nprocs
    start = jax.process_index() * dpp
    chunk_rows = dev.chunk_rows
    schema = dev.schema

    # bucket schema = the EXCHANGED row schema: probe the wave ops over an
    # empty chunk (also fills decomposable treedef boxes pre-merge)
    probe_b = ooc._chunk_to_batch(ooc.HChunk.empty_like(schema), 1)
    for op in leg_ops:
        probe_b, _ = _wave_chunk_op(probe_b, op, 1)
    out_schema = ooc.chunk_schema(ooc._batch_to_chunk(probe_b))

    spill = None if compact_fn is not None else \
        tempfile.mkdtemp(prefix="wave-", dir=job_root)
    store = ooc._BucketStore(out_schema, dpp, spill_dir=spill)
    out_cap = max(1, ex.out_capacity)

    def compact_bucket(d: int) -> None:
        merged = ooc._concat_hchunks(out_schema, store.fragments(d))
        capm = 1
        while capm < max(merged.n, 1):
            capm *= 2
        out = ooc._batch_to_chunk(jax.jit(compact_fn)(
            ooc._chunk_to_batch(merged, capm)))
        if out.n > out_cap:
            raise StreamPlanError(
                f"bucket {start + d} holds {out.n} distinct groups > "
                f"exchange capacity {out_cap}; raise chunk_rows",
                code="DTA903")
        store._ram[d] = [out]

    fns: Dict[Tuple, Any] = {}
    slack = config.initial_send_slack
    scale = 1
    # measured send-slot right-sizing (DrDynamicDistributor.cpp:388 role):
    # wave 1 ships the structural slack and MEASURES the real per-slot
    # need; later waves ship exact slots (quantized to 16 rows to bound
    # recompiles) — wire bytes converge to ~useful bytes
    slot_rows: Optional[int] = None
    jbounds = jnp.asarray(bounds_arr)
    # prefetch: the NEXT wave's chunk reads/unpacks overlap the current
    # wave's collective (exec/ooc.prefetch_iter, per-device threads)
    its = [ooc.prefetch_iter(iter(cs), config.ooc_prefetch_depth, stats)
           for cs in dev.streams]
    while True:
        chunks = [next(it, None) for it in its]
        live = _host_allgather(
            np.asarray([sum(c is not None for c in chunks)], np.int32),
            mesh)
        if int(live.sum()) == 0:
            break
        for attempt in range(config.max_capacity_retries + 1):
            key = (scale, slack, slot_rows)
            fn = fns.get(key)
            if fn is None:
                fn = fns[key] = _build_wave_fn(mesh, leg_ops, ex,
                                               chunk_rows, scale, slack,
                                               slot_rows=slot_rows)
            garr = _put_aligned(chunks, schema, chunk_rows, mesh)
            out, info = fn(garr, jbounds)
            local_info = _read_local_shards(info, start, dpp)
            need_scale = int(local_info[:, 0].max())
            need_slack = int(local_info[:, 1].max())
            slot_used = int(local_info[:, 3].max())
            if need_scale == 0 and need_slack == 0:
                if ex.kind != "broadcast":
                    # steady-state exact slots for the NEXT wave (never
                    # below this wave's measured need)
                    q = max(16, -(-slot_used // 16) * 16)
                    slot_rows = max(slot_rows or 0, q)
                break
            scale = max(scale, need_scale)
            if slot_rows is not None:
                # measured mode overflowed (data drifted): resize from
                # the fresh measurement
                slot_rows = max(16, -(-slot_used // 16) * 16)
            else:
                slack = max(slack, min(need_slack, mesh.devices.size))
        else:
            raise StreamPlanError(
                "wave exchange still overflowing after "
                f"{config.max_capacity_retries} retries (scale={scale})",
                code="DTA904")
        local = _read_local_shards(out, start, dpp)
        _, wave_chunks = local_batch_chunks(local)
        for d, hc in enumerate(wave_chunks):
            if hc.n == 0:
                continue
            store.append(d, hc)
            if compact_fn is not None and store.rows(d) > out_cap:
                compact_bucket(d)
    # waves done: release the spill WRITE handles (fragments() reads by
    # name) — a long-lived worker running many streamed jobs must not
    # accumulate open fds
    store.close()

    def bucket_source(d: int) -> ChunkSource:
        # capacity-retried waves may have delivered fragments larger than
        # the declared bound — re-slice so downstream chunk programs keep
        # their static shapes
        bound = max(out_cap, chunk_rows)

        def it():
            for frag in store.fragments(d):
                for s in range(0, max(frag.n, 1), bound):
                    e = min(s + bound, frag.n)
                    if e > s:
                        yield ooc._slice_hchunk(frag, s, e)
        return ChunkSource(it, out_schema, bound)

    return _DevStreams([bucket_source(d) for d in range(dpp)])


def _put_aligned(chunks, schema, chunk_rows: int, mesh):
    """Per-device host chunks -> one global mesh batch [P, chunk_rows]
    (each process fills only its own device rows)."""
    import jax

    from dryad_tpu.data.columnar import Batch, StringColumn
    from dryad_tpu.parallel.mesh import batch_sharding

    P_total = mesh.devices.size
    nprocs = jax.process_count()
    dpp = P_total // nprocs
    start = jax.process_index() * dpp
    sharding = batch_sharding(mesh)

    local_cols: Dict[str, Any] = {}
    counts = np.asarray([c.n if c is not None else 0 for c in chunks],
                        np.int32)
    for k, spec in schema.items():
        if spec["kind"] == "str":
            L = spec["max_len"]
            sd = np.zeros((dpp, chunk_rows, L), np.uint8)
            sl = np.zeros((dpp, chunk_rows), np.int32)
            for d, c in enumerate(chunks):
                if c is not None and c.n:
                    dat, ln = c.cols[k]
                    sd[d, :c.n] = dat
                    sl[d, :c.n] = ln
            local_cols[k] = (sd, sl)
        else:
            dt = np.dtype(spec["dtype"])
            tail = tuple(spec.get("shape", ()))
            sa = np.zeros((dpp, chunk_rows) + tail, dt)
            for d, c in enumerate(chunks):
                if c is not None and c.n:
                    sa[d, :c.n] = c.cols[k]
            local_cols[k] = sa

    def put(local):
        gshape = (P_total,) + local.shape[1:]

        def cb(idx):
            s = idx[0]
            return local[s.start - start: s.stop - start]

        return jax.make_array_from_callback(gshape, sharding, cb)

    cols: Dict[str, Any] = {}
    for k, spec in schema.items():
        if spec["kind"] == "str":
            d, l = local_cols[k]
            cols[k] = StringColumn(put(d), put(l))
        else:
            cols[k] = put(local_cols[k])
    return Batch(cols, put(counts))


# ---------------------------------------------------------------------------
# leg / body streaming through the single-partition machinery


def _global_take(dev: _DevStreams, n: int, mesh) -> _DevStreams:
    """Global take over cluster streams — a REAL lowering (this used to
    be a typed DTA001 error).  Every device drains AT MOST n rows from
    its stream (the pull stops early, upstream chunks past the bound
    are never fetched); ONE mirrored host allgather of the per-device
    prefix counts then assigns device d exactly
    ``clip(n - rows_before_d, 0, local)`` rows in DEVICE-MAJOR order —
    the same order streamed ``collect()``/``to_store`` emit rows, so
    ``take(n)`` is precisely the head of the streamed output (and after
    a range-exchanged ``order_by``, the exact global top-n).  The kept
    rows are materialized on host, bounded by n per device."""
    import jax

    from dryad_tpu.exec.ooc import ChunkSource, _slice_hchunk
    from dryad_tpu.runtime.stream_cluster import _host_allgather

    dpp = len(dev.streams)
    start = jax.process_index() * dpp
    schema, chunk_rows = dev.schema, dev.chunk_rows
    frags_per_dev: List[List[Any]] = []
    counts: List[int] = []
    for cs in dev.streams:
        frags: List[Any] = []
        got = 0
        for c in cs:
            if c.n == 0:
                continue
            take = min(c.n, n - got)
            frags.append(c if take == c.n else _slice_hchunk(c, 0, take))
            got += take
            if got >= n:
                break           # stop BEFORE pulling another chunk
        frags_per_dev.append(frags)
        counts.append(got)
    allc = _host_allgather(np.asarray(counts, np.int32), mesh
                           ).reshape(-1)          # [P] device-major
    outs: List[Any] = []
    for d, frags in enumerate(frags_per_dev):
        before = int(allc[: start + d].sum())
        keep = max(0, min(n - before, counts[d]))
        kept: List[Any] = []
        acc = 0
        for c in frags:
            if acc >= keep:
                break
            t = min(c.n, keep - acc)
            kept.append(c if t == c.n else _slice_hchunk(c, 0, t))
            acc += t
        outs.append(ChunkSource(lambda ks=tuple(kept): iter(ks),
                                schema, chunk_rows))
    return _DevStreams(outs)


def _apply_leg_ops(dev: _DevStreams, ops: List[StageOp], config, job_root,
                   mesh, stats=None) -> _DevStreams:
    """Leg ops with whole-stream semantics over a stage input's
    per-device streams: chunk-local runs and per-partition globals apply
    per device through exec/stream_exec; a GLOBAL take coordinates
    across the gang eagerly (mirrored — every process walks the same
    stages in the same order, so the allgather lines up)."""
    from dryad_tpu.exec import stream_exec

    for kind, payload in stream_exec._split_leg_ops(list(ops)):
        if kind == "local":
            dev = _DevStreams([
                stream_exec._stream_local(cs, payload, config,
                                          stats=stats)
                for cs in dev.streams])
            continue
        if payload.kind in _UNSUPPORTED:
            raise StreamPlanError(
                f"op {payload.kind!r} is not supported over cluster "
                f"streams: {_UNSUPPORTED[payload.kind]}",
                code="DTA003", span=payload.span)
        if payload.kind == "take" and payload.params.get("global"):
            dev = _global_take(dev, payload.params["n"], mesh)
            continue
        dev = _DevStreams([
            stream_exec._stream_global(cs, payload, config, job_root,
                                       stats=stats)
            for cs in dev.streams])
    return dev


def _run_body(legs_out: List[_DevStreams], body: List[StageOp], config,
              job_root, mesh, stats=None) -> _DevStreams:
    """Stage body over (bucket-aligned) per-device streams; per-device
    ops stream independently, a global take coordinates via
    ``_global_take``."""
    from dryad_tpu.exec import stream_exec

    dpp = len(legs_out[0].streams)
    cur = legs_out[0]
    rest = list(legs_out[1:])
    for op in body:
        if op.kind in ("join", "apply2", "semi_anti"):
            r = rest.pop(0)
            outs = []
            for d in range(dpp):
                right_b, right_h = stream_exec._materialize_small(
                    r.streams[d], config, "right/build")
                outs.append(stream_exec._stream_local(
                    cur.streams[d], [], config, extra_right=right_b,
                    right_chunk=right_h, body_op=op, stats=stats))
            cur = _DevStreams(outs)
        elif op.kind == "concat":
            r = rest.pop(0)
            cur = _DevStreams([
                stream_exec._concat_sources(cur.streams[d], r.streams[d])
                for d in range(dpp)])
        elif op.kind == "zip":
            r = rest.pop(0)
            cur = _DevStreams([
                stream_exec._zip_sources(cur.streams[d], r.streams[d],
                                         op.params.get("suffix", "_r"))
                for d in range(dpp)])
        elif op.kind in _UNSUPPORTED:
            raise StreamPlanError(
                f"op {op.kind!r} is not supported over cluster "
                f"streams: {_UNSUPPORTED[op.kind]}",
                code="DTA003", span=op.span)
        elif op.kind == "take" and op.params.get("global"):
            cur = _global_take(cur, op.params["n"], mesh)
        elif op.kind in stream_exec._STREAM_KINDS \
                or op.kind == "dgroup_merge":
            cur = _DevStreams([
                _body_stream_global(cur.streams[d], op, config, job_root)
                for d in range(dpp)])
        elif op.kind in stream_exec._LOCAL_KINDS:
            cur = _DevStreams([
                stream_exec._stream_local(cur.streams[d], [op], config,
                                          stats=stats)
                for d in range(dpp)])
        else:
            raise StreamPlanError(
                f"op {op.kind!r} unsupported over cluster streams",
                code="DTA003", span=op.span)
    return cur


def _body_stream_global(cs, op: StageOp, config, job_root):
    from dryad_tpu.exec import stream_exec

    if op.kind == "dgroup_merge":
        # decomposable reduce-side merge over the bucket stream: merge
        # partial-state rows, finalizing per the op
        import jax

        from dryad_tpu.exec import ooc
        from dryad_tpu.ops import kernels

        keys = list(op.params["keys"])
        decs, box = op.params["decs"], op.params["box"]
        final = op.params["finalize"]

        def run(b):
            return kernels.group_decompose_merge(b, keys, decs, box, final)

        def it():
            frags = list(cs)
            merged = ooc._concat_hchunks(cs.schema, frags)
            capm = 1
            while capm < max(merged.n, 1):
                capm *= 2
            out = ooc._batch_to_chunk(jax.jit(run)(
                ooc._chunk_to_batch(merged, capm)))
            yield out

        probe = ooc._batch_to_chunk(jax.jit(run)(
            ooc._chunk_to_batch(ooc.HChunk.empty_like(cs.schema), 1)))
        return ooc.ChunkSource(it, ooc.chunk_schema(probe), cs.chunk_rows)
    return stream_exec._stream_global(cs, op, config, job_root)


# ---------------------------------------------------------------------------
# the runner


def execute_stream_plan(plan_json: str, fn_table, source_specs, mesh,
                        event_log=None, store_path: Optional[str] = None,
                        store_partitioning: Optional[Dict[str, Any]] = None,
                        collect: Any = True, config=None,
                        keep_token: Optional[str] = None,
                        release: tuple = (),
                        store_compression: Optional[str] = None):
    """Streamed counterpart of runtime/exec_common.execute_plan: same
    submission contract ((table, extras) back to the worker loop), plan
    executed as chunk waves + per-device bucket streams."""
    import jax

    from dryad_tpu.exec import ooc
    from dryad_tpu.exec.stream_exec import chunks_to_table
    from dryad_tpu.plan.serialize import graph_from_json
    from dryad_tpu.runtime import exec_common
    from dryad_tpu.runtime.stream_cluster import (_gathered_bounds,
                                                  _host_allgather,
                                                  _sample_pass,
                                                  _write_partitions)
    from dryad_tpu.utils.config import JobConfig

    config = config or JobConfig()
    ev = event_log or (lambda e: None)
    for tok in release:
        exec_common._RESIDENT.pop(tok, None)

    sources: Dict[str, Any] = {}
    for key, spec in source_specs.items():
        if spec.get("kind") == "store_stream":
            sources[key] = _StreamSpec(spec)
        elif spec.get("kind") == "resident":
            tok = spec["token"]
            from dryad_tpu.runtime.sources import MissingResidentToken
            if tok not in exec_common._RESIDENT:
                raise MissingResidentToken(tok)
            sources[key] = exec_common._RESIDENT[tok]
        else:
            from dryad_tpu.runtime.sources import build_source
            sources[key] = build_source(spec, mesh,
                                        resident=exec_common._RESIDENT)
    graph = graph_from_json(plan_json, fn_table=fn_table, sources=sources)

    nprocs = jax.process_count()
    dpp = mesh.devices.size // nprocs
    start = jax.process_index() * dpp
    job_root = tempfile.mkdtemp(prefix="dryad-splan-")

    def as_dev_streams(x) -> _DevStreams:
        if isinstance(x, _DevStreams):
            return x
        if isinstance(x, _StreamSpec):
            return _source_streams(x.spec, mesh, config)
        # device-resident PData (loop state, columns, stores)
        return _resident_streams(x, mesh, config)

    import time

    results: Dict[int, _DevStreams] = {}
    stage_stats: List[Tuple[int, Any, Dict[str, Any]]] = []
    for st in graph.topo_order():
        t0 = time.time()
        # per-stage prefetch accounting: stalls measured while this
        # stage's waves/legs drain surface on its stream_stage_done
        stats = ooc.PrefetchStats()
        legs_out: List[_DevStreams] = []
        for leg in st.legs:
            if isinstance(leg.src, int):
                src = results[leg.src]
            elif leg.src[0] == "source":
                src = as_dev_streams(leg.src[1])
            else:
                raise StreamPlanError(
                    "placeholders are not supported in streamed cluster "
                    "plans (do_while ships loop state as residents)",
                    code="DTA002")
            src = as_dev_streams(src)
            if leg.exchange is None:
                legs_out.append(_apply_leg_ops(src, list(leg.ops),
                                               config, job_root, mesh,
                                               stats=stats))
                continue
            # split leg ops: whole-stream prefix runs host-side per
            # device; the trailing wave-fusable suffix rides the program
            ops = list(leg.ops)
            cut = len(ops)
            while cut > 0 and ops[cut - 1].kind in _WAVE_FUSABLE:
                cut -= 1
            pre, fus = ops[:cut], ops[cut:]
            pre_dev = src
            if pre:
                pre_dev = _apply_leg_ops(src, pre, config, job_root,
                                         mesh, stats=stats)
            bounds = np.zeros((0,), np.uint32)
            if leg.exchange.kind == "range":
                # sampled global quantile bounds (DryadLinqSampler.cs:42
                # role) from the exchange's own input streams
                samples = []
                for cs in pre_dev.streams:
                    s, _, _ = _sample_pass(
                        cs, *leg.exchange.sort_keys()[0])
                    samples.append(s)
                merged = (np.concatenate(samples) if samples
                          else np.zeros((0,), np.uint32))
                from dryad_tpu.runtime.stream_cluster import _MAX_SAMPLES
                if len(merged) > _MAX_SAMPLES:
                    merged = merged[np.linspace(
                        0, len(merged) - 1,
                        _MAX_SAMPLES).astype(np.int64)]
                bounds = _gathered_bounds(merged, mesh,
                                          mesh.devices.size)
            compact = _compact_fn_for(st) if any(
                o.kind in ("group", "dgroup_partial", "dgroup_local")
                for o in fus) else None
            legs_out.append(_run_leg_waves(pre_dev, fus, leg.exchange,
                                           mesh, config, bounds, compact,
                                           job_root, stats=stats))
        out = _run_body(legs_out, list(st.body), config, job_root, mesh,
                        stats=stats)
        results[st.id] = out
        snap = stats.snapshot()
        ev({"event": "stream_stage_done", "stage": st.id,
            "label": st.label, "wall_s": round(time.time() - t0, 4),
            "prefetch_stalls": snap["stalls"],
            "prefetch_stall_s": snap["stall_s"]})
        if snap["stalls"]:
            ev({"event": "prefetch_stall", "stage": st.id, **snap})
        # exchange-free stages compose LAZY streams: their prefetchers
        # stall later, when the final drain (or a downstream stage's
        # waves) actually pulls — keep the stats object so those late
        # stalls can be reported after the drain instead of lost
        stage_stats.append((st.id, stats, snap))

    final = results[graph.out_stage]
    extras: Dict[str, Any] = {}

    drained: Optional[List[List[Any]]] = None

    def drain() -> List[List[Any]]:
        nonlocal drained
        if drained is None:
            drained = [list(cs) for cs in final.streams]
        return drained

    if keep_token is not None:
        # materialize the (small: loop state / cached) result as gang-
        # resident PData with MIRRORED capacity (allgathered max)
        from dryad_tpu.exec.data import PData

        chunks = [ooc._concat_hchunks(final.schema, frags)
                  for frags in drain()]
        local_max = max([c.n for c in chunks] + [1])
        gmax = int(_host_allgather(
            np.asarray([local_max], np.int32), mesh).max())
        capm = 1
        while capm < gmax:
            capm *= 2
        batch = _put_aligned(chunks, final.schema, capm, mesh)
        pd = PData(batch, mesh.devices.size)
        exec_common._RESIDENT[keep_token] = pd
        extras["resident_capacity"] = pd.capacity

    table = None
    if collect == "count":
        # >HBM row counts exceed int32, and jax without x64 silently
        # truncates int64 arrays — ship (hi, lo) uint32 lanes
        local = sum(c.n for frags in drain() for c in frags)
        arr = np.asarray([[local >> 32, local & 0xFFFFFFFF]], np.uint32)
        allc = _host_allgather(arr, mesh).astype(np.uint64)
        table = int(sum((int(h) << 32) | int(l)
                        for h, l in allc.reshape(-1, 2)))
    elif collect:
        merged: List[Any] = [c for frags in drain() for c in frags]
        cs = ooc.ChunkSource(lambda: iter(merged), final.schema,
                             max(final.chunk_rows, 1))
        table = chunks_to_table(cs)
    if store_path is not None:
        part_chunks = drain()
        part_ids = list(range(start, start + dpp))
        _write_partitions(store_path, final.schema, part_chunks, part_ids,
                          mesh, final.chunk_rows,
                          partitioning=store_partitioning,
                          compression=store_compression,
                          capacity=final.chunk_rows)

    # late stalls: every consumer path above has drained by now — emit
    # the per-stage delta beyond what the stage's own stream_stage_done
    # already carried (obs/analyze folds prefetch_stall events into the
    # report TOTALS only, so this cannot double-count stage rows)
    for sid, stats, snap in stage_stats:
        late = stats.snapshot()
        d_stalls = late["stalls"] - snap["stalls"]
        if d_stalls > 0:
            ev({"event": "prefetch_stall", "stage": sid,
                "stalls": d_stalls,
                "stall_s": round(late["stall_s"] - snap["stall_s"], 6),
                "chunks": late["chunks"], "late": True})

    import shutil
    shutil.rmtree(job_root, ignore_errors=True)
    return table, extras
