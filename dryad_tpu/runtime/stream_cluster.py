"""Shared host<->mesh plumbing for streamed gang execution.

Helpers used by runtime/stream_plan.py (the planned streamed runner) and
runtime/exec_common.py (parallel collect / parallel store output):
per-process host allgather, wave placement onto the global mesh, local
shard readback, parallel partition writes with process-0 metadata commit,
and range-bounds sampling (DryadLinqSampler.cs:42 role).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["StreamJobError", "local_batch_chunks"]

_SAMPLES_PER_CHUNK = 512
_MAX_SAMPLES = 8192


class StreamJobError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# host <-> mesh plumbing (worker side)


def _host_allgather(arr: np.ndarray, mesh) -> np.ndarray:
    """Per-process host array [k, ...] -> [nprocs, k, ...] everywhere.
    Single collective over the dcn axis; nprocs=1 short-circuits."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    nprocs = jax.process_count()
    if nprocs == 1:
        return arr[None]
    from dryad_tpu.parallel.mesh import HOST_AXIS
    gshape = (nprocs,) + arr.shape
    sh = NamedSharding(mesh, P(HOST_AXIS))

    def cb(idx):
        return arr[None]

    garr = jax.make_array_from_callback(gshape, sh, cb)
    rep = jax.jit(lambda x: x,
                  out_shardings=NamedSharding(mesh, P()))(garr)
    return np.asarray(rep)


def _split_local(chunk, schema, dpp: int, chunk_rows: int):
    """Block-split one host chunk across the process's dpp local devices;
    returns (cols [dpp, chunk_rows, ...] zero-padded, counts [dpp])."""
    n = chunk.n if chunk is not None else 0
    base, rem = divmod(n, dpp)
    sizes = [base + (1 if d < rem else 0) for d in range(dpp)]
    offs = np.cumsum([0] + sizes)
    cols: Dict[str, Any] = {}
    for k, spec in schema.items():
        if spec["kind"] == "str":
            L = spec["max_len"]
            sd = np.zeros((dpp, chunk_rows, L), np.uint8)
            sl = np.zeros((dpp, chunk_rows), np.int32)
            if n:
                d, l = chunk.cols[k]
                for p in range(dpp):
                    sd[p, :sizes[p]] = d[offs[p]:offs[p + 1]]
                    sl[p, :sizes[p]] = l[offs[p]:offs[p + 1]]
            cols[k] = (sd, sl)
        else:
            dt = np.dtype(spec["dtype"])
            tail = tuple(spec.get("shape", ()))
            sa = np.zeros((dpp, chunk_rows) + tail, dt)
            if n:
                v = chunk.cols[k]
                for p in range(dpp):
                    sa[p, :sizes[p]] = v[offs[p]:offs[p + 1]]
            cols[k] = sa
    return cols, np.asarray(sizes, np.int32)


def _put_wave(chunk, schema, chunk_rows: int, mesh):
    """Place one process-local chunk onto the GLOBAL mesh batch
    [P_total, chunk_rows, ...]: each process fills only its own device
    rows (make_array_from_callback touches addressable shards only)."""
    import jax
    from dryad_tpu.data.columnar import Batch, StringColumn
    from dryad_tpu.parallel.mesh import batch_sharding

    P_total = mesh.devices.size
    nprocs = jax.process_count()
    dpp = P_total // nprocs
    start = jax.process_index() * dpp
    local_cols, local_counts = _split_local(chunk, schema, dpp, chunk_rows)
    sharding = batch_sharding(mesh)

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
    return Batch(cols, put(local_counts))


def local_batch_chunks(local) -> Tuple[Dict[str, Any], List[Any]]:
    """Split a host-side local Batch [dpp, cap, ...] (from
    _read_local_shards) into per-device TRIMMED HChunks plus their schema
    — the one conversion between sharded batches and host chunk rows
    (used by wave draining and the parallel store writers)."""
    from dryad_tpu.data.columnar import StringColumn
    from dryad_tpu.exec.ooc import HChunk

    counts = np.asarray(local.count)
    dpp = counts.shape[0]
    schema: Dict[str, Any] = {}
    for k, v in local.columns.items():
        if isinstance(v, StringColumn):
            schema[k] = {"kind": "str",
                         "max_len": int(np.asarray(v.data).shape[2])}
        else:
            a = np.asarray(v)
            schema[k] = {"kind": "dense", "dtype": a.dtype.name,
                         "shape": list(a.shape[2:])}
    chunks: List[Any] = []
    for d in range(dpp):
        n = int(counts[d])
        cols: Dict[str, Any] = {}
        for k, v in local.columns.items():
            if isinstance(v, StringColumn):
                cols[k] = (np.asarray(v.data)[d][:n],
                           np.asarray(v.lengths)[d][:n])
            else:
                cols[k] = np.asarray(v)[d][:n]
        chunks.append(HChunk(cols, n))
    return schema, chunks


def _read_local_shards(tree, start: int, dpp: int):
    """Pull a mesh-sharded pytree's LOCAL partitions to host:
    leaf [P, ...] -> np [dpp, ...] (this process's rows only)."""
    import jax

    def read(arr):
        parts: List[Any] = [None] * dpp
        for sh in arr.addressable_shards:
            g = sh.index[0].start if isinstance(sh.index[0], slice) else 0
            if start <= g < start + dpp:
                parts[g - start] = np.asarray(sh.data)[0]
        return np.stack(parts)

    return jax.tree.map(read, tree)


# ---------------------------------------------------------------------------
# wave programs


def _squeeze(b):
    import jax
    return jax.tree.map(lambda x: x[0], b)


def _expand(b):
    import jax
    return jax.tree.map(lambda x: x[None], b)


# ---------------------------------------------------------------------------
# parallel store output (each worker writes its own partitions)


def _write_partitions(out_path: str, schema, part_chunks, part_ids,
                      mesh, chunk_rows: int,
                      partitioning: Optional[Dict[str, Any]] = None,
                      compression: Optional[str] = None,
                      capacity: Optional[int] = None):
    """Every process writes its own partition files under out.tmp; counts
    and checksums are allgathered; process 0 merges meta.json and commits
    the rename (parallel output — DrOutputVertex per-vertex writers,
    DrVertex.h:325-351 — instead of funneling through one process).
    Checksums cover the UNCOMPRESSED segments (store read contract).

    ``hdfs://`` targets write the same way — every worker uploads ITS
    OWN partitions through the WebHDFS adapter into the shared temp
    directory, process 0 commits meta + the (atomic) HDFS rename — the
    reference's per-vertex HDFS output writers (DrHdfsClient.cpp write
    side, channelbufferhdfs.cpp)."""
    import jax
    from dryad_tpu.exec import ooc
    from dryad_tpu.io import store

    if compression not in (None, "gzip"):
        raise StreamJobError(f"unknown compression {compression!r}")
    if out_path.startswith("s3://"):
        raise StreamJobError(
            "cluster parallel output to s3:// is not supported (no "
            "atomic multi-object commit across writers); use a shared "
            "filesystem or hdfs:// target")
    # clear any stale temp dir from a crashed previous job BEFORE anyone
    # uploads, behind a barrier — a leftover part-NNNNN.bin from a dead
    # run with more partitions would otherwise ride the rename into the
    # committed store.  Process 0 clears; the allgather is the fence.
    if jax.process_index() == 0:
        store.clear_shared_temp(out_path)
    _host_allgather(np.zeros((1,), np.int32), mesh)
    writer = store.StoreWriter(out_path, store.store_schema(schema),
                               partitioning, compression, capacity,
                               shared=True)
    for g, chunks in zip(part_ids, part_chunks):
        merged = ooc._concat_hchunks(schema, list(chunks))
        writer.add_chunk(merged.n, merged.cols, g)

    # allgather (counts, checksums) — doubles as the write barrier.
    # uint32 lanes only: jax without x64 silently truncates 64-bit arrays,
    # so a partition's 64-bit digest rides as (hi, lo) words; its leaf
    # digests do not ride, and the manifest records none (readers verify
    # by the partition digest alone)
    sums = np.asarray([int(h, 16) for h in writer.checksums], np.uint64)
    arr = np.stack([np.asarray(writer.counts, np.uint32),
                    (sums >> np.uint64(32)).astype(np.uint32),
                    sums.astype(np.uint32)], axis=1)
    allinfo = _host_allgather(arr, mesh)  # [nprocs, dpp, 3]
    if jax.process_index() == 0:
        flat = allinfo.reshape(-1, 3).astype(np.uint64)
        writer.commit(
            [int(x) for x in flat[:, 0]],
            ["%016x" % int((h << np.uint64(32)) | l)
             for h, l in zip(flat[:, 1], flat[:, 2])])
    # post-commit barrier so no worker reports success (or starts the next
    # job's waves) before the rename happened
    _host_allgather(np.zeros((1,), np.int32), mesh)


# ---------------------------------------------------------------------------
# terminals


def _sample_pass(cs, key: Optional[str], descending: bool = False):
    """One full pass over the local stream: (lane samples, chunk count,
    row count).  Samples empty when key is None.  The lane is the first
    sort lane of ``key`` in its direction — the lane
    shuffle.range_key_lanes gives the streamed range exchange."""
    from dryad_tpu.exec import ooc

    samples: List[np.ndarray] = []
    nchunks = 0
    rows = 0
    for chunk in cs:
        nchunks += 1
        rows += chunk.n
        if key is None or chunk.n == 0:
            continue
        spec = cs.schema[key]
        take = min(chunk.n, _SAMPLES_PER_CHUNK)
        idx = np.linspace(0, chunk.n - 1, take).astype(np.int64)
        col = chunk.cols[key]
        if spec["kind"] == "str":
            sub = (col[0][idx], col[1][idx])
        else:
            sub = col[idx]
        samples.append(ooc._host_sort_lanes(spec, sub, descending)[0])
    s = (np.concatenate(samples) if samples
         else np.zeros((0,), np.uint32))
    if len(s) > _MAX_SAMPLES:
        s = s[np.linspace(0, len(s) - 1, _MAX_SAMPLES).astype(np.int64)]
    return s, nchunks, rows


def _gathered_bounds(samples: np.ndarray, mesh, n_buckets: int
                     ) -> np.ndarray:
    """Allgather per-process samples and cut global quantile bounds —
    the distributed form of the reference's sampling stage
    (DryadLinqSampler.cs:42 + DrDynamicRangeDistributor.h:23)."""
    from dryad_tpu.exec import ooc

    padded = np.zeros((_MAX_SAMPLES,), np.uint32)
    padded[:len(samples)] = samples
    meta = np.asarray([len(samples)], np.uint32)
    all_s = _host_allgather(padded, mesh)     # [nprocs, SMAX]
    all_n = _host_allgather(meta, mesh)       # [nprocs, 1]
    merged = np.concatenate([all_s[p, :int(all_n[p, 0])]
                             for p in range(all_s.shape[0])])
    return ooc._bounds_from_samples(merged, n_buckets)
