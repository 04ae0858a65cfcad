"""Partitioned (sharded) datasets on the device mesh.

The counterpart of the reference's partitioned files + channels: a dataset in
flight is a stacked Batch whose columns carry a leading partition dimension
[P, capacity, ...] sharded over the mesh's ``dp`` axis — i.e. partition p
lives in device p's HBM.  Stage boundaries materialize these (the replay
anchor for fault tolerance), where the reference materializes temp files
(channelbuffernativewriter.cpp) served over HTTP.
"""

from __future__ import annotations

import dataclasses
from functools import lru_cache, partial
from typing import (Any, Dict, Iterator, List, Mapping, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from dryad_tpu.data.columnar import Batch, Int64Column, StringColumn
from dryad_tpu.parallel.mesh import batch_sharding

__all__ = ["PData", "pdata_from_host", "pdata_to_host", "put_batch",
           "fetch_partitions", "replicate_tree", "collect_replicated",
           "batch_nbytes", "key_hashes"]


def batch_nbytes(tree) -> int:
    """Bytes a pytree of arrays holds, from shapes alone (no data is
    touched, on the host or the device)."""
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)))


def mesh_is_multiprocess(mesh) -> bool:
    """True when the mesh spans more than one OS process (runtime cluster
    mode) — host<->device placement must then go through per-process
    addressable shards instead of whole-array device_put."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def put_batch(tree, mesh):
    """Place a host pytree onto the mesh with the standard partition
    sharding.  Single-process: plain device_put.  Multi-process: every
    process holds the same full host value and fills only its addressable
    shards (jax.make_array_from_callback) — the runtime-cluster analogue of
    the reference's per-vertex input channel reads."""
    sharding = batch_sharding(mesh)
    if not mesh_is_multiprocess(mesh):
        return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])

    return jax.tree.map(put, tree)


# -- device -> host: a result that is about to be stored ----------------------

# Bytes of one chunk of one partition (its rows: the largest power of two
# that stays under this, and never more than the capacity).  Large enough
# that a copy runs at the link's rate, small enough that what is moved past
# a partition's count stays a few per cent of it.  A power of two ALWAYS, a
# column shorter than one chunk included (its last chunk overlaps the one
# before): the wide reshape of 400,000 rows of 6-11 bytes took the TPU
# compiler 138-164 s a column where 262,144 rows take 0.7 s (PERF.md
# section 6, PR 36).
_FETCH_CHUNK_BYTES = 8 << 20

# A chunk whose rows are narrower than this many elements leaves the device
# as rows of this many.  The TPU holds [rows, 10] or [rows, 90] bytes
# column-major (the long dimension minor), and the host array of such a
# copy keeps that order: making it contiguous is a transpose on one host
# thread, 3.7-3.9 s for 755 MB, where the same bytes as wide rows arrive
# contiguous at the link's rate (PERF.md section 6, PR 29).
_FETCH_LINK_WIDTH = 512


def _fetch_chunk_rows(cap: int, row_bytes: int) -> int:
    rows = max(1, min(_FETCH_CHUNK_BYTES // max(row_bytes, 1), cap))
    return 1 << (rows.bit_length() - 1)


def _row_elems(x) -> int:
    return int(np.prod(x.shape[2:], dtype=np.int64))


def _leaves_wide(x) -> bool:
    """Whether a chunk of the stacked ``[P, cap, ...]`` array ``x`` is
    reshaped to wide rows before it is copied: read from the column's
    shape alone (it has trailing dimensions, and they are narrow)."""
    return x.ndim > 2 and 0 < _row_elems(x) < _FETCH_LINK_WIDTH


@lru_cache(maxsize=None)
def _fetch_chunk_program(rows: int, wide: bool, out_sharding):
    """``x[:, start:start + rows]`` of a stacked ``[P, cap, ...]`` array,
    every shard on its own device; ``start`` is traced, so one program
    serves every chunk of every count.  ``wide``: each partition's chunk
    flattened, padded to whole rows of ``_FETCH_LINK_WIDTH`` elements."""
    def fetch_chunk(x, start):
        c = lax.dynamic_slice_in_dim(x, start, rows, axis=1)
        if wide:
            c = c.reshape(c.shape[0], -1)
            c = jnp.pad(c, ((0, 0), (0, -c.shape[1] % _FETCH_LINK_WIDTH)))
            c = c.reshape(c.shape[0], -1, _FETCH_LINK_WIDTH)
        return c
    return jax.jit(fetch_chunk, out_shardings=out_sharding)


def _partition_shards(x) -> Dict[Tuple[int, int], jax.Array]:
    """{(first partition, one past the last): the addressable shard's data}
    of an array whose leading dimension is the partition."""
    out: Dict[Tuple[int, int], jax.Array] = {}
    for s in x.addressable_shards:
        lo, hi, _ = s.index[0].indices(x.shape[0])
        out.setdefault((lo, hi), s.data)
    return out


def _partition_sharding(x):
    """The sharding of ``x``'s partition dimension alone (what a chunk of
    it keeps), or None to leave a single device's output where it is."""
    sh = x.sharding
    if isinstance(sh, NamedSharding):
        return NamedSharding(sh.mesh, PartitionSpec(*sh.spec[:1]))
    return None


def fetch_partitions(leaves: Sequence[Any], counts: np.ndarray
                     ) -> Iterator[Tuple[List[np.ndarray], int, int]]:
    """Bring the valid rows of stacked ``[P, cap, ...]`` arrays to the
    host: for partition 0, 1, ... in turn ``(pieces, moved_bytes,
    chunks)``, where ``pieces`` are contiguous host arrays that, end to
    end, are ``leaves[0][p, :counts[p]]``, then ``leaves[1][p, :counts[p]]``
    and so on — each leaf as the chunks it arrived in, not copied again.

    How a result leaves the device.  Partition p is the shard on device
    p, and a shard is cut into chunks of a fixed number of rows by ONE
    program a column shape (a ``dynamic_slice`` with a traced start —
    nothing that is compiled depends on a count).  Only the chunks that
    hold valid rows are moved, so at most one chunk a leaf a partition
    crosses the link in vain.  A chunk of narrow rows (a string column's
    bytes) is reshaped on the device to wide rows first
    (``_FETCH_LINK_WIDTH``), so that it arrives contiguous.  Every chunk
    of every leaf of every partition is cut and its copy started
    (``copy_to_host_async``) before the first is awaited: the links of
    all devices work at once, and partition p is handed on while p + 1
    ... are still on their way.  Until its host copy has been taken a
    chunk stays in HBM, at most one more copy of the result.  Leaves
    already on the host are sliced in place; a partition no shard of this
    process holds is an error."""
    counts = np.asarray(counts)
    plans = []          # per leaf: None (host) or (rows, {(lo, hi): [chunk]})
    for x in leaves:
        if not isinstance(x, jax.Array):
            plans.append(None)
            continue
        cap = x.shape[1]
        rows = _fetch_chunk_rows(cap, x.dtype.itemsize * _row_elems(x))
        program = _fetch_chunk_program(rows, _leaves_wide(x),
                                       _partition_sharding(x))
        spans = list(_partition_shards(x))
        need = {sp: -(-int(counts[sp[0]:sp[1]].max(initial=0)) // rows)
                for sp in spans}
        chunks: Dict[Tuple[int, int], list] = {sp: [] for sp in spans}
        for i in range(max(need.values(), default=0)):
            out = _partition_shards(program(x, min(i * rows, cap - rows)))
            for sp in spans:
                if i < need[sp]:
                    out[sp].copy_to_host_async()
                    chunks[sp].append(out[sp])
        plans.append((rows, chunks))

    for p, n in enumerate(counts.tolist()):
        pieces: List[np.ndarray] = []
        moved = nchunks = 0
        for x, plan in zip(leaves, plans):
            if plan is None:
                pieces.append(np.ascontiguousarray(np.asarray(x)[p, :n]))
                continue
            rows, chunks = plan
            sp = next((sp for sp in chunks if sp[0] <= p < sp[1]), None)
            if sp is None:
                raise ValueError(f"partition {p} is on no device of this "
                                 "process: it cannot be fetched here")
            if p == sp[0]:              # a shard's chunks count once
                moved += sum(c.nbytes for c in chunks[sp])
                nchunks += len(chunks[sp])
            cap, row = x.shape[1], x.shape[2:]
            for i in range(-(-n // rows)):
                start = min(i * rows, cap - rows)
                # the host's copy stays, the device's goes
                host = chunks[sp][i] = np.asarray(chunks[sp][i])
                host = host[p - sp[0]].reshape(-1)[:rows * _row_elems(x)]
                pieces.append(host.reshape((rows,) + row)
                              [i * rows - start:n - start])
            if n == 0:                  # a leaf is never no piece at all
                pieces.append(np.empty((0,) + row, x.dtype))
        yield pieces, moved, nchunks


def replicate_tree(tree, mesh):
    """All-gather a sharded pytree to a fully-replicated layout so every
    process can read it host-side (multihost-safe np.asarray)."""
    from jax.sharding import NamedSharding, PartitionSpec
    rep = NamedSharding(mesh, PartitionSpec())
    return jax.jit(lambda t: t, out_shardings=rep)(tree)


def shrink_bucket_cap(counts: np.ndarray, cap: int,
                      min_capacity: int = 1024,
                      waste_factor: int = 4) -> int | None:
    """Shared shrink-before-collect policy: pow2 bucket >= max count when
    the capacity is grossly oversized, else None (no shrink).  Thresholds
    come from JobConfig.collect_shrink_min_capacity /
    collect_shrink_waste_factor."""
    max_n = int(counts.max()) if counts.size else 0
    if cap <= min_capacity or cap <= waste_factor * max(max_n, 1):
        return None
    bucket = 1
    while bucket < max(max_n, 1):
        bucket *= 2
    return min(bucket, cap)


def _shrink_knobs(config) -> tuple:
    if config is None:
        from dryad_tpu.utils.config import JobConfig
        config = JobConfig()
    return (config.collect_shrink_min_capacity,
            config.collect_shrink_waste_factor)


def collect_replicated(pd: "PData", mesh, unpack: bool = True,
                       config=None) -> Optional[Dict[str, Any]]:
    """Multi-process collect: shrink (deterministically, mirrored on every
    process), replicate over the mesh, and unpack host-side.  All processes
    must call this (the replication is a collective); pass ``unpack=False``
    on processes that don't need the host table (they return None without
    paying the host-side string unpack)."""
    counts = np.asarray(replicate_tree(pd.batch.count, mesh))
    new_cap = shrink_bucket_cap(counts, pd.capacity,
                                *_shrink_knobs(config))
    if new_cap is not None:
        pd = shrink_pdata(pd, new_cap)
    rep = replicate_tree(pd.batch, mesh)
    if not unpack:
        return None
    return pdata_to_host(PData(rep, pd.nparts))


@dataclasses.dataclass
class PData:
    """Stacked per-partition batch: columns [P, cap, ...], count [P]."""

    batch: Batch
    nparts: int

    @property
    def capacity(self) -> int:
        for c in self.batch.columns.values():
            return jax.tree.leaves(c)[0].shape[1]
        raise ValueError("empty PData")

    @property
    def counts(self) -> jax.Array:
        return self.batch.count  # [P]

    def total_rows(self) -> int:
        return int(np.asarray(self.counts).sum())


def _block_slices(n: int, parts: int):
    """Contiguous block partitioning (reference: input partition files map
    1:1 to vertices; we keep row order partition-major)."""
    base, rem = divmod(n, parts)
    out, start = [], 0
    for p in range(parts):
        size = base + (1 if p < rem else 0)
        out.append((start, start + size))
        start += size
    return out


def pdata_from_host(columns: Mapping[str, Any], mesh, nparts: int | None = None,
                    capacity: int | None = None, str_max_len: int = 64) -> PData:
    """Build a sharded PData from host columns (block-partitioned rows)."""
    nparts = nparts or mesh.devices.size
    n = None
    for v in columns.values():
        n = len(v)
        break
    if n is None:
        raise ValueError("no columns")
    slices = _block_slices(n, nparts)
    max_block = max(1, max(e - s for s, e in slices))
    cap = capacity or max_block
    if cap < max_block:
        raise ValueError(
            f"capacity {cap} too small: {n} rows over {nparts} partitions "
            f"needs per-partition capacity >= {max_block}")

    cols: Dict[str, Any] = {}
    for k, v in columns.items():
        if isinstance(v, (list, tuple)) and (
                n == 0 or isinstance(v[0], (str, bytes))):
            from dryad_tpu import native
            items = [x.encode() if isinstance(x, str) else bytes(x)
                     for x in v]
            data, lens = native.pack_bytes_list(items, str_max_len,
                                                max(n, 1))
            sd = np.zeros((nparts, cap, str_max_len), np.uint8)
            sl = np.zeros((nparts, cap), np.int32)
            for p, (s, e) in enumerate(slices):
                sd[p, : e - s] = data[s:e]
                sl[p, : e - s] = lens[s:e]
            cols[k] = StringColumn(sd, sl)
        else:
            arr = np.asarray(v)
            stacked = np.zeros((nparts, cap) + arr.shape[1:], arr.dtype)
            for p, (s, e) in enumerate(slices):
                stacked[p, : e - s] = arr[s:e]
            cols[k] = stacked
    counts = np.asarray([e - s for s, e in slices], np.int32)
    batch = put_batch(Batch(cols, counts), mesh)
    return PData(batch, nparts)


def pdata_from_packed_strings(data: np.ndarray, lens: np.ndarray, mesh,
                              column: str = "line",
                              nparts: int | None = None,
                              capacity: int | None = None) -> PData:
    """Build sharded PData from an already-packed [n, max_len] byte matrix
    (native.pack_lines output) without any per-row Python work."""
    nparts = nparts or mesh.devices.size
    n, max_len = data.shape
    slices = _block_slices(n, nparts)
    max_block = max(1, max(e - s for s, e in slices))
    cap = capacity or max_block
    if cap < max_block:
        raise ValueError(f"capacity {cap} < max block {max_block}")
    sd = np.zeros((nparts, cap, max_len), np.uint8)
    sl = np.zeros((nparts, cap), np.int32)
    for p, (s, e) in enumerate(slices):
        sd[p, : e - s] = data[s:e]
        sl[p, : e - s] = lens[s:e]
    batch = put_batch(Batch({column: StringColumn(sd, sl)},
                            np.asarray([e - s for s, e in slices],
                                       np.int32)), mesh)
    return PData(batch, nparts)


@partial(jax.jit, static_argnums=(1,))
def _shrink_batch(batch: Batch, new_cap: int) -> Batch:
    return jax.vmap(lambda b: b.gather(
        jnp.arange(new_cap, dtype=jnp.int32)).with_count(b.count))(batch)


def shrink_pdata(pd: PData, new_cap: int) -> PData:
    """Reduce per-partition capacity (device-side) before host transfer —
    collect() uses this so a 1M-capacity / 12-row result doesn't ship 1M
    padded rows through PCIe.  new_cap must cover max(counts)."""
    return PData(_shrink_batch(pd.batch, new_cap), pd.nparts)


def maybe_shrink_for_collect(pd: PData, config=None) -> PData:
    # pow2 buckets bound the number of shrink-program compiles
    new_cap = shrink_bucket_cap(np.asarray(pd.counts), pd.capacity,
                                *_shrink_knobs(config))
    return pd if new_cap is None else shrink_pdata(pd, new_cap)


def key_hashes(batch: Batch, counts, keys: Sequence[str]) -> np.ndarray:
    """The 64-bit key hash of every valid row of a stacked ``[P, cap]``
    batch over the columns ``keys``, as numpy ``uint64`` — the hash the
    joins and the group-bys compare (ops/hashing.hash_batch_keys), taken
    on the device, 8 bytes a row brought to the host.  What a store's
    ``unique`` declaration is verified over (io/store.check_unique)."""
    from dryad_tpu.ops.hashing import hash_batch_keys
    hi, lo = jax.jit(jax.vmap(
        lambda b: hash_batch_keys(b, list(keys))))(batch)
    hi, lo = np.asarray(hi), np.asarray(lo)
    live = np.arange(hi.shape[1])[None, :] < np.asarray(counts)[:, None]
    return (hi[live].astype(np.uint64) << np.uint64(32)) \
        | lo[live].astype(np.uint64)


def pdata_to_host(pd: PData) -> Dict[str, Any]:
    """Collect valid rows to host, partition order preserved."""
    from dryad_tpu import native

    counts = np.asarray(pd.counts)
    out: Dict[str, Any] = {}
    for k, v in pd.batch.columns.items():
        if isinstance(v, StringColumn):
            data = np.asarray(v.data)
            lens = np.asarray(v.lengths)
            vals = []
            for p in range(pd.nparts):
                n = int(counts[p])
                vals.extend(native.unpack_rows(data[p, :n], lens[p, :n]))
            out[k] = vals
            continue
        if isinstance(v, Int64Column):      # the two words -> numpy int64
            arr = Int64Column.to_numpy(v.hi, v.lo)
        else:
            arr = np.asarray(v)
        out[k] = np.concatenate(
            [arr[p, : counts[p]] for p in range(pd.nparts)], axis=0)
    return out
