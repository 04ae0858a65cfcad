"""Out-of-core chunked execution: HBM <-> host-RAM <-> disk streaming.

The reference runs every channel through disk with double-buffered async IO
(reference DryadVertex/.../channelbuffernativereader.cpp,
channelbuffernativewriter.cpp — ~4.5 kLoC of IO-completion-port double
buffering — and channelbufferqueue.cpp:777), so a vertex never needs its
whole partition in memory.  The TPU-native equivalent implemented here:

* a partition's logical data lives in host RAM (or a store on disk) as a
  stream of fixed-capacity CHUNKS;
* chunks stream through single-device jit programs with DOUBLE BUFFERING —
  JAX async dispatch overlaps the host->device transfer and compute of chunk
  i+1 with the device->host fetch of chunk i (the channelbufferqueue role);
* exchanges become a per-chunk device bucket-scatter (range or hash dest,
  computed and grouped on device) followed by host-side bucket
  accumulation — the moral equivalent of the reference's materialized
  pull-shuffle files (SURVEY.md §2.8), re-readable per bucket;
* merge phases (external sort, streaming group-aggregate) recurse on
  buckets until each fits the device chunk capacity.

This is the path that makes >HBM datasets (the 1 TB TeraSort north star,
BASELINE.md config 2) expressible on a bounded-HBM chip: device working set
is O(chunk_rows), independent of total data size.

Single-device by design: OOC streaming is the *per-chip* story; the
multi-chip story is the sharded executor (exec/executor.py).  A multi-host
deployment runs one OOC stream per host feeding the sharded exchanges.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from collections import deque
from typing import (Any, Callable, Dict, Iterable, Iterator, List, Optional,
                    Sequence, Tuple)

import numpy as np

import jax
import jax.numpy as jnp

from dryad_tpu.data.columnar import Batch, StringColumn
from dryad_tpu.ops import kernels
from dryad_tpu.ops.hashing import hash_batch_keys

__all__ = [
    "HChunk", "ChunkSource", "stream_map", "external_sort",
    "streaming_group_aggregate", "streaming_group_decomposable",
    "streaming_group_topk", "streaming_distinct",
    "write_chunks_to_store", "OOCError",
    "PrefetchStats", "prefetch_iter",
    "cache_entry_paths", "cached_chunk_source", "write_chunk_cache",
    "adopt_chunk_cache", "invalidate_cache_entry", "cache_source",
]


class OOCError(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# host chunk representation

# a host column is a dense ndarray [n, ...] or a (data [n, L] u8,
# lengths [n] i32) pair for strings
HostCol = Any


@dataclasses.dataclass
class HChunk:
    """One host-resident chunk of rows (trimmed: no padding)."""

    cols: Dict[str, HostCol]
    n: int

    @staticmethod
    def empty_like(schema: Dict[str, Any]) -> "HChunk":
        cols: Dict[str, HostCol] = {}
        for k, spec in schema.items():
            if spec["kind"] == "str":
                cols[k] = (np.zeros((0, spec["max_len"]), np.uint8),
                           np.zeros((0,), np.int32))
            else:
                cols[k] = np.zeros((0,) + tuple(spec.get("shape", ())),
                                   np.dtype(spec["dtype"]))
        return HChunk(cols, 0)


def chunk_schema(chunk: HChunk) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in chunk.cols.items():
        if isinstance(v, tuple):
            out[k] = {"kind": "str", "max_len": int(v[0].shape[1])}
        else:
            out[k] = {"kind": "dense", "dtype": v.dtype.name,
                      "shape": list(v.shape[1:])}
    return out


def _concat_hchunks(schema, frags: Sequence[HChunk]) -> HChunk:
    if not frags:
        return HChunk.empty_like(schema)
    cols: Dict[str, HostCol] = {}
    for k, spec in schema.items():
        if spec["kind"] == "str":
            cols[k] = (np.concatenate([f.cols[k][0] for f in frags]),
                       np.concatenate([f.cols[k][1] for f in frags]))
        else:
            cols[k] = np.concatenate([f.cols[k] for f in frags])
    return HChunk(cols, sum(f.n for f in frags))


def _slice_hchunk(chunk: HChunk, s: int, e: int) -> HChunk:
    cols = {k: ((v[0][s:e], v[1][s:e]) if isinstance(v, tuple) else v[s:e])
            for k, v in chunk.cols.items()}
    return HChunk(cols, e - s)


def _chunk_to_batch(chunk: HChunk, capacity: int) -> Batch:
    """Pad a host chunk to a fixed-capacity device Batch (async H2D)."""
    if chunk.n > capacity:
        raise OOCError(f"chunk of {chunk.n} rows > capacity {capacity}")
    pad = capacity - chunk.n
    cols: Dict[str, Any] = {}
    for k, v in chunk.cols.items():
        if isinstance(v, tuple):
            d = np.pad(v[0], ((0, pad), (0, 0)))
            l = np.pad(v[1], (0, pad))
            cols[k] = StringColumn(jax.device_put(d), jax.device_put(l))
        else:
            p = [(0, pad)] + [(0, 0)] * (v.ndim - 1)
            cols[k] = jax.device_put(np.pad(v, p))
    return Batch(cols, jnp.asarray(chunk.n, jnp.int32))


@functools.partial(jax.jit, static_argnums=(1,))
def _slice_rows(batch: Batch, m: int) -> Batch:
    """Device-side leading-dim slice (valid rows sit at the front after
    every compacting kernel)."""
    return jax.tree.map(lambda x: x[:m] if x.ndim else x, batch)


def _batch_to_chunk(batch: Batch) -> HChunk:
    """Fetch a device Batch's valid rows to host (blocks).

    The device->host link is orders of magnitude slower than HBM, so the
    batch is sliced ON
    DEVICE to the next pow2 >= count before transfer — pow2 buckets bound
    the number of slice-program compiles while cutting the transfer from
    full capacity to ~valid rows (channelbuffer write-coalescing role)."""
    n = int(batch.count)
    cap = 0
    for v in batch.columns.values():
        cap = v.data.shape[0] if isinstance(v, StringColumn) else v.shape[0]
        break
    m = 1
    while m < max(n, 1):
        m *= 2
    if m < cap:
        batch = _slice_rows(batch, m)
    cols: Dict[str, HostCol] = {}
    for k, v in batch.columns.items():
        if isinstance(v, StringColumn):
            cols[k] = (np.asarray(v.data)[:n], np.asarray(v.lengths)[:n])
        else:
            cols[k] = np.asarray(v)[:n]
    return HChunk(cols, n)


# ---------------------------------------------------------------------------
# chunk sources


class ChunkSource:
    """A re-iterable stream of HChunks with a fixed schema.

    The OOC analogue of a partitioned input file list
    (reference DrPartitionFile.cpp): callers iterate it multiple times
    (sampling pass + scatter pass), so the factory must produce a fresh
    iterator per call.
    """

    # uncompressed hdfs:// partitions at least this big stream via
    # unverifiable ranged reads instead of the whole-part verified read
    # (see from_store) — sized so anything comfortably holdable in host
    # RAM keeps its checksum protection
    RANGED_STREAM_MIN_BYTES = 256 << 20

    def __init__(self, make_iter: Callable[[], Iterator[HChunk]],
                 schema: Dict[str, Any], chunk_rows: int):
        self._make_iter = make_iter
        self.schema = schema
        self.chunk_rows = chunk_rows
        # restart-stable content identity of the SOURCE data, when one
        # exists (store-backed / text-file sources set it) — the
        # re-streaming cache tier (Dataset.cache) folds it into cache
        # keys so changed source data can never serve a stale cache
        self.fingerprint: Optional[str] = None

    def __iter__(self) -> Iterator[HChunk]:
        return self._make_iter()

    # -- constructors ------------------------------------------------------

    @staticmethod
    def from_arrays(columns: Dict[str, Any], chunk_rows: int | None = None,
                    str_max_len: int = 64) -> "ChunkSource":
        """Slice host arrays (dense ndarrays or str/bytes lists) into
        chunks."""
        if chunk_rows is None:
            from dryad_tpu.utils.config import JobConfig
            chunk_rows = JobConfig().ooc_chunk_rows
        conv: Dict[str, HostCol] = {}
        n = None
        for k, v in columns.items():
            if isinstance(v, (list, tuple)):
                data = np.zeros((len(v), str_max_len), np.uint8)
                lens = np.zeros((len(v),), np.int32)
                for i, s in enumerate(v):
                    b = s.encode() if isinstance(s, str) else bytes(s)
                    b = b[:str_max_len]
                    data[i, : len(b)] = np.frombuffer(b, np.uint8)
                    lens[i] = len(b)
                conv[k] = (data, lens)
                n = len(v)
            else:
                arr = np.asarray(v)
                conv[k] = arr
                n = len(arr)
        whole = HChunk(conv, n or 0)
        schema = chunk_schema(whole)

        def it():
            for s in range(0, max(whole.n, 1), chunk_rows):
                e = min(s + chunk_rows, whole.n)
                if e > s or whole.n == 0:
                    yield _slice_hchunk(whole, s, e)
                if whole.n == 0:
                    return

        return ChunkSource(it, schema, chunk_rows)

    @staticmethod
    def from_store(path: str, chunk_rows: int,
                   partitions: Optional[Sequence[int]] = None,
                   columns: Optional[Sequence[str]] = None
                   ) -> "ChunkSource":
        """Stream a persisted store (io/store.py layout) partition by
        partition, slicing each into chunks.  Individual partitions must fit
        host RAM; the dataset as a whole need not — EXCEPT uncompressed
        ``hdfs://`` partitions past ``RANGED_STREAM_MIN_BYTES``, which
        stream through bounded ranged reads (one HTTP range per column
        segment per chunk), so even a single partition larger than host
        RAM flows chunk-wise (channelbufferhdfs.cpp:69-97 block-read
        role).  Per-partition checksums cannot be verified on that ranged
        path — they cover whole segments the stream never materializes —
        so partitions BELOW the threshold take the whole-part verified
        read like every other store.  ``partitions`` restricts to the
        listed store partitions (the per-worker subset of a cluster
        streamed job); ``columns`` to the named stored columns
        (``io/store.read_parts``: only their leaves are read and verified,
        and the stream's schema holds only them)."""
        from dryad_tpu.io import store

        meta = store.store_meta(path)
        schema = store.kept_schema(meta["schema"], columns)
        part_ids = (list(range(meta["npartitions"]))
                    if partitions is None else list(partitions))

        ranged_parts: set = set()
        if store.has_ranged_read(path, meta):
            row_bytes = store.schema_row_bytes(schema)
            ranged_parts = {
                p for p in part_ids
                if meta["counts"][p] * row_bytes
                >= ChunkSource.RANGED_STREAM_MIN_BYTES}

        def read(p):
            cols = store.read_parts(path, meta, [p], columns=columns)[1][0]
            return {k: cols[k] for k in schema}

        def it():
            for p in part_ids:
                cnt = meta["counts"][p]
                if p in ranged_parts:
                    # integrity trade documented above: too big to hold,
                    # so stream unverified ranged chunks
                    for cols, n in store.iter_part_chunks(
                            path, meta, p, chunk_rows, columns):
                        yield HChunk(cols, n)
                    continue
                if store.is_remote_store(path):
                    # multi-request remote read: transient provider
                    # failures re-issue the whole partition with
                    # backoff (io/providers.retry_transient) instead of
                    # surfacing raw mid-stream
                    # retries=2: the per-request provider clients retry
                    # internally already — this layer only re-issues the
                    # multi-request sequence for transients that slip
                    # past them (truncated streams, empty 200 bodies),
                    # so keep the stacked worst case bounded
                    from dryad_tpu.io.providers import retry_transient
                    cols = retry_transient(
                        lambda p=p: read(p),
                        what=f"remote part {p} of {path}", retries=2)
                else:
                    cols = read(p)
                whole = HChunk(cols, cnt)
                for s in range(0, cnt, chunk_rows):
                    yield _slice_hchunk(whole, s, min(s + chunk_rows, cnt))

        src = ChunkSource(it, schema, chunk_rows)
        import hashlib
        ident = ("store", path, meta.get("counts"), meta.get("checksums"),
                 sorted(part_ids))
        if len(schema) < len(meta["schema"]):
            # two column sets of one store are two sources
            ident += (tuple(schema),)
        src.fingerprint = hashlib.sha256(repr(ident).encode()).hexdigest()
        return src

    @staticmethod
    def from_text(paths, chunk_rows: int, max_line_len: int = 256,
                  column: str = "line") -> "ChunkSource":
        """Stream text files line by line, ``chunk_rows`` lines per chunk —
        the file itself is never held in memory (the streaming counterpart
        of io.providers.read_text_files; reference line-record channel,
        DryadLinqTextReader.cs).  A trailing unterminated line counts."""
        from dryad_tpu import native

        paths = [paths] if isinstance(paths, str) else list(paths)
        schema = {column: {"kind": "str", "max_len": max_line_len}}

        def pack(lines):
            data, lens = native.pack_bytes_list(lines, max_line_len,
                                                len(lines))
            return HChunk({column: (data[:len(lines)], lens[:len(lines)])},
                          len(lines))

        def strip_cr(line: bytes) -> bytes:
            # match the in-memory reader (native pack_lines strips \r)
            return line[:-1] if line.endswith(b"\r") else line

        def it():
            buf: List[bytes] = []
            for path in paths:
                rem = b""
                with open(path, "rb") as f:
                    while True:
                        blk = f.read(1 << 22)
                        if not blk:
                            break
                        parts = (rem + blk).split(b"\n")
                        rem = parts.pop()
                        buf.extend(strip_cr(p) for p in parts)
                        while len(buf) >= chunk_rows:
                            yield pack(buf[:chunk_rows])
                            buf = buf[chunk_rows:]
                if rem:
                    buf.append(strip_cr(rem))
            while buf:
                yield pack(buf[:chunk_rows])
                buf = buf[chunk_rows:]

        src = ChunkSource(it, schema, chunk_rows)
        try:
            import hashlib
            # nanosecond mtime: a same-second same-size rewrite (test
            # fixtures, in-place log rotation) must change the key
            sig = [(p, os.path.getsize(p), os.stat(p).st_mtime_ns)
                   for p in paths]
            src.fingerprint = hashlib.sha256(
                repr(("text", sig, max_line_len, column)).encode()
            ).hexdigest()
        except OSError:
            pass
        return src

    @staticmethod
    def from_generator(gen: Callable[[int], Dict[str, Any]], n_chunks: int,
                       chunk_rows: int, str_max_len: int = 64
                       ) -> "ChunkSource":
        """Synthesize chunks on the fly — gen(i) -> column dict.  This is
        how >RAM benchmark inputs are produced without materializing them."""
        first = ChunkSource.from_arrays(gen(0), chunk_rows, str_max_len)
        schema = first.schema

        def it():
            for i in range(n_chunks):
                for c in ChunkSource.from_arrays(gen(i), chunk_rows,
                                                 str_max_len):
                    yield c

        return ChunkSource(it, schema, chunk_rows)


# ---------------------------------------------------------------------------
# async host-IO prefetch (double-buffered chunk pipeline, host side)


class PrefetchStats:
    """Thread-safe per-job counters for the prefetch pipeline.

    ``stalls`` counts the times a consumer had to WAIT for the producer
    thread (the prefetch queue was empty while the producer was still
    running) — the direct "host IO is the bottleneck" signal EXPLAIN
    ANALYZE surfaces as ``prefetch_stall``; ``stall_s`` is the summed
    wait.  The queue-priming wait for the very first chunk is not a
    stall (nothing could have been overlapped yet)."""

    def __init__(self):
        import threading
        self._lock = threading.Lock()
        self.stalls = 0
        self.stall_s = 0.0
        self.chunks = 0

    def _stall(self, dt: float) -> None:
        with self._lock:
            self.stalls += 1
            self.stall_s += dt

    def _chunk(self) -> None:
        with self._lock:
            self.chunks += 1

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            return {"stalls": self.stalls,
                    "stall_s": round(self.stall_s, 6),
                    "chunks": self.chunks}


def prefetch_iter(it: Iterator[HChunk], depth: int | None = None,
                  stats: Optional[PrefetchStats] = None
                  ) -> Iterator[HChunk]:
    """Pull up to ``depth`` chunks ahead of the consumer on a background
    thread — the host-IO half of the reference's completion-port double
    buffering (channelbuffernativereader.cpp): while the consumer holds
    the device busy with chunk i, the NEXT chunk's store read / ranged
    fetch / unpack proceeds concurrently (reads release the GIL).

    ``depth`` <= 0 degrades to the plain synchronous iterator (the
    prefetch-off A/B lever); default is ``JobConfig.ooc_prefetch_depth``.
    Early consumer abandonment (``take`` closing the stream) stops the
    producer thread promptly; producer exceptions re-raise in the
    consumer."""
    if depth is None:
        from dryad_tpu.utils.config import JobConfig
        depth = JobConfig().ooc_prefetch_depth
    if depth <= 0:
        yield from it
        return
    import queue as _queue
    import threading
    import time as _time

    q: "_queue.Queue" = _queue.Queue(maxsize=depth)
    stop = threading.Event()
    end = object()
    box: Dict[str, BaseException] = {}

    def pump():
        try:
            for item in it:
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.05)
                        break
                    except _queue.Full:
                        continue
                if stop.is_set():
                    return
        except BaseException as e:            # surfaces in the consumer
            box["exc"] = e
        finally:
            while not stop.is_set():
                try:
                    q.put(end, timeout=0.05)
                    return
                except _queue.Full:
                    continue

    t = threading.Thread(target=pump, daemon=True,
                         name="dryad-ooc-prefetch")
    t.start()
    first = True
    try:
        while True:
            if (stats is not None and not first and q.empty()
                    and t.is_alive()):
                t0 = _time.monotonic()
                item = q.get()
                stats._stall(_time.monotonic() - t0)
            else:
                item = q.get()
            if item is end:
                break
            first = False
            if stats is not None:
                stats._chunk()
            yield item
        exc = box.get("exc")
        if exc is not None:
            raise exc
    finally:
        stop.set()


# ---------------------------------------------------------------------------
# double-buffered device streaming


def stream_through(chunks: Iterable[HChunk], device_fn, capacity: int,
                   depth: int = 2, prefetch: int | None = None,
                   stats: Optional[PrefetchStats] = None
                   ) -> Iterator[Batch]:
    """Stream chunks through ``device_fn`` (a jitted Batch -> pytree fn),
    keeping up to ``depth`` chunks in flight.

    JAX async dispatch makes this the double-buffered pipeline of the
    reference's channelbufferqueue: while the host blocks fetching result
    i, the transfer+compute of results i+1..i+depth-1 proceed on device —
    and the prefetch thread (``prefetch_iter``) overlaps the NEXT chunk's
    host IO + unpack with both.
    """
    pending: deque = deque()
    for chunk in prefetch_iter(iter(chunks), prefetch, stats):
        b = _chunk_to_batch(chunk, capacity)   # async H2D
        pending.append(device_fn(b))           # async compute
        if len(pending) >= depth:
            yield pending.popleft()
    while pending:
        yield pending.popleft()


def stream_map(src: ChunkSource, batch_fn, out_capacity: int | None = None,
               depth: int = 2) -> ChunkSource:
    """Lazy chunk-wise map: apply a Batch->Batch device fn to every chunk.

    ``batch_fn`` may change row counts (filter/flat_map) and columns; the
    output schema is probed by tracing one empty chunk.
    """
    cap = out_capacity or src.chunk_rows
    fn = jax.jit(batch_fn)

    probe = _batch_to_chunk(batch_fn(_chunk_to_batch(
        HChunk.empty_like(src.schema), 1)))
    out_schema = chunk_schema(probe)

    def it():
        for out in stream_through(iter(src), fn, src.chunk_rows,
                                  depth=depth):
            yield _batch_to_chunk(out)

    return ChunkSource(it, out_schema, cap)


# ---------------------------------------------------------------------------
# host-side ordering mirror (for rare oversize-bucket merges)


def _host_sort_lanes(spec, col: HostCol, descending: bool = False
                     ) -> List[np.ndarray]:
    """Numpy mirror of ops.kernels.sort_lanes_for: uint32 lanes whose
    unsigned lex order equals the column's sort order."""
    if spec["kind"] == "str":
        data, lens = col
        L = data.shape[1]
        mask = np.arange(L)[None, :] < lens[:, None]
        b = np.where(mask, data, 0).astype(np.uint32)
        pad = (-L) % 4
        lens_u = lens.astype(np.uint32)
        fold_len = pad >= 2 and L <= 0xFFFF
        if fold_len:
            # exact mirror of kernels._string_sort_lanes length folding
            cols = [b, (lens_u >> 8)[:, None], (lens_u & 0xFF)[:, None]]
            if pad == 3:
                cols.append(np.zeros((b.shape[0], 1), np.uint32))
            b = np.concatenate(cols, axis=1)
        elif pad:
            b = np.pad(b, ((0, 0), (0, pad)))
        b4 = b.reshape(b.shape[0], -1, 4)
        lanes = list(np.moveaxis(
            (b4[..., 0] << 24) | (b4[..., 1] << 16) |
            (b4[..., 2] << 8) | b4[..., 3], -1, 0))
        if not fold_len:
            lanes.append(lens_u)
    else:
        arr = col
        if np.issubdtype(arr.dtype, np.floating):
            bits = arr.astype(np.float32).view(np.uint32)
            sign = bits >> 31
            bits = np.where(sign == 1, ~bits, bits | np.uint32(0x80000000))
            lanes = [bits]
        elif arr.dtype in (np.int64, np.uint64):
            u = arr.astype(np.int64)
            hi = (u >> 32).astype(np.uint32)
            if arr.dtype == np.int64:
                hi = hi ^ np.uint32(0x80000000)
            lanes = [hi, u.astype(np.uint32)]
        elif np.issubdtype(arr.dtype, np.signedinteger):
            lanes = [arr.astype(np.uint32) ^ np.uint32(0x80000000)]
        else:
            lanes = [arr.astype(np.uint32)]
    if descending:
        lanes = [np.invert(l) for l in lanes]
    return lanes


def _host_sort_order(schema, chunk: HChunk,
                     keys: Sequence[Tuple[str, bool]]) -> np.ndarray:
    lanes: List[np.ndarray] = []
    for name, desc in keys:
        lanes.extend(_host_sort_lanes(schema[name], chunk.cols[name], desc))
    return np.lexsort(tuple(reversed(lanes)))


# ---------------------------------------------------------------------------
# external sort


def _collect_samples(src: ChunkSource, key: str,
                     samples_per_chunk: int = 512
                     ) -> Tuple[np.ndarray, int]:
    """One streaming pass: (lane samples, total row count).

    The sampling stage of the reference's dynamic range distribution
    (DryadLinqSampler.cs:42 + DrDynamicRangeDistributor.h:23).  Lanes are
    computed host-side on <= samples_per_chunk rows per chunk — never the
    full column (VERDICT r1 weak item 3) — and the host lane transform is
    an exact mirror of the device one (``_host_sort_lanes`` ==
    ``sort_lanes_for`` lane 0)."""
    spec = src.schema[key]
    samples: List[np.ndarray] = []
    total = 0
    for chunk in src:
        if chunk.n == 0:
            continue
        total += chunk.n
        take = min(chunk.n, samples_per_chunk)
        idx = np.linspace(0, chunk.n - 1, take).astype(np.int64)
        col = chunk.cols[key]
        if spec["kind"] == "str":
            lane = _host_sort_lanes(spec, (col[0][idx], col[1][idx]))[0]
        else:
            lane = _host_sort_lanes(spec, col[idx])[0]
        samples.append(lane)
    if not samples:
        return np.zeros((0,), np.uint32), 0
    return np.concatenate(samples), total


def _bounds_from_samples(samples: np.ndarray, n_buckets: int) -> np.ndarray:
    if len(samples) == 0:
        return np.zeros((n_buckets - 1,), np.uint32)
    s = np.sort(samples.astype(np.uint64))
    qs = np.asarray([len(s) * (i + 1) // n_buckets
                     for i in range(n_buckets - 1)], np.int64)
    return s[np.minimum(qs, len(s) - 1)].astype(np.uint32)


def _sample_bounds(src: ChunkSource, key: str, n_buckets: int,
                   samples_per_chunk: int = 512) -> np.ndarray:
    samples, _ = _collect_samples(src, key, samples_per_chunk)
    return _bounds_from_samples(samples, n_buckets)


@functools.lru_cache(maxsize=256)
def _make_scatter_fn(key: str, n_buckets: int):
    """Device fn: chunk Batch + bounds -> rows grouped by range bucket,
    with per-bucket counts.

    lru_cache'd on the static params so repeated external_sort calls reuse
    the SAME jitted callable — a fresh closure per call would miss jax's
    compile cache and re-XLA-compile every run."""

    def fn(b: Batch, bounds: jax.Array):
        from dryad_tpu.parallel.shuffle import range_dest, range_key_lanes

        # the one range rule, handed the one lane this path samples
        # (``bounds`` [n_buckets-1] over the primary's first lane): rows
        # equal in it share a bucket, and a bucket that outgrows a chunk
        # is re-bucketed or host-merged (_sorted_bucket_chunks)
        dest, _ = range_dest(range_key_lanes(b, [(key, False)]),
                             bounds[:, None])
        dest = jnp.where(b.valid_mask(), dest, n_buckets)  # padding last
        return _scatter_by_dest(b, dest, n_buckets)

    return jax.jit(fn)


def _scatter_by_dest(b: Batch, dest: jax.Array, n_buckets: int):
    """Group a chunk's rows by destination bucket + per-bucket counts.

    Value-carry sort instead of argsort+gather (TPU random gathers run
    ~10.7 ns/row — the gather alone cost more than the whole sort), and
    the pallas tile histogram instead of bincount (XLA lowers bincount to
    sort+segment machinery, measured 72x slower; benchmarks/pallas_probe).
    Together ~7x on the per-chunk device step of every streamed exchange
    (the role of the reference's per-channel partition writer,
    channelbuffernativewriter.cpp)."""
    from dryad_tpu.ops.kernels import permute_by_sort
    from dryad_tpu.ops.pallas_kernels import hist_buckets

    grouped = permute_by_sort(b, (dest.astype(jnp.uint32),))
    hist = hist_buckets(dest, n_buckets)
    return grouped, hist


@functools.lru_cache(maxsize=256)
def _make_hash_scatter_fn(keys: Sequence[str], n_buckets: int):
    def fn(b: Batch):
        _, lo = hash_batch_keys(b, list(keys))
        dest = (lo % jnp.uint32(n_buckets)).astype(jnp.int32)
        dest = jnp.where(b.valid_mask(), dest, n_buckets)
        return _scatter_by_dest(b, dest, n_buckets)

    return jax.jit(fn)


@functools.lru_cache(maxsize=256)
def _make_sort_fn(keys: Tuple[Tuple[str, bool], ...]):
    return jax.jit(lambda b: kernels.sort_by_columns(b, list(keys)))


class _BucketStore:
    """Per-bucket fragment accumulator: host RAM, or spill files on disk.

    The host-side materialization of an exchange — the role of the
    reference's per-channel temp files served for pull
    (channelbuffernativewriter.cpp + ProcessService FileServer)."""

    def __init__(self, schema, n_buckets: int,
                 spill_dir: Optional[str] = None):
        self.schema = schema
        self.n_buckets = n_buckets
        self.spill_dir = spill_dir
        self._ram: List[List[HChunk]] = [[] for _ in range(n_buckets)]
        self._files: List[Any] = []
        if spill_dir:
            os.makedirs(spill_dir, exist_ok=True)
            self._files = [open(os.path.join(spill_dir, f"bucket-{i:05d}"),
                                "wb") for i in range(n_buckets)]
            self._frag_rows: List[List[int]] = [[] for _ in range(n_buckets)]

    def append(self, bucket: int, frag: HChunk) -> None:
        if frag.n == 0:
            return
        if not self.spill_dir:
            self._ram[bucket].append(frag)
            return
        f = self._files[bucket]
        for k in sorted(self.schema):
            v = frag.cols[k]
            if self.schema[k]["kind"] == "str":
                f.write(np.ascontiguousarray(v[0]).tobytes())
                f.write(np.ascontiguousarray(v[1]).tobytes())
            else:
                f.write(np.ascontiguousarray(v).tobytes())
        self._frag_rows[bucket].append(frag.n)

    def fragments(self, bucket: int) -> List[HChunk]:
        if not self.spill_dir:
            return self._ram[bucket]
        if not self._files[bucket].closed:
            self._files[bucket].flush()
        out: List[HChunk] = []
        with open(self._files[bucket].name, "rb") as f:
            for n in self._frag_rows[bucket]:
                cols: Dict[str, HostCol] = {}
                for k in sorted(self.schema):
                    spec = self.schema[k]
                    if spec["kind"] == "str":
                        L = spec["max_len"]
                        d = np.frombuffer(f.read(n * L), np.uint8
                                          ).reshape(n, L)
                        l = np.frombuffer(f.read(n * 4), np.int32)
                        cols[k] = (d, l)
                    else:
                        dt = np.dtype(spec["dtype"])
                        tshape = tuple(spec.get("shape", ()))
                        cnt = n * int(np.prod(tshape, dtype=np.int64) or 1)
                        cols[k] = np.frombuffer(
                            f.read(cnt * dt.itemsize), dt
                        ).reshape((n,) + tshape)
                out.append(HChunk(cols, n))
        return out

    def rows(self, bucket: int) -> int:
        if not self.spill_dir:
            return sum(f.n for f in self._ram[bucket])
        return sum(self._frag_rows[bucket])

    def clear(self, bucket: int) -> None:
        if not self.spill_dir:
            self._ram[bucket] = []

    def close(self) -> None:
        """Release WRITE handles; fragments() keep reading by name."""
        for f in self._files:
            if not f.closed:
                f.close()


def _sorted_bucket_chunks(schema, frags: List[HChunk],
                          keys: Sequence[Tuple[str, bool]],
                          chunk_rows: int, sort_fn,
                          rebucket_depth: int = 2) -> Iterator[HChunk]:
    """Yield a bucket's rows fully sorted, in chunks of <= chunk_rows.

    Fits on device -> one device sort.  Oversize -> re-bucket recursively
    on resampled bounds; if bounds degenerate (heavy lane skew), fall back
    to a host lexsort over the exact device sort-lane order."""
    total = sum(f.n for f in frags)
    if total == 0:
        return
    if total <= chunk_rows:
        merged = _concat_hchunks(schema, frags)
        b = _chunk_to_batch(merged, chunk_rows)
        out = _batch_to_chunk(sort_fn(b))
        yield out
        return
    key0, desc0 = keys[0]
    if rebucket_depth > 0:
        sub_n = max(2, -(-total // chunk_rows) * 2)
        sub = ChunkSource(lambda: iter(frags), schema, chunk_rows)
        bounds = _sample_bounds(sub, key0, sub_n)
        if len(np.unique(bounds)) > 1:  # non-degenerate: recurse
            scatter = _make_scatter_fn(key0, sub_n)
            jbounds = jnp.asarray(bounds)
            store = _BucketStore(schema, sub_n)
            for frag in frags:
                for s in range(0, frag.n, chunk_rows):
                    piece = _slice_hchunk(frag, s,
                                          min(s + chunk_rows, frag.n))
                    grouped, hist = scatter(_chunk_to_batch(piece,
                                                            chunk_rows),
                                            jbounds)
                    gh = _batch_to_chunk(grouped)
                    h = np.asarray(hist)
                    offs = np.cumsum(np.concatenate([[0], h]))
                    for i in range(sub_n):
                        store.append(i, _slice_hchunk(gh, int(offs[i]),
                                                      int(offs[i + 1])))
            order = range(sub_n - 1, -1, -1) if desc0 else range(sub_n)
            for i in order:
                yield from _sorted_bucket_chunks(
                    schema, store.fragments(i), keys, chunk_rows, sort_fn,
                    rebucket_depth - 1)
            return
    # degenerate lane: exact host merge over full sort-lane order
    merged = _concat_hchunks(schema, frags)
    order = _host_sort_order(schema, merged, keys)
    for s in range(0, total, chunk_rows):
        idx = order[s: s + chunk_rows]
        cols = {k: ((v[0][idx], v[1][idx]) if isinstance(v, tuple)
                    else v[idx]) for k, v in merged.cols.items()}
        yield HChunk(cols, len(idx))


def _schema_row_bytes(schema) -> int:
    # the store's row width (io/store.part_layout); floored at 1 so an
    # empty schema cannot zero the in-core byte estimate
    from dryad_tpu.io.store import schema_row_bytes
    return max(schema_row_bytes(schema), 1)


def external_sort(src: ChunkSource, keys: Sequence[Tuple[str, bool]],
                  n_buckets: int | None = None,
                  spill_dir: Optional[str] = None,
                  depth: int | None = None,
                  incore_bytes: int = 0,
                  prefetch: int | None = None,
                  stats: Optional[PrefetchStats] = None
                  ) -> Iterator[HChunk]:
    """Globally sort an arbitrarily large chunk stream; yields sorted
    chunks in order.  Device working set stays O(chunk_rows) — except the
    in-core tier below.

    Pass A samples range bounds on the primary key; pass B scatters chunks
    into range buckets on device (double-buffered); pass C sorts each
    bucket (recursing on oversize buckets) and emits them in bucket order —
    range buckets make concatenation globally sorted, exactly the
    TeraSort plan (sampling + RangePartition, BASELINE.md config 2).

    Memory-hierarchy tier (``incore_bytes`` > 0, from
    JobConfig.ooc_incore_bytes): pass A already counts the total rows; a
    dataset that fits the budget skips passes B/C for ONE device sort —
    one H2D, one sort program, one D2H — instead of round-tripping every
    chunk through the host twice.  The reference picks RAM FIFO channels
    over disk files by the same criterion (channelbufferqueue.cpp:777).
    """
    if depth is None:
        from dryad_tpu.utils.config import JobConfig
        depth = JobConfig().ooc_inflight
    chunk_rows = src.chunk_rows
    key0, desc0 = keys[0]

    # pass A: one streaming pass collects samples AND the total row count
    samples, total = _collect_samples(src, key0)

    if incore_bytes > 0 and total * _schema_row_bytes(src.schema) \
            <= incore_bytes:
        # in-core tier: the whole dataset in one device sort
        merged = _concat_hchunks(src.schema, list(src))
        cap = 1
        while cap < max(merged.n, 1):
            cap *= 2
        sort_fn = _make_sort_fn(tuple(tuple(k) for k in keys))
        out = _batch_to_chunk(sort_fn(_chunk_to_batch(merged, cap)))
        for s in range(0, max(out.n, 1), chunk_rows):
            e = min(s + chunk_rows, out.n)
            if e > s:
                yield _slice_hchunk(out, s, e)
        return
    nb = n_buckets or max(2, -(-total // chunk_rows) * 2)
    bounds = _bounds_from_samples(samples, nb)
    jbounds = jnp.asarray(bounds)

    # pass B: scatter into buckets (double-buffered device pipeline)
    scatter = _make_scatter_fn(key0, nb)
    store = _BucketStore(src.schema, nb, spill_dir=spill_dir)
    pending: deque = deque()

    def drain_one():
        grouped, hist = pending.popleft()
        gh = _batch_to_chunk(grouped)
        h = np.asarray(hist)
        offs = np.cumsum(np.concatenate([[0], h]))
        for i in range(nb):
            store.append(i, _slice_hchunk(gh, int(offs[i]),
                                          int(offs[i + 1])))

    for chunk in prefetch_iter(iter(src), prefetch, stats):
        pending.append(scatter(_chunk_to_batch(chunk, chunk_rows), jbounds))
        if len(pending) >= depth:
            drain_one()
    while pending:
        drain_one()

    # pass C: per-bucket sort + emit in bucket order
    sort_fn = _make_sort_fn(tuple(keys))
    order = range(nb - 1, -1, -1) if desc0 else range(nb)
    try:
        for i in order:
            yield from _sorted_bucket_chunks(
                src.schema, store.fragments(i), keys, chunk_rows, sort_fn)
            store.clear(i)
    finally:
        store.close()


# ---------------------------------------------------------------------------
# streaming group-aggregate

# jitted (partial, merge, finalize) triples cached across passes: an
# iterative streamed job re-plans its group-by every superstep with the
# same keys/aggs — a fresh jit per pass would retrace at chunk shape
# each time.  Decomposable members key by identity; entries hold refs
# so ids cannot alias after GC.  Bounded FIFO.
from collections import OrderedDict as _OrderedDict


def fifo_memo(cache: "_OrderedDict[tuple, Any]", maxn: int,
              key, refs, builder):
    """id-keyed bounded memo shared by the compiled-program caches
    (stream_exec._PROG_CACHE, the group-fns cache below): each entry
    holds STRONG refs to the callables its key identifies by id(), so a
    key can never alias a garbage-collected-and-reallocated id; FIFO
    eviction bounds the footprint."""
    hit = cache.get(key)
    if hit is None:
        hit = cache[key] = (builder(), refs)
        if len(cache) > maxn:
            cache.popitem(last=False)
    return hit[0]


_GROUP_FNS_CACHE: "_OrderedDict[tuple, Any]" = _OrderedDict()
_GROUP_FNS_MAX = 128


def _cached_group_fns(key, refs, builder):
    return fifo_memo(_GROUP_FNS_CACHE, _GROUP_FNS_MAX, key, refs,
                     builder)


def streaming_group_aggregate(src: ChunkSource, keys: Sequence[str],
                              aggs: Dict[str, Tuple[str, Optional[str]]],
                              n_buckets: int | None = None,
                              depth: int | None = None,
                              prefetch: int | None = None,
                              stats: Optional[PrefetchStats] = None
                              ) -> Iterator[HChunk]:
    """GroupBy+aggregate over an arbitrarily large chunk stream.

    Per chunk (on device): partial aggregate, then hash-scatter the partial
    groups into ``n_buckets`` key buckets.  Buckets accumulate partials on
    host and are COMPACTED on device (re-aggregated) whenever they exceed
    the chunk capacity — the streaming form of the reference's dynamic
    aggregation trees (DrDynamicAggregateManager.cpp: map-side combine,
    then hierarchical merge).  Finally each bucket is merge-aggregated and
    yielded.  Distinct keys per bucket must fit chunk capacity; raise
    ``n_buckets`` for higher-cardinality keys.
    """
    n_buckets, depth = _resolve_bucket_knobs(n_buckets, depth)

    def build():
        from dryad_tpu.plan.planner import _decompose_aggs
        partial, final, mean_cols = _decompose_aggs(dict(aggs))
        pagg = jax.jit(lambda b: kernels.group_aggregate(
            b, list(keys), partial))
        merge = jax.jit(lambda b: kernels.group_aggregate(
            b, list(keys), final))

        def final_fn(b):
            m = kernels.group_aggregate(b, list(keys), final)
            return Batch(kernels.mean_finalize_columns(dict(m.columns),
                                                       mean_cols),
                         m.count)
        return pagg, merge, jax.jit(final_fn)

    key = ("group_agg", tuple(keys),
           tuple(sorted((k, v if isinstance(v, tuple) else id(v))
                        for k, v in aggs.items())))
    refs = tuple(v for v in aggs.values() if not isinstance(v, tuple))
    pagg, merge, final_jit = _cached_group_fns(key, refs, build)

    probe = _batch_to_chunk(pagg(_chunk_to_batch(
        HChunk.empty_like(src.schema), 1)))
    yield from _hash_bucketed_reduce(src, keys, pagg, merge, final_jit,
                                     chunk_schema(probe), n_buckets,
                                     depth, "group", prefetch=prefetch,
                                     stats=stats)


# ---------------------------------------------------------------------------
# shared hash-bucketed streaming reduction machinery
#
# ONE implementation of the scatter/accumulate/compact/finalize pipeline
# that streaming_group_aggregate, streaming_group_decomposable, and
# streaming_distinct all ride (the streaming form of the reference's
# dynamic aggregation trees, DrDynamicAggregateManager.cpp): per chunk a
# LOCAL device reduction, hash-scatter of its rows into key buckets,
# host-side accumulation with device-side COMPACTION whenever a bucket
# would exceed the chunk capacity, then a per-bucket FINALIZE.


def _resolve_bucket_knobs(n_buckets, depth):
    if depth is None or n_buckets is None:
        from dryad_tpu.utils.config import JobConfig
        _cfg = JobConfig()
        depth = depth if depth is not None else _cfg.ooc_inflight
        n_buckets = (n_buckets if n_buckets is not None
                     else _cfg.ooc_hash_buckets)
    return n_buckets, depth


def _hash_bucketed_reduce(src: ChunkSource, keys: Sequence[str],
                          local_fn, compact_fn, final_fn,
                          row_schema, n_buckets: int, depth: int,
                          what: str, prefetch: int | None = None,
                          stats: Optional[PrefetchStats] = None
                          ) -> Iterator[HChunk]:
    """local_fn: per-chunk device reduction (jitted Batch -> Batch);
    compact_fn: associative device re-reduction of accumulated bucket
    rows; final_fn: per-bucket finishing pass.  ``row_schema`` is the
    schema of local_fn's output rows.  Distinct reduced rows per bucket
    must fit the chunk capacity — raise n_buckets otherwise."""
    chunk_rows = src.chunk_rows
    scatter = _make_hash_scatter_fn(tuple(keys), n_buckets)

    buckets: List[List[HChunk]] = [[] for _ in range(n_buckets)]
    bucket_rows = [0] * n_buckets

    def compact_bucket(i: int) -> None:
        merged = _concat_hchunks(row_schema, buckets[i])
        out = _batch_to_chunk(compact_fn(
            _chunk_to_batch(merged, chunk_rows)))
        buckets[i] = [out]
        bucket_rows[i] = out.n

    def add_rows(ph: HChunk) -> None:
        grouped, hist = scatter(_chunk_to_batch(ph, chunk_rows))
        gh = _batch_to_chunk(grouped)
        h = np.asarray(hist)
        offs = np.cumsum(np.concatenate([[0], h]))
        for i in range(n_buckets):
            frag = _slice_hchunk(gh, int(offs[i]), int(offs[i + 1]))
            if frag.n == 0:
                continue
            if bucket_rows[i] + frag.n > chunk_rows:
                compact_bucket(i)
                if bucket_rows[i] + frag.n > chunk_rows:
                    raise OOCError(
                        f"{what} bucket {i} holds {bucket_rows[i]} "
                        f"reduced rows; with {frag.n} incoming it exceeds "
                        f"chunk capacity {chunk_rows}; raise n_buckets")
            buckets[i].append(frag)
            bucket_rows[i] += frag.n

    pending: deque = deque()
    for chunk in prefetch_iter(iter(src), prefetch, stats):
        pending.append(local_fn(_chunk_to_batch(chunk, chunk_rows)))
        if len(pending) >= depth:
            add_rows(_batch_to_chunk(pending.popleft()))
    while pending:
        add_rows(_batch_to_chunk(pending.popleft()))

    for i in range(n_buckets):
        if bucket_rows[i] == 0:
            continue
        merged = _concat_hchunks(row_schema, buckets[i])
        yield _batch_to_chunk(final_fn(
            _chunk_to_batch(merged, chunk_rows)))


def streaming_group_whole(src: ChunkSource, keys: Sequence[str],
                          bucket_fn, out_schema: Dict[str, Any],
                          n_buckets: int | None = None,
                          depth: int | None = None,
                          max_bucket_rows: int | None = None,
                          what: str = "group_whole",
                          prefetch: int | None = None,
                          stats: Optional[PrefetchStats] = None
                          ) -> Iterator[HChunk]:
    """Whole-group operators over an arbitrarily large chunk stream.

    Aggregates compose (partial + merge), but result selectors over whole
    groups — group_apply's user fn, group_median — do NOT: every row of a
    key must be materialized together (reference DryadLinqVertex.cs:
    510-753, GroupBy handing complete IGroupings to user code).  So RAW
    rows hash-scatter into ``n_buckets`` key buckets (all rows of a key
    land in one bucket — the same alignment a post-exchange partition
    has), each bucket accumulates on host, and finalize materializes one
    DEVICE batch per bucket for ``bucket_fn``.  A bucket's rows must fit
    ``max_bucket_rows`` (JobConfig.ooc_group_bucket_rows): there is no
    associative compaction to fall back on, so the bound is the honest
    contract — raise n_buckets (or the knob) for bigger data.
    """
    n_buckets, depth = _resolve_bucket_knobs(n_buckets, depth)
    if max_bucket_rows is None:
        from dryad_tpu.utils.config import JobConfig
        max_bucket_rows = JobConfig().ooc_group_bucket_rows
    chunk_rows = src.chunk_rows
    scatter = _make_hash_scatter_fn(tuple(keys), n_buckets)

    buckets: List[List[HChunk]] = [[] for _ in range(n_buckets)]
    bucket_rows = [0] * n_buckets

    for chunk in prefetch_iter(iter(src), prefetch, stats):
        if chunk.n == 0:
            continue
        grouped, hist = scatter(_chunk_to_batch(chunk, chunk_rows))
        gh = _batch_to_chunk(grouped)
        h = np.asarray(hist)
        offs = np.cumsum(np.concatenate([[0], h]))
        for i in range(n_buckets):
            frag = _slice_hchunk(gh, int(offs[i]), int(offs[i + 1]))
            if frag.n == 0:
                continue
            if bucket_rows[i] + frag.n > max_bucket_rows:
                raise OOCError(
                    f"{what} bucket {i} holds {bucket_rows[i]} raw rows; "
                    f"with {frag.n} incoming it exceeds "
                    f"ooc_group_bucket_rows={max_bucket_rows} (whole "
                    f"groups cannot be compacted) — raise n_buckets or "
                    f"the knob")
            buckets[i].append(frag)
            bucket_rows[i] += frag.n

    for i in range(n_buckets):
        if bucket_rows[i] == 0:
            continue
        merged = _concat_hchunks(src.schema, buckets[i])
        buckets[i] = []
        out = bucket_fn(_chunk_to_batch(merged, merged.n))
        yield _batch_to_chunk(out)


# ---------------------------------------------------------------------------
# streaming user-decomposable aggregation (IDecomposable over streams)


def streaming_group_decomposable(src: ChunkSource, keys: Sequence[str],
                                 decs: Dict[str, Any],
                                 n_buckets: int | None = None,
                                 depth: int | None = None,
                                 prefetch: int | None = None,
                                 stats: Optional[PrefetchStats] = None
                                 ) -> Iterator[HChunk]:
    """GroupBy with USER-DEFINED Decomposable aggregates over an
    arbitrarily large chunk stream: per-chunk seed+merge (map-side
    combine) -> hash-scatter of flattened states into key buckets ->
    periodic device-side merge compaction -> FinalReduce per bucket.
    The streamed form of the dgroup partial/merge lowering
    (plan/planner._lower_group_decomposable; IDecomposable.cs:34)."""
    n_buckets, depth = _resolve_bucket_knobs(n_buckets, depth)
    keys_l = list(keys)
    box: Dict[str, Any] = {}
    pagg = jax.jit(lambda b: kernels.group_decompose_partial(
        b, keys_l, decs, box))
    merge = jax.jit(lambda b: kernels.group_decompose_merge(
        b, keys_l, decs, box, False))
    fin = jax.jit(lambda b: kernels.group_decompose_merge(
        b, keys_l, decs, box, True))
    # partial-state schema probe (also fills the treedef box before any
    # merge traces — partials always trace first)
    probe = _batch_to_chunk(pagg(_chunk_to_batch(
        HChunk.empty_like(src.schema), 1)))
    yield from _hash_bucketed_reduce(src, keys, pagg, merge, fin,
                                     chunk_schema(probe), n_buckets,
                                     depth, "decomposable-group",
                                     prefetch=prefetch, stats=stats)


# ---------------------------------------------------------------------------
# streaming per-group top-k (group contents over streams)


def streaming_group_topk(src: ChunkSource, keys: Sequence[str], k: int,
                         by: str, descending: bool = True,
                         n_buckets: int | None = None,
                         depth: int | None = None,
                         prefetch: int | None = None,
                         stats: Optional[PrefetchStats] = None
                         ) -> Iterator[HChunk]:
    """Per-group top-k rows over an arbitrarily large stream.  Top-k is
    idempotent under composition (top-k of accumulated top-ks = global
    top-k), so buckets accumulate candidate rows and re-compact with the
    group_top_k kernel whenever they exceed the chunk capacity; bucket
    residency is bounded by k x (distinct keys in the bucket).  (Not a
    _hash_bucketed_reduce client: top-k buckets may legitimately exceed
    the chunk capacity pre-compaction, so it compacts at pow2 device
    sizes instead of the fixed chunk bound.)"""
    n_buckets, depth = _resolve_bucket_knobs(n_buckets, depth)
    chunk_rows = src.chunk_rows
    keys_l = list(keys)
    topk = jax.jit(lambda b: kernels.group_top_k(b, keys_l, k, by,
                                                 descending))
    scatter = _make_hash_scatter_fn(tuple(keys), n_buckets)

    buckets: List[List[HChunk]] = [[] for _ in range(n_buckets)]
    bucket_rows = [0] * n_buckets

    def compact_bucket(i: int) -> None:
        merged = _concat_hchunks(src.schema, buckets[i])
        capm = 1
        while capm < max(merged.n, 1):
            capm *= 2
        out = _batch_to_chunk(topk(_chunk_to_batch(merged, capm)))
        if out.n > chunk_rows:
            raise OOCError(
                f"top-{k} bucket {i} holds {out.n} rows (> chunk capacity "
                f"{chunk_rows}) even after compaction; raise n_buckets")
        buckets[i] = [out]
        bucket_rows[i] = out.n

    def add_rows(ch: HChunk) -> None:
        grouped, hist = scatter(_chunk_to_batch(ch, chunk_rows))
        gh = _batch_to_chunk(grouped)
        h = np.asarray(hist)
        offs = np.cumsum(np.concatenate([[0], h]))
        for i in range(n_buckets):
            frag = _slice_hchunk(gh, int(offs[i]), int(offs[i + 1]))
            if frag.n == 0:
                continue
            if bucket_rows[i] + frag.n > chunk_rows:
                compact_bucket(i)
            buckets[i].append(frag)
            bucket_rows[i] += frag.n

    pending: deque = deque()
    for chunk in prefetch_iter(iter(src), prefetch, stats):
        # local pre-trim: a chunk never contributes more than top-k per
        # group it holds
        pending.append(topk(_chunk_to_batch(chunk, chunk_rows)))
        if len(pending) >= depth:
            add_rows(_batch_to_chunk(pending.popleft()))
    while pending:
        add_rows(_batch_to_chunk(pending.popleft()))

    for i in range(n_buckets):
        if bucket_rows[i] == 0:
            continue
        compact_bucket(i)
        yield buckets[i][0]


# ---------------------------------------------------------------------------
# streaming distinct


@functools.lru_cache(maxsize=256)
def _make_distinct_fn(keys: Tuple[str, ...] | None):
    return jax.jit(lambda b: kernels.distinct(
        b, list(keys) if keys else None))


def streaming_distinct(src: ChunkSource, keys: Sequence[str] = (),
                       n_buckets: int | None = None,
                       depth: int | None = None,
                       prefetch: int | None = None,
                       stats: Optional[PrefetchStats] = None
                       ) -> Iterator[HChunk]:
    """Distinct rows over an arbitrarily large chunk stream.

    Per chunk: local dedup on device, hash-scatter survivors into key
    buckets; buckets accumulate on host and re-dedup on device whenever
    they exceed chunk capacity (distinct rows per bucket must fit the
    chunk — raise ``n_buckets`` for higher cardinality).  The streaming
    form of distinct-before-and-after-exchange (plan/planner.py Distinct
    lowering)."""
    n_buckets, depth = _resolve_bucket_knobs(n_buckets, depth)
    key_names = tuple(keys) or tuple(sorted(src.schema))
    dd = _make_distinct_fn(tuple(keys) if keys else None)
    yield from _hash_bucketed_reduce(src, key_names, dd, dd, dd,
                                     src.schema, n_buckets, depth,
                                     "distinct", prefetch=prefetch,
                                     stats=stats)


# ---------------------------------------------------------------------------
# chunked store output


def write_chunks_to_store(path: str, chunks: Iterable[HChunk],
                          schema: Dict[str, Any],
                          partitioning: Optional[Dict[str, Any]] = None,
                          compression: Optional[str] = None
                          ) -> Dict[str, Any]:
    """Stream chunks to a store directory (io/store.py layout), one
    partition file per chunk, committed atomically via temp-dir rename
    (``hdfs://`` targets commit the same way through the WebHDFS
    adapter's rename; each chunk uploads as soon as it is drained, so
    host memory stays O(chunk_rows) on the write side too)."""
    if path.startswith("s3://"):
        raise OOCError(
            "streamed writes to s3:// are not supported (no atomic "
            "multi-object commit for an unbounded chunk stream); "
            "to_store to a local or hdfs:// path instead")
    from dryad_tpu.io.store import StoreWriter, store_schema

    writer = StoreWriter(path, store_schema(schema), partitioning,
                         compression)
    for chunk in chunks:
        writer.add_chunk(chunk.n, chunk.cols)
    return writer.commit()


# ---------------------------------------------------------------------------
# store-backed re-streaming chunk cache (the Dataset.cache() tier for
# streamed / edge-scale data)
#
# The reference keeps loop-invariant intermediates as materialized temp
# outputs read in place every superstep (DrVertex.h:325-351); the OOC
# equivalent is a LOCAL chunked cache in the io/store.py layout: the cold
# pass writes one part file per chunk (per-chunk fnv64 fingerprints ride
# meta.json exactly like spill sidecars), warm passes re-stream from
# local sequential reads instead of ranged hdfs:// / s3:// / http://
# fetches, and a restarted job with an intact entry skips the cold pass
# entirely.  A ``cache.json`` sidecar records the producing query's
# stable fingerprint — a changed query or changed source data misses; a
# corrupt chunk (fingerprint mismatch on read) falls back to a clean
# re-stream of the producer, never wrong rows.


def cache_entry_paths(root: str, key: str) -> Tuple[str, str, str]:
    """(entry dir, data store path, sidecar path) for a cache key."""
    entry = os.path.join(root, "ooc-cache-" + key[:16])
    return entry, os.path.join(entry, "data"), os.path.join(entry,
                                                           "cache.json")


def cached_chunk_source(root: str, key: str
                        ) -> Optional[Tuple[ChunkSource, Dict[str, Any]]]:
    """Validated warm cache entry: (re-streaming ChunkSource over the
    entry's data store, sidecar dict), or None when the entry is absent,
    carries a different key (stale: the producing query or its source
    data changed), or its store metadata is unreadable.  Per-chunk data
    fingerprints are verified lazily on read (``ChunkSource.from_store``
    checksums every partition before its rows are yielded)."""
    import json

    from dryad_tpu.io.store import store_meta

    entry, data, side = cache_entry_paths(root, key)
    try:
        with open(side) as f:
            sc = json.load(f)
        if sc.get("key") != key:
            return None
        store_meta(data)          # meta.json must parse
        cs = ChunkSource.from_store(data, int(sc["chunk_rows"]))
    except Exception:
        return None
    return cs, sc


def _commit_sidecar(root: str, key: str, chunk_rows: int,
                    meta: Dict[str, Any]) -> Dict[str, Any]:
    """Sidecar-LAST commit shared by both cold-write paths: an entry
    without a matching sidecar reads as a miss, so a crash mid-write can
    never serve a half-entry."""
    import json

    _entry, _data, side = cache_entry_paths(root, key)
    sidecar = {"key": key, "chunk_rows": int(chunk_rows),
               "rows": int(sum(meta["counts"])),
               "bytes": int(sum(meta.get("bytes", [])))}
    tmp = side + ".tmp"
    with open(tmp, "w") as f:
        json.dump(sidecar, f)
    os.replace(tmp, side)
    return sidecar


def write_chunk_cache(root: str, key: str, src: ChunkSource,
                      chunk_rows: int | None = None) -> Dict[str, Any]:
    """Cold pass: drain the producing stream into the entry's data store
    (atomic temp-dir rename, per-chunk checksums), then commit the
    sidecar last.  Returns the sidecar dict."""
    entry, data, _side = cache_entry_paths(root, key)
    os.makedirs(entry, exist_ok=True)
    meta = write_chunks_to_store(data, iter(src), src.schema)
    return _commit_sidecar(root, key, chunk_rows or src.chunk_rows,
                           meta)


def adopt_chunk_cache(root: str, key: str, chunk_rows: int
                      ) -> Dict[str, Any]:
    """Sidecar commit for an entry whose data store was written by an
    EXTERNAL writer (the in-memory ``to_store`` path, or the cluster's
    parallel partition writers): read the freshly committed store meta
    and record the key + read chunk size."""
    from dryad_tpu.io.store import store_meta

    _entry, data, _side = cache_entry_paths(root, key)
    return _commit_sidecar(root, key, chunk_rows, store_meta(data))


def invalidate_cache_entry(root: str, key: str) -> None:
    import shutil
    entry, _, _ = cache_entry_paths(root, key)
    shutil.rmtree(entry, ignore_errors=True)


def cache_source(root: str, key: str, chunk_rows: int, schema,
                 make_producer: Callable[[], Iterable[HChunk]],
                 on_event=None) -> ChunkSource:
    """The re-streaming cache read: a re-iterable ChunkSource that serves
    each pass from the validated local entry (``ooc_cache_hit``), lazily
    rebuilding a missing/stale entry from ``make_producer`` first
    (``ooc_cache_write``).  A fingerprint mismatch mid-stream — a chunk
    whose bytes no longer match its recorded checksum — wipes the entry
    and falls back to a clean re-stream of the producer
    (``ooc_cache_invalid``), skipping exactly the rows already yielded
    (which WERE verified): degraded to remote speed, never wrong rows.
    Streamed single-partition execution is deterministic in row order,
    which is what makes the skip exact."""
    ev = on_event or (lambda e: None)

    def it():
        got = cached_chunk_source(root, key)
        if got is None:
            # entry missing or stale: rebuild it from the producer (the
            # self-repair pass after an invalidation, or a first pass
            # that skipped the eager write)
            src = make_producer()
            if not isinstance(src, ChunkSource):
                src = ChunkSource(lambda s=src: iter(s), schema,
                                  chunk_rows)
            sc = write_chunk_cache(root, key, src, chunk_rows=chunk_rows)
            ev({"event": "ooc_cache_write",
                "path": cache_entry_paths(root, key)[0],
                "rows": sc["rows"], "bytes": sc["bytes"]})
            got = cached_chunk_source(root, key)
            if got is None:               # unwritable root: stream direct
                yield from make_producer()
                return
        inner, sc = got
        ev({"event": "ooc_cache_hit",
            "path": cache_entry_paths(root, key)[0],
            "rows": sc.get("rows"), "bytes": sc.get("bytes")})
        yielded = 0
        restream = False
        try:
            for c in inner:
                yield c
                yielded += c.n
        except GeneratorExit:
            raise
        except Exception as e:
            # corrupt/vanished chunk mid-stream: everything yielded so
            # far passed its checksum — wipe the entry and continue from
            # the producer at the exact row boundary
            ev({"event": "ooc_cache_invalid",
                "path": cache_entry_paths(root, key)[0],
                "error": repr(e)[:200], "rows_served": yielded})
            invalidate_cache_entry(root, key)
            restream = True
        if restream:
            skip = yielded
            for c in make_producer():
                if c.n == 0:
                    continue
                if skip >= c.n:
                    skip -= c.n
                    continue
                if skip:
                    c = _slice_hchunk(c, skip, c.n)
                    skip = 0
                yield c

    src = ChunkSource(it, schema, chunk_rows)
    # the entry key IS a restart-stable content identity (it folds in
    # the producing query's fingerprint, sources included), so queries
    # DERIVED from a cached stream — deg = edges.cache().group_by(...)
    # .cache() — get restart-stable keys of their own instead of
    # degrading to the process salt (which would re-write every derived
    # entry on restart)
    src.fingerprint = "ooc-cache:" + key
    return src
