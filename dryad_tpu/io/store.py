"""Partitioned columnar dataset store.

The counterpart of the reference's dataset layer: URI-scheme data providers
(LinqToDryad/DataProvider.cs, DataPath.cs:124), partitioned files
(GraphManager/filesystem/DrPartitionFile.cpp), and dataset metadata
(DryadLinqMetaData.cs).

Layout (one directory per dataset):
    meta.json        — schema, npartitions, counts, partitioning, version
    part-00000.bin   — all columns of partition 0, concatenated row-major
                       in sorted-column order (strings: data then lengths)

Partition files are written/read by the native parallel scatter-gather IO
engine (native/dryad_io.cpp via dryad_tpu.native) — partitions move in
parallel on a worker pool, the role of the reference's per-channel async
buffer queues (channelbufferqueue.cpp) — with a pure-Python fallback.

A PData that is about to be stored leaves the device through
``exec.data.fetch_partitions`` (whole shards, fixed chunks of the valid
rows only, every copy started before any is awaited, narrow rows reshaped
to wide ones on the device so that they arrive contiguous).  A partition's
segments are then each leaf's chunks as they arrived, in file order: a
column is several consecutive segments, never one assembled copy — file
bytes and the digest are the same either way.

Integrity (``part_checksums``, the ONE digest of every writer and
verifier): every byte written is digested before the write returns and
every byte read is verified before the read returns.  The manifest names
the digest's form.  ``fnv64-blocks`` (format v4): a partition's leaves in
file order (a column's array; a string column is data then lengths), each
cut into blocks of ``checksum_block`` bytes, each block 64-bit FNV-1a
from the basis, a leaf's digest FNV-1a over its block digests, a
partition's (``checksums[p]``) over its leaf digests
(``leaf_checksums[p]``) — independent chains that native/dryad_io.cpp runs
several in lockstep a worker on every core.  ``fnv64`` (format v3, still
read and appended to as written): one chain over all of a partition's
bytes.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from dryad_tpu import native
from dryad_tpu.data.columnar import Batch, StringColumn
from dryad_tpu.exec.data import (PData, batch_nbytes, fetch_partitions,
                                 put_batch)
from dryad_tpu.obs import trace

__all__ = ["write_store", "read_store", "store_meta", "build_meta",
           "schema_row_bytes", "StoreIntegrityError", "is_remote_store",
           "remote_read_part_views", "append_store", "store_generation",
           "parts_since", "part_checksums", "leaf_nbytes"]

# the digest's form a store is written in (docs/store_format.md); a block
# is sized so that a column of a few MB already fills a worker's lanes and
# the per-block bookkeeping stays under a thousandth of its bytes
CHECKSUM_ALGO = "fnv64-blocks"
CHECKSUM_BLOCK = 1 << 20
# a manifest's format_version follows the form its digests are in
_FORMAT_VERSION = {"fnv64": 3, CHECKSUM_ALGO: 4}

_REMOTE_SCHEMES = ("s3://", "hdfs://")


def is_remote_store(path: str) -> bool:
    """True for store paths served by a remote-storage adapter (s3://
    object stores, hdfs:// WebHDFS) rather than the local filesystem."""
    return path.startswith(_REMOTE_SCHEMES)


def remote_read_part_views(path: str, meta: Dict[str, Any], p: int):
    """(segments, column views) of one remote partition — the shared
    building block of read_store and ooc.ChunkSource.from_store
    (DataProvider.cs scheme dispatch, read side)."""
    if path.startswith("s3://"):
        from dryad_tpu.io.s3_store import s3_read_part_views
        return s3_read_part_views(path, meta, p)
    from dryad_tpu.io.webhdfs import hdfs_read_part_views
    return hdfs_read_part_views(path, meta, p)


class StoreIntegrityError(RuntimeError):
    """A partition file's content does not match its recorded checksum
    (``part_checksums`` in the form the manifest names — the role of the
    reference's channel fingerprints, classlib fingerprint.cpp /
    ms_fprint.cpp)."""


def _part_path(path: str, p: int) -> str:
    return os.path.join(path, f"part-{p:05d}.bin")


def schema_row_bytes(schema: Dict[str, Any]) -> int:
    """Uncompressed payload bytes of ONE row under a store schema
    (str columns: max_len data + 4-byte length lane).  Delegates to the
    static cost analyzer's domain (analysis/domain.py) so the manifest's
    byte counts, the OOC in-core decision (exec/ooc.py), and the cost
    model's predictions share ONE row-width arithmetic."""
    from dryad_tpu.analysis.domain import (schema_from_store_schema,
                                           schema_row_bytes as _srb)
    return _srb(schema_from_store_schema(schema))


def checksum_form(meta: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """The digest's form as a manifest names it (a manifest has ONE form:
    ``append_store`` digests new partitions in the form the store was
    written in); the current form for a store not yet written."""
    if meta is None:
        return {"checksum_algo": CHECKSUM_ALGO,
                "checksum_block": CHECKSUM_BLOCK}
    algo = meta.get("checksum_algo", "fnv64")
    if algo == "fnv64":
        return {"checksum_algo": algo}
    if algo != CHECKSUM_ALGO:
        raise ValueError(f"unknown checksum_algo {algo!r} in a store's "
                         "manifest")
    return {"checksum_algo": algo,
            "checksum_block": int(meta["checksum_block"])}


def build_meta(schema: Dict[str, Any], counts: List[int],
               checksums: List[str],
               partitioning: Optional[Dict[str, Any]] = None,
               compression: Optional[str] = None,
               capacity: Optional[int] = None,
               generation: int = 0,
               part_generations: Optional[List[int]] = None,
               leaf_checksums: Optional[List[List[str]]] = None,
               form: Optional[Dict[str, Any]] = None
               ) -> Dict[str, Any]:
    """The ONE meta.json constructor — every writer (in-memory write_store,
    streamed write_chunks_to_store, cluster parallel partition writers)
    goes through it, so format_version / field skew cannot happen.

    ``bytes`` records each partition's UNCOMPRESSED payload bytes
    (count x schema row width — the exact size ``fill_segments``
    materializes on read) so admission/streaming policies (ROADMAP
    items 1 and 4) can size jobs without opening a single partition
    file.  The static cost analyzer seeds its intervals from the
    manifest's ``counts`` + ``schema`` riding store_spec
    (runtime/sources.py -> analysis/cost._source_state).

    ``generation`` / ``part_generations`` make the manifest append-
    aware for continuous queries (dryad_tpu/inc): a fresh write is
    generation 0, every :func:`append_store` commit bumps it, and
    ``part_generations[p]`` records the generation that added partition
    p — so a standing-query refresh holding watermark W scopes its scan
    to ``parts_since(meta, W)`` without touching old partition files.

    ``checksums`` / ``leaf_checksums`` are ``part_checksums``' in the
    digest's ``form`` (``checksum_form``; the current one by default).
    ``leaf_checksums[p]`` are partition p's leaf digests in file order —
    what a read of some columns only verifies those columns by; a writer
    that cannot carry them (the cluster writer's allgather of one digest a
    partition) leaves them out and readers verify ``checksums`` alone."""
    rb = schema_row_bytes(schema)
    form = form or checksum_form()
    return {
        "format_version": _FORMAT_VERSION[form["checksum_algo"]],
        "npartitions": len(counts),
        "counts": list(counts),
        "bytes": [int(c) * rb for c in counts],
        "capacity": capacity if capacity is not None
        else max(list(counts) or [1]),
        "schema": schema,
        "partitioning": partitioning or {"kind": "none"},
        "compression": compression,
        **form,
        "checksums": checksums,
        **({"leaf_checksums": leaf_checksums}
           if form["checksum_algo"] == CHECKSUM_ALGO else {}),
        "native_io": native.available(),
        "generation": int(generation),
        "part_generations": (list(part_generations)
                             if part_generations is not None
                             else [0] * len(counts)),
    }


def store_generation(meta: Dict[str, Any]) -> int:
    """Monotonic append watermark of a manifest (0 for stores written
    before the field existed — they have never been appended to)."""
    return int(meta.get("generation", 0))


def parts_since(meta: Dict[str, Any], watermark: int) -> List[int]:
    """Store partition ids committed AFTER ``watermark`` — the delta a
    standing-query refresh must scan.  ``watermark=-1`` (no state yet)
    returns every partition; ``watermark=store_generation(meta)``
    returns none."""
    gens = meta.get("part_generations") or [0] * int(meta["npartitions"])
    return [p for p, g in enumerate(gens) if int(g) > watermark]


def _col_order(schema: Dict[str, Any]) -> List[str]:
    return sorted(schema.keys())


def pdata_schema(pd: "PData") -> Dict[str, Any]:
    """Store schema of a PData's columns — the ONE schema-inference
    point shared by every store writer (local, s3://, hdfs://), so a
    new column kind cannot diverge between adapters."""
    schema: Dict[str, Any] = {}
    for k, v in pd.batch.columns.items():
        if isinstance(v, StringColumn):
            schema[k] = {"kind": "str", "max_len": int(v.data.shape[2])}
        else:
            schema[k] = {"kind": "dense", "dtype": np.dtype(v.dtype).name,
                         "shape": list(v.shape[2:])}
    return schema


def chunk_segments(schema: Dict[str, Any],
                   cols: Dict[str, Any]) -> List[np.ndarray]:
    """One host chunk's column segments in file order (sorted columns,
    strings as data+lengths) — the write-side counterpart of
    ``_alloc_part_views``, shared by every chunk writer."""
    segs: List[np.ndarray] = []
    for k in _col_order(schema):
        v = cols[k]
        if schema[k]["kind"] == "str":
            segs.append(np.ascontiguousarray(v[0]))
            segs.append(np.ascontiguousarray(v[1]))
        else:
            segs.append(np.ascontiguousarray(v))
    return segs


def segments_blob(segs: List[np.ndarray],
                  compression: Optional[str]) -> bytes:
    """Serialize part segments to the single on-wire blob encoding every
    remote writer ships (and verify_checksums' layout assumes)."""
    import gzip
    blob = b"".join(np.ascontiguousarray(s).tobytes() for s in segs)
    if compression == "gzip":
        blob = gzip.compress(blob, compresslevel=1)
    return blob


def fill_segments(segs: List[np.ndarray], data: bytes, what: str) -> None:
    """Fill preallocated part segments from one (decompressed) blob —
    the read-side inverse of ``segments_blob``, shared by the remote
    adapters.  Size check FIRST: short (truncated/corrupt) data would
    otherwise crash inside np.frombuffer with an error naming no file;
    ``what`` names the part in the diagnostic."""
    expected = sum(s.nbytes for s in segs)
    if expected != len(data):
        raise IOError(f"partition size mismatch: expected {expected} "
                      f"bytes, {what} holds {len(data)}")
    off = 0
    for s in segs:
        nb = s.nbytes
        s.reshape(-1)[:] = np.frombuffer(data[off:off + nb], dtype=s.dtype)
        off += nb


def leaf_nbytes(schema: Dict[str, Any], n: int) -> List[int]:
    """Byte length of each leaf of a partition of ``n`` rows, in file
    order (sorted columns; a string column is data then lengths)."""
    sizes: List[int] = []
    for k in _col_order(schema):
        spec = schema[k]
        if spec["kind"] == "str":
            sizes.extend([n * int(spec["max_len"]), n * 4])
        else:
            sizes.append(n * np.dtype(spec["dtype"]).itemsize
                         * int(np.prod(spec.get("shape", ()), dtype=np.int64)))
    return sizes


def part_checksums(schema: Dict[str, Any], counts, segments,
                   meta: Optional[Dict[str, Any]] = None
                   ) -> Tuple[List[str], Optional[List[List[str]]],
                              Dict[str, Any]]:
    """The ONE digest of every store writer and verifier:
    ``(checksums, leaf_checksums, info)`` of partitions holding
    ``counts[i]`` rows whose bytes are ``segments[i]``, contiguous arrays
    cut anywhere (the chunks a column came off the device in, an array a
    column, one blob), in the form ``meta`` names — the current one for a
    store not yet written.  ``leaf_checksums`` is None in the chained form,
    which has none; ``info`` says what ran (``algo``, ``blocks``,
    ``threads``) and rides on the ``store.verify`` / ``store.checksum``
    spans."""
    form = checksum_form(meta)
    if form["checksum_algo"] == "fnv64":
        return (["%016x" % native.checksum_segments(segs)
                 for segs in segments], None,
                {"algo": "fnv64", "blocks": len(segments), "threads": 1})
    sums, leaves, ran = native.digest_parts(
        segments, [leaf_nbytes(schema, int(n)) for n in counts],
        form["checksum_block"])
    return (["%016x" % h for h in sums],
            [["%016x" % h for h in part] for part in leaves],
            {"algo": form["checksum_algo"], **ran})


def _segments_nbytes(segments) -> int:
    """Payload bytes of per-partition segment lists, from their shapes."""
    return sum(s.nbytes for segs in segments for s in segs)


def fetch_part_segments(pd: PData, schema, counts: np.ndarray):
    """For partition 0, 1, ... in turn ``(segments, moved_bytes, chunks)``:
    the blobs of the partition's ``counts[p]`` valid rows in sorted-column
    order (strings: data then lengths; a column is one segment a chunk it
    arrived in), brought off the device by ``exec.data.fetch_partitions``
    — the ONE fetch of every store writer (local, append, s3://, hdfs://,
    the stage spill)."""
    leaves: List[Any] = []
    for k in _col_order(schema):
        v = pd.batch.columns[k]
        leaves.extend([v.data, v.lengths] if isinstance(v, StringColumn)
                      else [v])
    return fetch_partitions(leaves, counts)


def write_store(path: str, pd: PData,
                partitioning: Optional[Dict[str, Any]] = None,
                compression: Optional[str] = None) -> Optional[int]:
    """Persist a PData (ToStore, DryadLinqQueryable.cs:3909).  Atomic via
    temp-dir rename (the reference commits temp outputs at job end,
    DrVertex.h:325-351).  A local write returns the rows it wrote.

    ``compression="gzip"`` writes level-1 gzip partition files (the
    per-channel compression transform of the reference,
    GzipCompressionChannelTransform.cpp).  Checksums are fnv64 over the
    UNCOMPRESSED segments, verified on read."""
    if compression not in (None, "gzip"):
        raise ValueError(f"unknown compression {compression!r}")
    if path.startswith("s3://"):
        # cloud adapter: same layout as objects, meta-last commit
        from dryad_tpu.io.s3_store import s3_write_store
        return s3_write_store(path, pd, partitioning=partitioning,
                              compression=compression)
    if path.startswith("hdfs://"):
        # hdfs adapter: same layout as files, temp-dir rename commit
        from dryad_tpu.io.webhdfs import hdfs_write_store
        return hdfs_write_store(path, pd, partitioning=partitioning,
                                compression=compression)
    with trace.span("store.write", "io", partitions=pd.nparts) as sp:
        tmp = path + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        counts = np.asarray(pd.counts)
        schema = pdata_schema(pd)
        paths, segments = [], []
        fetched = fetch_part_segments(pd, schema, counts)
        for p in range(pd.nparts):
            paths.append(_part_path(tmp, p))
            # device -> host: partition 0's span starts the copy of every
            # chunk of every partition, then each span waits for what its
            # partition still misses (exec.data.fetch_partitions)
            with trace.span("store.fetch", "io", partition=p) as fsp:
                segs, moved, chunks = next(fetched)
                segments.append(segs)
                fsp.set(bytes=_segments_nbytes(segments[-1:]),
                        moved_bytes=moved, chunks=chunks)
        nbytes = _segments_nbytes(segments)
        sp.set(bytes=nbytes)
        with trace.span("store.file_write", "io", bytes=nbytes,
                        files=len(paths)):
            native.write_files(paths, segments,
                               compress=(compression == "gzip"))
        with trace.span("store.checksum", "io", bytes=nbytes) as csp:
            checksums, leaf_checksums, ran = part_checksums(
                schema, counts, segments)
            csp.set(**ran)
        with trace.span("store.commit", "io"):
            meta = build_meta(schema, counts.tolist(), checksums,
                              partitioning=partitioning,
                              compression=compression,
                              capacity=pd.capacity,
                              leaf_checksums=leaf_checksums)
            with open(os.path.join(tmp, "meta.json"), "w") as f:
                json.dump(meta, f, indent=1)
            if os.path.exists(path):
                import shutil
                shutil.rmtree(path)
            os.rename(tmp, path)
        return int(counts.sum())


def append_store(path: str, pd: PData) -> int:
    """Append a PData to an EXISTING local store as a new generation;
    returns the committed generation number.

    The growing-store primitive of the continuous-query subsystem
    (dryad_tpu/inc): new partition files land at indices >= the current
    ``npartitions`` under their final names, then ONE atomic
    ``os.replace`` of ``meta.json`` publishes the extended manifest with
    ``generation+1`` (same rename-commit discipline as write_store — a
    crash before the replace leaves orphan part files the old manifest
    never references, so readers and watermarks never see a torn
    append; a retry simply overwrites them).

    The appended columns must match the store schema exactly (same
    string max_len) — appends never migrate schemas.  A non-trivial
    partitioning claim is downgraded to ``none``: appended rows were
    not placed, so the persisted hash/range layout no longer holds."""
    if is_remote_store(path):
        raise NotImplementedError(
            "append_store supports local stores only (remote adapters "
            "commit whole stores; re-write via write_store)")
    meta = store_meta(path)
    schema = pdata_schema(pd)
    if schema != meta["schema"]:
        raise ValueError(
            f"append schema mismatch for {path}: store has "
            f"{meta['schema']}, appended data has {schema}")
    compression = meta.get("compression")
    counts = np.asarray(pd.counts)
    base = int(meta["npartitions"])
    paths, segments, new_counts = [], [], []
    for n, (segs, _, _) in zip(counts.tolist(),
                               fetch_part_segments(pd, schema, counts)):
        if n == 0:  # empty shards would bloat the manifest forever
            continue
        paths.append(_part_path(path, base + len(new_counts)))
        segments.append(segs)
        new_counts.append(n)
    if not new_counts:
        return store_generation(meta)
    native.write_files(paths, segments,
                       compress=(compression == "gzip"))
    checksums, leaf_checksums, _ = part_checksums(schema, new_counts,
                                                  segments, meta)
    old_leaves = meta.get("leaf_checksums")
    gen = store_generation(meta) + 1
    gens = list(meta.get("part_generations") or [0] * base)
    part = meta.get("partitioning") or {"kind": "none"}
    new_meta = build_meta(
        meta["schema"], list(meta["counts"]) + new_counts,
        list(meta.get("checksums") or []) + checksums,
        partitioning=part if part.get("kind") == "none"
        else {"kind": "none"},
        compression=compression,
        capacity=max(int(meta.get("capacity", 1)), max(new_counts)),
        generation=gen,
        part_generations=gens + [gen] * len(new_counts),
        # a manifest carries leaf digests for every partition or for none
        leaf_checksums=(old_leaves + leaf_checksums
                        if None not in (old_leaves, leaf_checksums)
                        else None),
        form=checksum_form(meta))
    from dryad_tpu.utils.atomic import atomic_write_json
    atomic_write_json(os.path.join(path, "meta.json"), new_meta,
                      indent=1)
    return gen


def store_meta(path: str) -> Dict[str, Any]:
    if path.startswith("s3://"):
        from dryad_tpu.io.s3_store import s3_store_meta
        return s3_store_meta(path)
    if path.startswith("hdfs://"):
        from dryad_tpu.io.webhdfs import hdfs_store_meta
        return hdfs_store_meta(path)
    with open(os.path.join(path, "meta.json")) as f:
        return json.load(f)


def verify_checksums(path: str, meta: Dict[str, Any],
                     segments: List[List[np.ndarray]],
                     partitions: Optional[List[int]] = None
                     ) -> Optional[Dict[str, Any]]:
    """Compare freshly-read partition segments against the recorded
    checksums, in the form the manifest names and in ONE digest call for
    all of them; raise StoreIntegrityError naming the partition (and, where
    the manifest carries leaf digests, the column) on a mismatch.  Returns
    ``part_checksums``' info.  Stores written before format v3 carry no
    checksums and are accepted as-is (None)."""
    recorded = meta.get("checksums")
    if not recorded:
        return None
    parts = list(partitions if partitions is not None
                 else range(len(segments)))
    schema = meta["schema"]
    counts = [int(meta["counts"][p]) for p in parts]
    for segs, p, n in zip(segments, parts, counts):
        have, want = _segments_nbytes([segs]), sum(leaf_nbytes(schema, n))
        if have != want:
            raise StoreIntegrityError(
                f"partition {p} of {path}: {have} bytes read, the manifest's "
                f"{n} rows are {want} — file truncated or tampered")
    sums, leaves, ran = part_checksums(schema, counts, segments, meta)
    rec_leaves = meta.get("leaf_checksums") if leaves is not None else None
    leaf_column = [k for k in _col_order(schema)
                   for _ in range(2 if schema[k]["kind"] == "str" else 1)]
    for i, p in enumerate(parts):
        bad = [j for j, (got, rec) in enumerate(zip(leaves[i], rec_leaves[p]))
               if got != rec] if rec_leaves else []
        if sums[i] != recorded[p] or bad:
            where = (f" (column {leaf_column[bad[0]]!r}, leaf {bad[0]})"
                     if bad else "")
            raise StoreIntegrityError(
                f"partition {p} of {path}{where}: checksum {sums[i]} != "
                f"recorded {recorded[p]} — file corrupted or tampered")
    return ran


def _alloc_part_views(schema, n: int) -> Tuple[List[np.ndarray],
                                               Dict[str, Any]]:
    """Allocate per-column arrays for one partition's n valid rows, in file
    order; return (ordered segment list, name -> array(s) map)."""
    segs: List[np.ndarray] = []
    cols: Dict[str, Any] = {}
    for k in _col_order(schema):
        spec = schema[k]
        if spec["kind"] == "str":
            d = np.empty((n, spec["max_len"]), np.uint8)
            l = np.empty((n,), np.int32)
            segs.extend([d, l])
            cols[k] = ("str", d, l, spec["max_len"])
        else:
            a = np.empty((n,) + tuple(spec["shape"]),
                         np.dtype(spec["dtype"]))
            segs.append(a)
            cols[k] = ("dense", a)
    return segs, cols


def read_store(path: str, mesh, capacity: Optional[int] = None,
               partitions: Optional[List[int]] = None,
               verify: bool = True) -> PData:
    """Load a dataset store as sharded PData (FromStore,
    DryadLinqContext.cs:1176).

    When the store's partition count equals the mesh size, store partition p
    is loaded into mesh partition p VERBATIM (per-partition counts
    preserved), so persisted hash/range placement — honored by
    ``from_store`` for shuffle elimination — stays valid.  Only when the
    counts differ are rows re-blocked evenly (and ``from_store`` then drops
    the partitioning claim).

    ``partitions`` reads only the listed store partitions (the per-task
    input granularity of the task farm — one vertex per partition file,
    DrPartitionFile.cpp:607)."""
    meta = store_meta(path)
    part_ids = (list(range(meta["npartitions"])) if partitions is None
                else list(partitions))
    with trace.span("store.read", "io", partitions=len(part_ids),
                    columns=len(meta["schema"])) as sp:
        counts = [meta["counts"][p] for p in part_ids]
        nparts_store = len(part_ids)
        schema = meta["schema"]
        nparts = mesh.devices.size

        paths, segments, partviews = [], [], []
        with trace.span("store.file_read", "io", files=len(part_ids)) as fsp:
            if is_remote_store(path):
                for p in part_ids:
                    segs, cols = remote_read_part_views(path, meta, p)
                    segments.append(segs)
                    partviews.append(cols)
            else:
                for p in part_ids:
                    segs, cols = _alloc_part_views(schema, meta["counts"][p])
                    paths.append(_part_path(path, p))
                    segments.append(segs)
                    partviews.append(cols)
                    # a file cut short or grown is named as what it is, not
                    # as a failed read (a gzip file's size says nothing)
                    if verify and meta.get("compression") is None:
                        have = os.path.getsize(paths[-1])
                        want = _segments_nbytes([segs])
                        if have != want:
                            raise StoreIntegrityError(
                                f"partition {p} of {path}: the file holds "
                                f"{have} bytes, the manifest's "
                                f"{meta['counts'][p]} rows are {want} — "
                                "file truncated or tampered")
                native.read_files(paths, segments,
                                  compress=(meta.get("compression") == "gzip"))
            nbytes = _segments_nbytes(segments)
            fsp.set(bytes=nbytes)
        sp.set(bytes=nbytes)
        if verify:
            with trace.span("store.verify", "io", bytes=nbytes) as vsp:
                vsp.set(**(verify_checksums(path, meta, segments,
                                            partitions=part_ids) or {}))

        if nparts_store == nparts:
            # verbatim per-partition load: placement-preserving
            cap = capacity or max(int(meta.get("capacity", 0)),
                                  max(counts or [0]), 1)
            part_rows = [{k: (partviews[p][k][1:3]
                              if schema[k]["kind"] == "str"
                              else partviews[p][k][1])
                          for k in schema} for p in range(nparts)]
            return _stack_partitions(schema, part_rows, counts, cap, mesh)

        # partition counts differ: concatenate store partitions then
        # re-block over the mesh (placement-destroying; callers drop
        # partitioning claims)
        concat: Dict[str, Any] = {}
        for k in schema:
            if schema[k]["kind"] == "str":
                concat[k] = (np.concatenate([pv[k][1] for pv in partviews]),
                             np.concatenate([pv[k][2] for pv in partviews]))
            else:
                concat[k] = np.concatenate([pv[k][1] for pv in partviews])

        total = sum(counts)
        base, rem = divmod(total, nparts)
        sizes = [base + (1 if p < rem else 0) for p in range(nparts)]
        cap = capacity or max(1, max(sizes))
        offs = np.cumsum([0] + sizes)
        part_rows = [{k: ((concat[k][0][offs[p]:offs[p + 1]],
                           concat[k][1][offs[p]:offs[p + 1]])
                          if schema[k]["kind"] == "str"
                          else concat[k][offs[p]:offs[p + 1]])
                      for k in schema} for p in range(nparts)]
        return _stack_partitions(schema, part_rows, sizes, cap, mesh)


def _stack_partitions(schema, part_rows: List[Dict[str, Any]],
                      counts, cap: int, mesh) -> PData:
    """Stack per-partition row blocks into a sharded [P, cap, ...] PData.

    ``part_rows[p][k]`` is either a dense array of partition p's rows or a
    ``(data, lengths)`` pair for string columns; ``counts[p]`` rows each."""
    nparts = len(part_rows)
    if cap < max(list(counts) or [0]):
        raise ValueError(f"capacity {cap} < max partition count "
                         f"{max(counts)}")
    cols: Dict[str, Any] = {}
    with trace.span("store.stack", "io") as sp:
        for k, spec in schema.items():
            if spec["kind"] == "str":
                max_len = spec["max_len"]
                sd = np.zeros((nparts, cap, max_len), np.uint8)
                sl = np.zeros((nparts, cap), np.int32)
                for p in range(nparts):
                    d, l = part_rows[p][k]
                    sd[p, : counts[p]] = d
                    sl[p, : counts[p]] = l
                cols[k] = StringColumn(sd, sl)
            else:
                first = part_rows[0][k]
                stacked = np.zeros((nparts, cap) + first.shape[1:],
                                   first.dtype)
                for p in range(nparts):
                    stacked[p, : counts[p]] = part_rows[p][k]
                cols[k] = stacked
        host = Batch(cols, np.asarray(counts, np.int32))
        nbytes = batch_nbytes(host)
        sp.set(bytes=nbytes)
    # the enqueue only: the copy to the device ends after this span does
    with trace.span("store.put", "io", bytes=nbytes):
        batch = put_batch(host, mesh)
    return PData(batch, nparts)
