"""Partitioned columnar dataset store — the ONE module that knows the format.

The counterpart of the reference's dataset layer: URI-scheme data providers
(LinqToDryad/DataProvider.cs, DataPath.cs:124), partitioned files
(GraphManager/filesystem/DrPartitionFile.cpp), and dataset metadata
(DryadLinqMetaData.cs).

Layout (one directory per dataset, docs/store_format.md):
    meta.json        — schema, npartitions, counts, partitioning, version
    part-00000.bin   — all columns of partition 0, concatenated row-major
                       in sorted-column order (strings: data then lengths)

Three layers, arrows one way:

* callers (api/dataset, sql/catalog, exec/recovery, exec/ooc,
  runtime/stream_cluster, inc/refresh) hand rows over and take rows back:
  ``write_store`` / ``append_store`` / ``StoreWriter`` / ``read_store`` /
  ``read_parts`` / ``iter_part_chunks``;
* this module owns the format: ``part_layout`` (where every leaf of a
  partition lies), segments <-> bytes, the digest's form, the manifest
  (``build_meta``) and the order of a commit (bytes, digest, manifest last,
  commit) in ``StoreWriter``, the order of a read (allocate, fill, verify)
  in ``read_parts``;
* a byte target a scheme (``_LocalDir`` here, ``io/s3_store.S3Prefix``,
  ``io/webhdfs.HdfsDir``; ``_target`` is the one dispatch) knows names and
  bytes and what makes its commit atomic — nothing of schemas, digests or
  manifests — and never imports this module.

Local partition files are written/read by the native parallel scatter-gather
IO engine (native/dryad_io.cpp via dryad_tpu.native) — partitions move in
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

import gzip
import json
import os
import shutil
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from dryad_tpu import native
from dryad_tpu.data.columnar import Batch, Int64Column, StringColumn
from dryad_tpu.exec.data import (PData, batch_nbytes, fetch_partitions,
                                 key_hashes, put_batch)
from dryad_tpu.obs import trace

__all__ = ["write_store", "read_store", "store_meta", "build_meta",
           "schema_row_bytes", "StoreIntegrityError", "is_remote_store",
           "StoreKeyError", "check_unique",
           "append_store", "store_generation", "parts_since",
           "part_checksums", "part_layout", "store_schema", "kept_schema",
           "StoreWriter",
           "read_parts", "iter_part_chunks", "has_ranged_read",
           "clear_shared_temp"]

# the digest's form a store is written in (docs/store_format.md); a block
# is sized so that a column of a few MB already fills a worker's lanes and
# the per-block bookkeeping stays under a thousandth of its bytes
CHECKSUM_ALGO = "fnv64-blocks"
CHECKSUM_BLOCK = 1 << 20
# a manifest's format_version follows the form its digests are in
_FORMAT_VERSION = {"fnv64": 3, CHECKSUM_ALGO: 4}

_REMOTE_SCHEMES = ("s3://", "hdfs://")

# schema kinds whose rows are a pair of arrays above the store
_PAIR_KINDS = ("str", "int64")


def is_remote_store(path: str) -> bool:
    """True for store paths served by a remote byte target (s3:// object
    stores, hdfs:// WebHDFS) rather than the local filesystem."""
    return path.startswith(_REMOTE_SCHEMES)


class StoreIntegrityError(RuntimeError):
    """A partition file's content does not match its recorded checksum
    (``part_checksums`` in the form the manifest names — the role of the
    reference's channel fingerprints, classlib fingerprint.cpp /
    ms_fprint.cpp)."""


class StoreKeyError(ValueError):
    """The rows do not bear out a ``unique`` declaration: two of them
    share the key (or, to the join kernels, look as if they did: equal
    64-bit key hashes), or the key names no column of theirs."""


def check_unique(hashes: np.ndarray, unique: Sequence[str],
                 what: str) -> None:
    """Hold rows to a ``unique`` declaration, given their 64-bit key
    hashes (``exec.data.key_hashes``): sorted, adjacent pairs compared —
    the check ``kernels.hash_join(right_unique=True)`` makes of its right
    side in every run, made once, where the rows are written or
    registered.  Two distinct keys that collide in all 64 bits are
    refused as duplicates (the lookup join tells rows apart by that hash),
    and so is a key that hashes to the all-ones value padding rows sort
    under."""
    s = np.sort(hashes)
    dup = int((s[1:] == s[:-1]).sum())
    if dup or (s.size and s[-1] == np.uint64(0xFFFFFFFFFFFFFFFF)):
        raise StoreKeyError(
            f"{what}: the columns {list(unique)} are declared unique, but "
            + (f"{dup} of {s.size} rows repeat an earlier row's key"
               if dup else "a key hashes to the padding rows' value"))


# ---------------------------------------------------------------------------
# the layout


class Leaf(NamedTuple):
    """One leaf of a partition file: a dense column's array (``str_part``
    None), or the first (``str_part`` 0) or the second (1) array of a
    column held as two: a string column's data and lengths, a 64-bit
    integer column's upper and lower words."""
    column: str
    str_part: Optional[int]
    dtype: np.dtype
    row_shape: Tuple[int, ...]
    row_bytes: int
    offset: int
    nbytes: int


def part_layout(schema: Dict[str, Any], n: int = 0) -> List[Leaf]:
    """THE layout: the leaves of a partition of ``n`` rows in file order
    (columns sorted by name; a string column is its padded bytes, then its
    int32 lengths; an ``int64`` column its int32 upper words, then its
    uint32 lower words, as the device holds it), each with where it
    starts and how long it is.  Rows
    [s, e) of a leaf are the ``(e - s) * row_bytes`` bytes at
    ``offset + s * row_bytes``.  Every allocation, segment order, digest
    and ranged read of a store is read off this."""
    leaves: List[Leaf] = []
    off = 0
    for k in sorted(schema):
        spec = schema[k]
        if spec["kind"] == "str":
            parts = [(0, np.dtype(np.uint8), (int(spec["max_len"]),)),
                     (1, np.dtype(np.int32), ())]
        elif spec["kind"] == "int64":
            parts = [(0, np.dtype(np.int32), ()),
                     (1, np.dtype(np.uint32), ())]
        else:
            parts = [(None, np.dtype(spec["dtype"]),
                      tuple(int(d) for d in spec.get("shape", ())))]
        for str_part, dt, shape in parts:
            rb = dt.itemsize * int(np.prod(shape, dtype=np.int64))
            leaves.append(Leaf(k, str_part, dt, shape, rb, off, n * rb))
            off += n * rb
    return leaves


def schema_row_bytes(schema: Dict[str, Any]) -> int:
    """Uncompressed payload bytes of ONE row under a store schema (str
    columns: max_len data + 4-byte length lane): the manifest's byte
    counts and the OOC in-core decision (exec/ooc.py) read it here;
    analysis/domain.py's row width is held equal to it by
    tests/test_store_seam.py."""
    return sum(leaf.row_bytes for leaf in part_layout(schema))


def store_schema(schema: Dict[str, Any]) -> Dict[str, Any]:
    """A chunk stream's schema (exec/ooc.chunk_schema) as a manifest
    records it — the ONE conversion, for every chunk writer."""
    out: Dict[str, Any] = {}
    for k, spec in schema.items():
        if spec["kind"] == "str":
            out[k] = {"kind": "str", "max_len": spec["max_len"]}
        elif spec["kind"] == "int64":
            out[k] = {"kind": "int64"}
        else:
            out[k] = {"kind": "dense", "dtype": spec["dtype"],
                      "shape": list(spec.get("shape", ()))}
    return out


def pdata_schema(pd: "PData") -> Dict[str, Any]:
    """Store schema of a PData's columns — the ONE schema-inference
    point of ``write_store`` and ``append_store``, whatever the target."""
    schema: Dict[str, Any] = {}
    for k, v in pd.batch.columns.items():
        if isinstance(v, StringColumn):
            schema[k] = {"kind": "str", "max_len": int(v.data.shape[2])}
        elif isinstance(v, Int64Column):
            schema[k] = {"kind": "int64"}
        else:
            schema[k] = {"kind": "dense", "dtype": np.dtype(v.dtype).name,
                         "shape": list(v.shape[2:])}
    return schema


def _columns(layout: List[Leaf], arrays) -> Dict[str, Any]:
    """name -> array (dense), (data, lengths) (string) or (upper words,
    lower words) (int64), from one array a leaf in file order — the shape
    a chunk's and a partition's rows have everywhere above the store."""
    cols: Dict[str, Any] = {}
    for leaf, a in zip(layout, arrays):
        # of a column held as two arrays, str_part 0 comes first
        cols[leaf.column] = (cols[leaf.column], a) if leaf.str_part else a
    return cols


def chunk_segments(schema: Dict[str, Any],
                   cols: Dict[str, Any]) -> List[np.ndarray]:
    """One host chunk's column segments in file order — the inverse of
    ``_columns``."""
    return [np.ascontiguousarray(cols[leaf.column] if leaf.str_part is None
                                 else cols[leaf.column][leaf.str_part])
            for leaf in part_layout(schema)]


def kept_schema(schema: Dict[str, Any],
                columns: Optional[Sequence[str]]) -> Dict[str, Any]:
    """The schema of a read of ``columns`` (stored column names; None =
    all): the named columns' specs in the manifest's column order, so that
    a source's column order is a function of the manifest and the names,
    not of how a caller listed them — the schema itself where that is every
    column.  A name the schema lacks and an empty list are refused."""
    if columns is None:
        return schema
    names = set(columns)
    if not names:
        raise ValueError("a read of no column: name at least one, or pass "
                         "columns=None for all of them")
    missing = sorted(names - set(schema))
    if missing:
        raise KeyError(f"the store has no column "
                       f"{', '.join(map(repr, missing))} (it has "
                       f"{', '.join(sorted(schema))})")
    return schema if len(names) == len(schema) \
        else {k: spec for k, spec in schema.items() if k in names}


def _alloc_part_views(schema, n: int,
                      held: Optional[Sequence[int]] = None
                      ) -> Tuple[List[np.ndarray], Dict[str, Any]]:
    """Allocate one array a leaf for a partition's n valid rows — every
    leaf of the layout, or the leaves ``held`` (indices, ascending) — in
    file order; return (ordered segment list, ``_columns`` of them)."""
    layout = part_layout(schema)
    if held is not None:
        layout = [layout[j] for j in held]
    segs = [np.empty((n,) + leaf.row_shape, leaf.dtype) for leaf in layout]
    return segs, _columns(layout, segs)


def segments_blob(segs: List[np.ndarray],
                  compression: Optional[str]) -> bytes:
    """Serialize part segments to the single blob a remote target is
    handed (and verify_checksums' layout assumes)."""
    blob = b"".join(np.ascontiguousarray(s).tobytes() for s in segs)
    if compression == "gzip":
        blob = gzip.compress(blob, compresslevel=1)
    return blob


def fill_segments(segs: List[np.ndarray], data: bytes, what: str) -> None:
    """Fill preallocated part segments from one (decompressed) blob —
    the read-side inverse of ``segments_blob``.  Size check FIRST: short
    (truncated/corrupt) data would otherwise crash inside np.frombuffer
    with an error naming no file; ``what`` names the part in the
    diagnostic."""
    expected = sum(s.nbytes for s in segs)
    if expected != len(data):
        raise IOError(f"partition size mismatch: expected {expected} "
                      f"bytes, {what} holds {len(data)}")
    off = 0
    for s in segs:
        nb = s.nbytes
        s.reshape(-1)[:] = np.frombuffer(data[off:off + nb], dtype=s.dtype)
        off += nb


def _segments_nbytes(segments) -> int:
    """Payload bytes of per-partition segment lists, from their shapes."""
    return sum(s.nbytes for segs in segments for s in segs)


# ---------------------------------------------------------------------------
# the byte targets: names and bytes, and what makes a commit atomic


def _part_path(path: str, p: int) -> str:
    return os.path.join(path, f"part-{p:05d}.bin")


class _LocalDir:
    """The local byte target: part files and the manifest in a directory.
    A fresh write lands in ``<path>.tmp`` and commits by renaming it onto
    ``<path>`` (the reference commits temp outputs at job end,
    DrVertex.h:325-351); an append lands under final names the old
    manifest never references and commits by the atomic replace of
    ``meta.json``."""

    ranged = False

    def __init__(self, path: str):
        self.path = self.dir = path

    def read_meta(self) -> bytes:
        with open(os.path.join(self.path, "meta.json"), "rb") as f:
            return f.read()

    def clear_stale(self) -> None:
        shutil.rmtree(self.path + ".tmp", ignore_errors=True)

    def begin(self, shared: bool = False) -> None:
        self.dir = self.path + ".tmp"
        os.makedirs(self.dir, exist_ok=True)

    def part(self, p: int) -> str:
        return _part_path(self.dir, p)

    def commit_append(self, manifest: bytes) -> None:
        from dryad_tpu.utils.atomic import atomic_write_bytes
        atomic_write_bytes(os.path.join(self.path, "meta.json"), manifest)

    def commit(self, manifest: bytes) -> None:
        with open(os.path.join(self.dir, "meta.json"), "wb") as f:
            f.write(manifest)
        if os.path.exists(self.path):
            shutil.rmtree(self.path)
        os.rename(self.dir, self.path)


def _target(path: str, meta: Optional[Dict[str, Any]] = None):
    """The byte target of a store path — the ONE scheme dispatch
    (DataProvider.cs).  A reader passes the manifest: an ``s3://`` prefix
    resolves part names through the generation the manifest records."""
    if path.startswith("s3://"):
        from dryad_tpu.io.s3_store import S3Prefix
        return S3Prefix(path, (meta or {}).get("generation", ""))
    if path.startswith("hdfs://"):
        from dryad_tpu.io.webhdfs import HdfsDir
        return HdfsDir(path)
    return _LocalDir(path)


def _put_parts(target, part_ids, segments, compression) -> None:
    """Partitions' bytes onto the target.  The local directory takes the
    segments as they are, all partitions of the call in ONE native
    scatter-gather call (no blob is ever built); a remote target takes one
    encoded blob a partition."""
    if isinstance(target, _LocalDir):
        native.write_files([target.part(p) for p in part_ids], segments,
                           compress=(compression == "gzip"))
        return
    for p, segs in zip(part_ids, segments):
        target.put(p, segments_blob(segs, compression))


def _fill_parts(target, part_ids, segments, compression) -> None:
    """Preallocated segments filled with their partitions' bytes: locally
    ONE native call for all of them; a remote partition as one blob, or —
    uncompressed, on a target with ranged reads — piece by piece straight
    into the segments (ranges of a gzip stream don't decompress alone)."""
    if isinstance(target, _LocalDir):
        native.read_files([target.part(p) for p in part_ids], segments,
                          compress=(compression == "gzip"))
        return
    for p, segs in zip(part_ids, segments):
        if target.ranged and compression is None:
            target.fill(p, segs)
            continue
        data = target.get(p)
        if compression == "gzip":
            data = gzip.decompress(data)
        fill_segments(segs, data, target.what(p))


def _fill_ranges(target, part_ids, segments, ranges) -> None:
    """Preallocated segments filled from byte ranges of their
    (uncompressed) partitions, ``ranges[i][j]`` = (offset, nbytes) of
    ``segments[i][j]``: locally ONE native call for all of them, a remote
    partition one ranged request a segment."""
    if isinstance(target, _LocalDir):
        native.read_files([target.part(p) for p in part_ids], segments,
                          offsets=[[off for off, _ in rs] for rs in ranges])
        return
    for p, segs, rs in zip(part_ids, segments, ranges):
        for seg, (off, nb) in zip(segs, rs):
            if nb:
                fill_segments([seg], target.get_range(p, off, nb),
                              target.what(p))


def has_ranged_read(path: str, meta: Dict[str, Any]) -> bool:
    """Whether ``iter_part_chunks`` can stream this store's partitions:
    the target reads byte ranges (``hdfs://`` today) and the parts are
    uncompressed."""
    return _target(path, meta).ranged and meta.get("compression") != "gzip"


def clear_shared_temp(path: str) -> None:
    """Remove what a crashed ``StoreWriter(shared=True)`` job left in the
    shared temp directory.  ONE process calls it, and the caller fences it
    from the writers with a barrier."""
    _target(path).clear_stale()


# ---------------------------------------------------------------------------
# the digest and the manifest


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
               form: Optional[Dict[str, Any]] = None,
               unique: Optional[Sequence[str]] = None
               ) -> Dict[str, Any]:
    """The ONE meta.json constructor (``StoreWriter.commit`` calls it for
    every writer), so format_version / field skew cannot happen.

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
    partition) leaves them out and readers verify ``checksums`` alone.

    ``unique``: the columns that together are a key of the stored rows —
    no two rows agree on all of them — VERIFIED by the writer over the
    rows it wrote (``write_store``); the field is left out where none was
    declared, and a store without it has no key."""
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
        **({"unique": list(unique)} if unique else {}),
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


def part_checksums(schema: Dict[str, Any], counts, segments,
                   meta: Optional[Dict[str, Any]] = None,
                   leaves: Optional[Sequence[int]] = None
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
    spans.

    ``leaves`` (the block form only): the segments hold just these leaves
    of the layout (indices, ascending).  Their leaf digests come out as
    they are recorded — a leaf's digest does not depend on its neighbours —
    and the partition digests, taken over a subset, compare with
    nothing."""
    form = checksum_form(meta)
    if form["checksum_algo"] == "fnv64":
        return (["%016x" % native.checksum_segments(segs)
                 for segs in segments], None,
                {"algo": "fnv64", "blocks": len(segments), "threads": 1})
    sizes = []
    for n in counts:
        layout = part_layout(schema, int(n))
        sizes.append([layout[j].nbytes for j in
                      (range(len(layout)) if leaves is None else leaves)])
    sums, leaf_sums, ran = native.digest_parts(segments, sizes,
                                               form["checksum_block"])
    return (["%016x" % h for h in sums],
            [["%016x" % h for h in part] for part in leaf_sums],
            {"algo": form["checksum_algo"], **ran})


def store_meta(path: str) -> Dict[str, Any]:
    return json.loads(_target(path).read_meta())


# ---------------------------------------------------------------------------
# the writer


class StoreWriter:
    """The ONE writer of a store: bytes, digest, manifest last, commit.

    Opening begins the write on the path's byte target (a temp directory
    locally and on ``hdfs://``, a fresh generation prefix on ``s3://``).
    ``add`` puts partitions' bytes through the target and digests them in
    the manifest's form, so every byte written is digested before ``add``
    returns; ``commit`` builds the manifest and hands it to the target's
    commit.  Nothing is visible to a reader before that.

    ``old``: the manifest of an existing local store, to append to — new
    partitions land at indices >= its ``npartitions`` under their final
    names, digested in ITS form, and ``commit`` publishes the extended
    manifest with ``generation + 1`` by ONE atomic replace (a crash before
    it leaves orphan part files the old manifest never references, so
    readers and watermarks never see a torn append; a retry overwrites
    them).

    ``shared``: several processes write partitions of one store into one
    temp directory of a fixed name, each through its own writer; one of
    them commits with everybody's counts and digests (``commit``'s
    arguments).  The caller clears a stale directory first
    (``clear_shared_temp``) and brings its own barriers."""

    def __init__(self, path: str, schema: Dict[str, Any],
                 partitioning: Optional[Dict[str, Any]] = None,
                 compression: Optional[str] = None,
                 capacity: Optional[int] = None,
                 old: Optional[Dict[str, Any]] = None,
                 shared: bool = False,
                 unique: Optional[Sequence[str]] = None):
        if compression not in (None, "gzip"):
            raise ValueError(f"unknown compression {compression!r}")
        if old is not None and old.get("unique"):
            # the key was verified over the rows of the write; appended
            # rows would have to be checked against every row there is
            raise StoreKeyError(
                f"{path}: the store declares {old['unique']} unique, and "
                "an append is not verified against the stored rows — "
                "write the store anew (to_store(unique=...))")
        self.unique = list(unique) if unique else None
        self.schema = schema
        self.partitioning = partitioning
        self.compression = compression
        self.capacity = capacity
        self.old = old
        self.counts: List[int] = []
        self.checksums: List[str] = []
        # a manifest carries leaf digests for every partition or for none
        self.leaf_checksums: Optional[List[List[str]]] = []
        self.target = _target(path)
        # the name the target resolves this write's parts by, where it has
        # one to record in the manifest (an s3:// generation prefix)
        self._gen = None if old is not None else self.target.begin(shared)

    def add(self, counts: Sequence[int], segments: List[List[np.ndarray]],
            part_ids: Optional[Sequence[int]] = None) -> None:
        """Put and digest partitions of ``counts[i]`` rows whose bytes are
        ``segments[i]`` (contiguous arrays in file order, cut anywhere), as
        partitions ``part_ids`` — the next free ones by default."""
        if part_ids is None:
            base = (int(self.old["npartitions"]) if self.old else 0) \
                + len(self.counts)
            part_ids = range(base, base + len(segments))
        nbytes = _segments_nbytes(segments)
        with trace.span("store.file_write", "io", bytes=nbytes,
                        files=len(segments)):
            _put_parts(self.target, part_ids, segments, self.compression)
        with trace.span("store.checksum", "io", bytes=nbytes) as csp:
            sums, leaves, ran = part_checksums(self.schema, counts, segments,
                                               self.old)
            csp.set(**ran)
        self.counts += [int(n) for n in counts]
        self.checksums += sums
        if leaves is None:
            self.leaf_checksums = None
        elif self.leaf_checksums is not None:
            self.leaf_checksums += leaves

    def add_chunk(self, n: int, cols: Dict[str, Any],
                  part_id: Optional[int] = None) -> None:
        """``add`` one partition of ``n`` rows from a host chunk's columns
        (name -> array, or (data, lengths) of a string column)."""
        self.add([n], [chunk_segments(self.schema, cols)],
                 None if part_id is None else [part_id])

    def commit(self, counts: Optional[List[int]] = None,
               checksums: Optional[List[str]] = None) -> Dict[str, Any]:
        """Manifest last, then the target's commit; returns the manifest.
        ``counts`` / ``checksums``: every partition's, where other
        processes wrote some of them (``shared``) — one digest a partition
        is all they hand over, so the manifest then carries no leaf
        digests."""
        with trace.span("store.commit", "io"):
            leaves = self.leaf_checksums if checksums is None else None
            counts = self.counts if counts is None else counts
            checksums = self.checksums if checksums is None else checksums
            old = self.old
            if old is None:
                meta = build_meta(self.schema, counts, checksums,
                                  partitioning=self.partitioning,
                                  compression=self.compression,
                                  capacity=self.capacity,
                                  leaf_checksums=leaves,
                                  unique=self.unique)
                if self._gen is not None:
                    meta["generation"] = self._gen
            else:
                base = int(old["npartitions"])
                gen = store_generation(old) + 1
                old_leaves = old.get("leaf_checksums")
                part = old.get("partitioning") or {"kind": "none"}
                meta = build_meta(
                    old["schema"], list(old["counts"]) + counts,
                    list(old.get("checksums") or []) + checksums,
                    # appended rows were not placed: a hash/range claim no
                    # longer holds
                    partitioning=(part if part.get("kind") == "none"
                                  else {"kind": "none"}),
                    compression=self.compression,
                    capacity=max(int(old.get("capacity", 1)), max(counts)),
                    generation=gen,
                    part_generations=list(old.get("part_generations")
                                          or [0] * base)
                    + [gen] * len(counts),
                    leaf_checksums=(old_leaves + leaves
                                    if None not in (old_leaves, leaves)
                                    else None),
                    form=checksum_form(old))
            manifest = json.dumps(meta, indent=1).encode()
            if old is None:
                self.target.commit(manifest)
            else:
                self.target.commit_append(manifest)
        return meta


def fetch_part_segments(pd: PData, schema, counts: np.ndarray):
    """For partition 0, 1, ... in turn ``(segments, moved_bytes, chunks)``:
    the blobs of the partition's ``counts[p]`` valid rows in file order (a
    leaf is one segment a chunk it arrived in), brought off the device by
    ``exec.data.fetch_partitions`` — the ONE fetch of ``write_store`` and
    ``append_store``, whatever the target, and so of the stage spill."""
    leaves: List[Any] = []
    for leaf in part_layout(schema):
        v = pd.batch.columns[leaf.column]
        leaves.append(v if leaf.str_part is None
                      else jax.tree.leaves(v)[leaf.str_part])
    return fetch_partitions(leaves, counts)


def write_store(path: str, pd: PData,
                partitioning: Optional[Dict[str, Any]] = None,
                compression: Optional[str] = None,
                unique: Optional[Sequence[str]] = None) -> int:
    """Persist a PData (ToStore, DryadLinqQueryable.cs:3909) to a local,
    ``s3://`` or ``hdfs://`` path, atomically (``StoreWriter``); returns
    the rows it wrote.

    ``compression="gzip"`` writes level-1 gzip partition files (the
    per-channel compression transform of the reference,
    GzipCompressionChannelTransform.cpp).  Checksums are over the
    UNCOMPRESSED segments, verified on read.

    ``unique``: columns that together are a key of these rows.  Verified
    here, over all partitions, before a byte is written (``check_unique``;
    ``StoreKeyError`` where two rows share the key), then recorded in the
    manifest, where ``sql.Catalog.register_store`` finds it."""
    with trace.span("store.write", "io", partitions=pd.nparts) as sp:
        counts = np.asarray(pd.counts)
        schema = pdata_schema(pd)
        if unique:
            missing = [k for k in unique if k not in schema]
            if missing:
                raise StoreKeyError(f"{path}: unique names {missing}, no "
                                    f"column of {sorted(schema)}")
            check_unique(key_hashes(pd.batch, counts, unique), unique, path)
        writer = StoreWriter(path, schema, partitioning, compression,
                             pd.capacity, unique=unique)
        segments = []
        fetched = fetch_part_segments(pd, schema, counts)
        for p in range(pd.nparts):
            # device -> host: partition 0's span starts the copy of every
            # chunk of every partition, then each span waits for what its
            # partition still misses (exec.data.fetch_partitions)
            with trace.span("store.fetch", "io", partition=p) as fsp:
                segs, moved, chunks = next(fetched)
                segments.append(segs)
                fsp.set(bytes=_segments_nbytes(segments[-1:]),
                        moved_bytes=moved, chunks=chunks)
        sp.set(bytes=_segments_nbytes(segments))
        writer.add(counts.tolist(), segments)
        writer.commit()
        return int(counts.sum())


def append_store(path: str, pd: PData) -> int:
    """Append a PData to an EXISTING local store as a new generation
    (``StoreWriter`` opened on its manifest); returns the committed
    generation number — the growing-store primitive of the
    continuous-query subsystem (dryad_tpu/inc).

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
    counts = np.asarray(pd.counts)
    segments, new_counts = [], []
    for n, (segs, _, _) in zip(counts.tolist(),
                               fetch_part_segments(pd, schema, counts)):
        if n == 0:  # empty shards would bloat the manifest forever
            continue
        segments.append(segs)
        new_counts.append(n)
    if not new_counts:
        return store_generation(meta)
    writer = StoreWriter(path, meta["schema"],
                         compression=meta.get("compression"), old=meta)
    writer.add(new_counts, segments)
    return store_generation(writer.commit())


# ---------------------------------------------------------------------------
# the reader


def verify_checksums(path: str, meta: Dict[str, Any],
                     segments: List[List[np.ndarray]],
                     partitions: Optional[List[int]] = None,
                     leaves: Optional[Sequence[int]] = None
                     ) -> Optional[Dict[str, Any]]:
    """Compare freshly-read partition segments against the recorded
    checksums, in the form the manifest names and in ONE digest call for
    all of them; raise StoreIntegrityError naming the partition (and, where
    the manifest carries leaf digests, the column) on a mismatch.  Returns
    ``part_checksums``' info.  Stores written before format v3 carry no
    checksums and are accepted as-is (None).

    ``leaves``: the segments hold these leaves of the layout only (a read
    of some columns).  Each is compared with its ``leaf_checksums`` entry;
    a manifest without them cannot vouch for a subset and is refused."""
    recorded = meta.get("checksums")
    if not recorded and leaves is None:
        return None
    parts = list(partitions if partitions is not None
                 else range(len(segments)))
    schema = meta["schema"]
    counts = [int(meta["counts"][p]) for p in parts]
    layout = part_layout(schema)
    held = list(range(len(layout))) if leaves is None else list(leaves)
    row_bytes = sum(layout[j].row_bytes for j in held)
    for segs, p, n in zip(segments, parts, counts):
        have, want = _segments_nbytes([segs]), n * row_bytes
        if have != want:
            raise StoreIntegrityError(
                f"partition {p} of {path}: {have} bytes read, the manifest's "
                f"{n} rows are {want} — file truncated or tampered")
    rec_leaves = meta.get("leaf_checksums")
    if leaves is not None and not rec_leaves:
        raise ValueError(f"{path}: its manifest has no leaf digests, so "
                         "some of its columns cannot be verified alone")
    sums, leaf_sums, ran = part_checksums(schema, counts, segments, meta,
                                          leaves)
    for i, p in enumerate(parts):
        bad = next(((j, got, rec_leaves[p][j])
                    for j, got in zip(held, leaf_sums[i])
                    if got != rec_leaves[p][j]), None) \
            if rec_leaves and leaf_sums else None
        # a subset of the leaves has no partition digest to compare
        if not bad and (leaves is not None or sums[i] == recorded[p]):
            continue
        where = (f" (column {layout[bad[0]].column!r}, leaf {bad[0]})"
                 if bad else "")
        got, rec = (sums[i], recorded[p]) if leaves is None else bad[1:]
        raise StoreIntegrityError(
            f"partition {p} of {path}{where}: checksum {got} != "
            f"recorded {rec} — file corrupted or tampered")
    return ran


def read_parts(path: str, meta: Dict[str, Any], part_ids: Sequence[int],
               verify: bool = True,
               columns: Optional[Sequence[str]] = None
               ) -> Tuple[List[List[np.ndarray]], List[Dict[str, Any]]]:
    """The ONE partition read: for the listed partitions, in order,
    ``(segments, columns)`` — the arrays that were read, in file order, and
    the asked-for columns' arrays by name (an array, or (data, lengths) of a
    string column).  Allocate from the layout, fill through the byte
    target, verify every byte read in one digest call before returning.

    ``columns`` (stored column names; None = all): only those columns'
    leaves are allocated, filled — from their byte ranges of the partition
    file, nothing between them touched — and verified, each by its entry
    of the manifest's ``leaf_checksums``.  A store that cannot be read in
    part is read whole, verified whole and its other columns dropped here:
    a ``gzip`` store (ranges of a gzip stream do not decompress alone) and,
    with ``verify`` on, a manifest without ``leaf_checksums`` (docs/
    store_format.md, "Reading some columns").  Naming every column is the
    whole read."""
    target = _target(path, meta)
    schema, compression = meta["schema"], meta.get("compression")
    keep = kept_schema(schema, columns)
    # the layout's leaves this read holds; None = all of them, the whole
    # read.  What the manifest says decides, never a knob
    held = None
    if len(keep) < len(schema) and compression is None \
            and (not verify or meta.get("leaf_checksums")):
        held = [j for j, leaf in enumerate(part_layout(schema))
                if leaf.column in keep]
    row_bytes = schema_row_bytes(schema)
    segments, out = [], []
    with trace.span("store.file_read", "io", files=len(part_ids)) as fsp:
        for p in part_ids:
            n = meta["counts"][p]
            segs, cols = _alloc_part_views(schema, n, held)
            segments.append(segs)
            out.append(cols)
            # a file cut short or grown is named as what it is, not as a
            # failed read (a gzip file's size says nothing): it holds the
            # whole layout's bytes, whichever of them are read
            if verify and compression is None \
                    and isinstance(target, _LocalDir):
                have, want = os.path.getsize(target.part(p)), n * row_bytes
                if have != want:
                    raise StoreIntegrityError(
                        f"partition {p} of {path}: the file holds "
                        f"{have} bytes, the manifest's "
                        f"{meta['counts'][p]} rows are {want} — "
                        "file truncated or tampered")
        if held is None:
            _fill_parts(target, part_ids, segments, compression)
        else:
            layouts = [part_layout(schema, meta["counts"][p])
                       for p in part_ids]
            _fill_ranges(target, part_ids, segments,
                         [[(layout[j].offset, layout[j].nbytes)
                           for j in held] for layout in layouts])
        nbytes = _segments_nbytes(segments)
        fsp.set(bytes=nbytes)
    if verify:
        with trace.span("store.verify", "io", bytes=nbytes) as vsp:
            vsp.set(**(verify_checksums(path, meta, segments,
                                        partitions=list(part_ids),
                                        leaves=held) or {}))
            if held is not None:
                vsp.set(leaves=len(held) * len(part_ids))
    if len(keep) < len(schema):
        out = [{k: cols[k] for k in keep} for cols in out]
    return segments, out


def iter_part_chunks(path: str, meta: Dict[str, Any], p: int,
                     chunk_rows: int,
                     columns: Optional[Sequence[str]] = None):
    """Yield one partition's rows as (columns, n) chunks of at most
    ``chunk_rows`` rows, each fetched by PER-LEAF ranged reads — host
    memory stays O(chunk_rows) even when the partition itself exceeds RAM
    (the channelbufferhdfs.cpp:69-97 block-read pattern applied to the
    columnar part layout: rows [s, e) of a leaf are one contiguous byte
    range, so a chunk is one range a leaf).

    ``has_ranged_read`` stores only; the store's digests cover whole
    leaves and are NOT verifiable on this path.  ``columns``: the stream
    takes those columns' leaves of the layout only."""
    import concurrent.futures

    from dryad_tpu.io.providers import retry_transient

    target = _target(path, meta)
    if not has_ranged_read(path, meta):
        raise IOError(f"{path}: iter_part_chunks streams uncompressed parts "
                      "of a target with ranged reads only")
    cnt = int(meta["counts"][p])
    keep = kept_schema(meta["schema"], columns)
    layout = [leaf for leaf in part_layout(meta["schema"], cnt)
              if leaf.column in keep]

    def fetch(leaf: Leaf, s: int, e: int) -> np.ndarray:
        # route MID-STREAM ranged reads through the provider
        # retry/backoff path whole-partition reads already enjoy: the
        # whole leaf range re-issues from scratch (ranged GETs are
        # idempotent), so one flaky datanode hop — an empty 200, a
        # truncated body, a dropped connection past the per-request
        # retries — costs a backoff, not a multi-hour streamed job
        raw = retry_transient(
            lambda: target.get_range(p, leaf.offset + s * leaf.row_bytes,
                                     (e - s) * leaf.row_bytes),
            what=f"ranged read of {target.what(p)}", retries=2)
        # bytearray copy -> writable array (frombuffer over bytes
        # would hand downstream kernels read-only buffers)
        return np.frombuffer(bytearray(raw), leaf.dtype).reshape(
            (e - s,) + leaf.row_shape)

    # a chunk's per-leaf ranges are independent — fetch them in
    # parallel (each costs a namenode redirect + datanode GET; serial
    # fetches would be latency-bound, per-channel IO thread role)
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, max(len(layout), 1))) as pool:
        for s in range(0, cnt, chunk_rows):
            e = min(s + chunk_rows, cnt)
            arrs = list(pool.map(lambda leaf: fetch(leaf, s, e), layout))
            yield _columns(layout, arrs), e - s


def read_store(path: str, mesh, capacity: Optional[int] = None,
               partitions: Optional[List[int]] = None,
               verify: bool = True,
               columns: Optional[Sequence[str]] = None) -> PData:
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
    DrPartitionFile.cpp:607).

    ``columns`` reads only the named stored columns (``read_parts``: their
    leaves alone are fetched, verified, stacked and put on the device); the
    PData holds them in the manifest's column order.  The ``store.read``
    span says what that saved: ``columns`` of ``columns_stored``, ``bytes``
    read of ``bytes_stored``."""
    meta = store_meta(path)
    part_ids = (list(range(meta["npartitions"])) if partitions is None
                else list(partitions))
    schema = kept_schema(meta["schema"], columns)
    with trace.span("store.read", "io", partitions=len(part_ids),
                    columns=len(schema),
                    columns_stored=len(meta["schema"])) as sp:
        counts = [meta["counts"][p] for p in part_ids]
        nparts = mesh.devices.size
        segments, part_rows = read_parts(path, meta, part_ids, verify,
                                         columns)
        sp.set(bytes=_segments_nbytes(segments),
               bytes_stored=sum(int(n) for n in counts)
               * schema_row_bytes(meta["schema"]))
        del segments    # a store read whole for some columns: the others go

        if len(part_ids) == nparts:
            # verbatim per-partition load: placement-preserving
            cap = capacity or max(int(meta.get("capacity", 0)),
                                  max(counts or [0]), 1)
            return _stack_partitions(schema, part_rows, counts, cap, mesh)

        # partition counts differ: concatenate store partitions then
        # re-block over the mesh (placement-destroying; callers drop
        # partitioning claims)
        concat: Dict[str, Any] = {}
        for k in schema:
            if schema[k]["kind"] in _PAIR_KINDS:
                concat[k] = (np.concatenate([pr[k][0] for pr in part_rows]),
                             np.concatenate([pr[k][1] for pr in part_rows]))
            else:
                concat[k] = np.concatenate([pr[k] for pr in part_rows])

        total = sum(counts)
        base, rem = divmod(total, nparts)
        sizes = [base + (1 if p < rem else 0) for p in range(nparts)]
        cap = capacity or max(1, max(sizes))
        offs = np.cumsum([0] + sizes)
        part_rows = [{k: ((concat[k][0][offs[p]:offs[p + 1]],
                           concat[k][1][offs[p]:offs[p + 1]])
                          if schema[k]["kind"] in _PAIR_KINDS
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
            elif spec["kind"] == "int64":
                words = [np.zeros((nparts, cap), dt)
                         for dt in (np.int32, np.uint32)]
                for p in range(nparts):
                    for w, rows in zip(words, part_rows[p][k]):
                        w[p, : counts[p]] = rows
                cols[k] = Int64Column(*words)
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
