"""WebHDFS (``hdfs://``) storage adapter — the hdfs-family member of the
cloud-storage layer (io/s3.py is the object-store member).

Reference parity: the GM-side HDFS client
(GraphManager/filesystem/DrHdfsClient.cpp:1-676) and the vertex-side
block-ranged channel reader (channelbufferhdfs.cpp:69-97) read/write
partitioned datasets against HDFS, and block locations feed the
scheduler's affinity lists (ClusterInterface/Interfaces.cs:98-152).
This module speaks the WebHDFS REST dialect (the namenode's HTTP
gateway; Hadoop's ``webhdfs://`` — served by every stock namenode and
by HttpFS proxies):

* namenode -> datanode 307 redirect protocol (OPEN/CREATE/APPEND send
  data only to the redirected datanode, per the WebHDFS spec);
* ranged reads (``op=OPEN&offset=&length=``) — the block-read pattern
  of channelbufferhdfs.cpp:69-97, so a partition streams through host
  RAM in bounded pieces;
* ``GETFILEBLOCKLOCATIONS`` block->host metadata, surfaced as ordered
  locality hints for the task farm (runtime/farm.py dispatches a task
  to a worker on a host that holds its input blocks);
* bounded exponential-backoff retries on 5xx / connection errors;
* the partitioned-store layout of io/store.py (part-NNNNN.bin +
  meta.json) committed atomically via HDFS's rename (the same temp-dir
  rename commit the local store uses, DrVertex.h:325-351).

``hdfs://namenode:port/path`` URIs address the WebHDFS endpoint
``http://namenode:port/webhdfs/v1/path``; io/store.py routes any
``hdfs://`` store path here, io/providers.py registers the scheme for
``ctx.read``.
"""

from __future__ import annotations

import gzip
import json
import os
import socket
import time
import urllib.error
import urllib.parse
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["WebHdfsClient", "WebHdfsError", "parse_hdfs_url",
           "hdfs_client", "hdfs_store_meta", "hdfs_write_store",
           "hdfs_read_part_views", "hdfs_part_path",
           "hdfs_preferred_hosts", "hdfs_provider"]

# ranged-read piece size: the reference FileServer's 2 MB block
# (HttpServer.cs:631-651); also the HDFS client-side read granularity
_RANGE_BLOCK = 2 << 20
_TIMEOUT_S = 60.0
_MAX_REDIRECTS = 4


class WebHdfsError(IOError):
    """A non-retryable WebHDFS failure (4xx, protocol violation, or
    retries exhausted).  ``status`` carries the HTTP code when one was
    received; the message includes the namenode's RemoteException text
    when the body carries one."""

    def __init__(self, msg: str, status: Optional[int] = None):
        super().__init__(msg)
        self.status = status


def parse_hdfs_url(url: str) -> Tuple[str, str]:
    """hdfs://namenode:port/path -> ("http://namenode:port", "/path")."""
    if not url.startswith("hdfs://"):
        raise ValueError(f"not an hdfs url: {url!r}")
    rest = url[len("hdfs://"):]
    authority, _, path = rest.partition("/")
    if not authority:
        raise ValueError(f"hdfs url has no namenode authority: {url!r}")
    return "http://" + authority, "/" + path


class _NoRedirect(urllib.request.HTTPRedirectHandler):
    """WebHDFS redirects are PROTOCOL, not transparency: the datanode
    Location must be followed manually (data ships only on the second
    hop), so automatic redirect following is disabled."""

    def redirect_request(self, *a, **kw):
        return None


_OPENER = urllib.request.build_opener(_NoRedirect)


def _remote_exception(body: bytes) -> str:
    try:
        exc = json.loads(body)["RemoteException"]
        return f"{exc.get('exception')}: {exc.get('message')}"
    except Exception:
        return body[:200].decode("utf-8", "replace")


class WebHdfsClient:
    """Minimal WebHDFS REST client (stdlib-only, like io/s3.S3Client).

    ``user`` rides as ``user.name`` on every request (HDFS simple auth;
    resolves from HADOOP_USER_NAME when unset).  Kerberos/token auth is
    out of scope — front a gateway for secured clusters.
    """

    def __init__(self, base_url: str, user: Optional[str] = None,
                 timeout_s: float = _TIMEOUT_S, max_retries: int = 3):
        self.base = base_url.rstrip("/")
        self.user = user or os.environ.get("HADOOP_USER_NAME")
        self.timeout_s = timeout_s
        self.max_retries = max_retries

    # -- request plumbing --------------------------------------------------

    def _url(self, path: str, op: str, **params) -> str:
        if not path.startswith("/"):
            path = "/" + path
        q: List[Tuple[str, str]] = [("op", op)]
        if self.user:
            q.append(("user.name", self.user))
        q.extend((k, str(v)) for k, v in params.items() if v is not None)
        return (self.base + "/webhdfs/v1"
                + urllib.parse.quote(path, safe="/")
                + "?" + urllib.parse.urlencode(q))

    def _attempt(self, method: str, url: str, data: Optional[bytes],
                 retries: Optional[int] = None
                 ) -> Tuple[int, bytes, Optional[str]]:
        """One HTTP exchange with retries on 5xx/connection errors;
        returns (status, body, redirect_location).  ``retries``
        overrides the client default (0 for non-idempotent hops)."""
        max_retries = self.max_retries if retries is None else retries
        last: Optional[BaseException] = None
        for attempt in range(max_retries + 1):
            req = urllib.request.Request(url, data=data, method=method)
            if data is not None:
                req.add_header("Content-Type", "application/octet-stream")
            try:
                with _OPENER.open(req, timeout=self.timeout_s) as r:
                    return r.getcode(), r.read(), None
            except urllib.error.HTTPError as e:
                body = e.read()
                loc = e.headers.get("Location")
                if e.code in (301, 302, 303, 307) and loc:
                    return e.code, body, loc
                if e.code >= 500 and attempt < max_retries:
                    last = e
                    time.sleep(min(0.1 * 2 ** attempt, 2.0))
                    continue
                raise WebHdfsError(
                    f"webhdfs {method} {url} failed: HTTP {e.code} "
                    f"({_remote_exception(body)})", status=e.code) from e
            except (urllib.error.URLError, socket.timeout, TimeoutError,
                    ConnectionError) as e:
                if attempt < max_retries:
                    last = e
                    time.sleep(min(0.1 * 2 ** attempt, 2.0))
                    continue
                raise WebHdfsError(
                    f"webhdfs {method} {url} unreachable after "
                    f"{max_retries + 1} attempts: {e}") from e
        raise WebHdfsError(f"webhdfs {method} {url} failed: {last}")

    def _read_op(self, method: str, url: str) -> Tuple[int, bytes]:
        """Body-less op, following the namenode->datanode redirect."""
        for _hop in range(_MAX_REDIRECTS):
            status, body, loc = self._attempt(method, url, None)
            if loc is None:
                return status, body
            url = loc
        raise WebHdfsError(f"webhdfs {method}: too many redirects at {url}")

    def _data_op(self, method: str, url: str, data: bytes,
                 data_retries: Optional[int] = None) -> Tuple[int, bytes]:
        """Two-step write: the namenode request carries NO body and must
        307-redirect to a datanode; the data ships only there (WebHDFS
        CREATE/APPEND protocol).  ``data_retries`` bounds retries of the
        DATA hop only (0 for non-idempotent ops: a lost reply after an
        applied APPEND must not resend the bytes)."""
        status, body, loc = self._attempt(method, url, None)
        if loc is None:
            raise WebHdfsError(
                f"webhdfs {method} {url}: namenode did not redirect to a "
                f"datanode (HTTP {status}); data was NOT written",
                status=status)
        status, body, loc = self._attempt(method, loc, data,
                                          retries=data_retries)
        if loc is not None:
            raise WebHdfsError(
                f"webhdfs {method}: datanode redirected again ({loc})")
        return status, body

    def _json(self, method: str, path: str, op: str, **params
              ) -> Dict[str, Any]:
        _status, body = self._read_op(method, self._url(path, op, **params))
        return json.loads(body) if body.strip() else {}

    # -- filesystem ops ----------------------------------------------------

    def status(self, path: str) -> Dict[str, Any]:
        """GETFILESTATUS -> FileStatus dict (length, type, ...)."""
        return self._json("GET", path, "GETFILESTATUS")["FileStatus"]

    def list_status(self, path: str) -> List[Dict[str, Any]]:
        """LISTSTATUS -> child FileStatus list (pathSuffix per entry)."""
        return (self._json("GET", path, "LISTSTATUS")
                ["FileStatuses"]["FileStatus"])

    def exists(self, path: str) -> bool:
        try:
            self.status(path)
            return True
        except WebHdfsError as e:
            if e.status == 404:
                return False
            raise

    def mkdirs(self, path: str) -> bool:
        return bool(self._json("PUT", path, "MKDIRS").get("boolean"))

    def delete(self, path: str, recursive: bool = False) -> bool:
        return bool(self._json("DELETE", path, "DELETE",
                               recursive=str(bool(recursive)).lower()
                               ).get("boolean"))

    def rename(self, src: str, dst: str) -> None:
        if not self._json("PUT", src, "RENAME",
                          destination=dst).get("boolean"):
            raise WebHdfsError(f"webhdfs rename {src!r} -> {dst!r} refused")

    def open(self, path: str, offset: int = 0,
             length: Optional[int] = None) -> bytes:
        """Ranged read (op=OPEN&offset=&length=) via datanode redirect.
        Every ranged read is one io span (bytes + latency) — the
        channel-level visibility Artemis mines from the Calypso stream."""
        from dryad_tpu.obs import trace
        with trace.span("hdfs.open", "io", path=path,
                        offset=offset) as sp:
            _status, body = self._read_op(
                "GET", self._url(path, "OPEN", offset=offset,
                                 length=length))
            sp.set(bytes=len(body))
        return body

    def read_all(self, path: str, block: int = _RANGE_BLOCK) -> bytes:
        """Whole file via bounded ranged reads (channelbufferhdfs.cpp
        block-read role) — never one unbounded GET."""
        size = int(self.status(path)["length"])
        chunks: List[bytes] = []
        off = 0
        while off < size:
            piece = self.open(path, offset=off,
                              length=min(block, size - off))
            if not piece:
                raise WebHdfsError(
                    f"webhdfs read of {path!r} truncated at {off}/{size}")
            chunks.append(piece)
            off += len(piece)
        return b"".join(chunks)

    def create(self, path: str, data: bytes, overwrite: bool = True
               ) -> None:
        from dryad_tpu.obs import trace
        with trace.span("hdfs.create", "io", path=path, bytes=len(data)):
            self._data_op("PUT", self._url(
                path, "CREATE", overwrite=str(bool(overwrite)).lower()),
                data)

    def append(self, path: str, data: bytes) -> None:
        """APPEND is NOT idempotent — the data hop never retries (a
        reply lost after the datanode applied the append would
        otherwise duplicate the bytes); callers own at-least-once
        semantics if they retry around a WebHdfsError."""
        self._data_op("POST", self._url(path, "APPEND"), data,
                      data_retries=0)

    def block_locations(self, path: str, offset: int = 0,
                        length: Optional[int] = None
                        ) -> List[Dict[str, Any]]:
        """GETFILEBLOCKLOCATIONS -> [{"offset", "length", "hosts"}, ...].

        Namenodes predating the op (or HttpFS proxies without it) return
        4xx — surfaced as an EMPTY list, because locality is a hint: the
        farm's dispatch must keep working without it."""
        try:
            res = self._json("GET", path, "GETFILEBLOCKLOCATIONS",
                             offset=offset, length=length)
        except WebHdfsError as e:
            if e.status is not None and 400 <= e.status < 500:
                return []
            raise
        blocks = res.get("BlockLocations", {}).get("BlockLocation", [])
        return [{"offset": int(b.get("offset", 0)),
                 "length": int(b.get("length", 0)),
                 "hosts": list(b.get("hosts", []))} for b in blocks]


# -- per-namenode client cache ----------------------------------------------

_CLIENTS: Dict[str, WebHdfsClient] = {}


def hdfs_client(url: str) -> Tuple[WebHdfsClient, str]:
    """(process-cached client for the url's namenode, hdfs path)."""
    base, path = parse_hdfs_url(url)
    c = _CLIENTS.get(base)
    if c is None:
        c = _CLIENTS[base] = WebHdfsClient(base)
    return c, path


def _resolve(url: str, client: Optional[WebHdfsClient]
             ) -> Tuple[WebHdfsClient, str]:
    """(client, path) — an explicitly-passed client wins over the
    per-namenode cache."""
    if client is not None:
        return client, parse_hdfs_url(url)[1]
    return hdfs_client(url)


# -- partitioned-store layout (io/store.py format on HDFS) -------------------


def hdfs_part_path(path: str, p: int) -> str:
    return path.rstrip("/") + f"/part-{p:05d}.bin"


def hdfs_store_meta(url: str, client: Optional[WebHdfsClient] = None
                    ) -> Dict[str, Any]:
    c, path = _resolve(url, client)
    return json.loads(c.read_all(path.rstrip("/") + "/meta.json"))


def hdfs_write_store(url: str, pd, partitioning=None, compression=None,
                     client: Optional[WebHdfsClient] = None) -> None:
    """write_store for hdfs:// paths.  HDFS has an atomic rename, so the
    commit is the same temp-dir rename the local store uses (parts +
    meta under ``<path>.tmp-<nonce>``, then RENAME onto ``<path>``) —
    a reader never observes a half-written store."""
    import uuid

    from dryad_tpu.io.store import (build_meta, fetch_part_segments,
                                    part_checksums, pdata_schema,
                                    segments_blob)

    if compression not in (None, "gzip"):
        raise ValueError(f"unknown compression {compression!r}")
    c, path = _resolve(url, client)
    path = path.rstrip("/")
    counts = np.asarray(pd.counts)
    schema = pdata_schema(pd)
    tmp = path + ".tmp-" + uuid.uuid4().hex[:12]
    c.mkdirs(tmp)
    segments = []
    for p, (segs, _, _) in enumerate(
            fetch_part_segments(pd, schema, counts)):
        segments.append(segs)
        c.create(hdfs_part_path(tmp, p), segments_blob(segs, compression))
    # digests of the UNCOMPRESSED segments, every partition in one call
    checksums, leaf_checksums, _ = part_checksums(schema, counts, segments)
    meta = build_meta(schema, counts.tolist(), checksums,
                      partitioning=partitioning, compression=compression,
                      capacity=pd.capacity, leaf_checksums=leaf_checksums)
    c.create(tmp + "/meta.json", json.dumps(meta, indent=1).encode())
    c.delete(path, recursive=True)   # False = nothing to remove
    c.rename(tmp, path)


def _fill_ranged(c: WebHdfsClient, path: str, segs: List[np.ndarray],
                 block: int = _RANGE_BLOCK) -> None:
    """Fill preallocated column segments with a part file's bytes via
    bounded ranged reads — the partition never exists as one host blob
    (the streamed-ranged-read contract of channelbufferhdfs.cpp:69-97)."""
    # memoryview.cast rejects zero-sized shapes; empty segments (a
    # 0-row partition) need no bytes anyway
    views = [memoryview(s).cast("B") for s in segs if s.nbytes]
    total = sum(len(v) for v in views)
    seg_i = 0
    seg_off = 0
    off = 0
    while off < total:
        piece = c.open(path, offset=off, length=min(block, total - off))
        if not piece:
            raise WebHdfsError(
                f"webhdfs read of {path!r} truncated at {off}/{total}")
        pv = memoryview(piece)
        while len(pv):
            room = len(views[seg_i]) - seg_off
            take = min(room, len(pv))
            views[seg_i][seg_off:seg_off + take] = pv[:take]
            seg_off += take
            pv = pv[take:]
            if seg_off == len(views[seg_i]):
                seg_i += 1
                seg_off = 0
        off += len(piece)


def hdfs_read_part_views(url: str, meta: Dict[str, Any], p: int,
                         client: Optional[WebHdfsClient] = None):
    """(segments, column views) for one partition — the read_store /
    ChunkSource building block (io/s3_store.s3_read_part_views shape).
    Uncompressed parts fill their segments directly from ranged reads;
    gzip parts are fetched whole (ranges of a gzip stream don't
    decompress independently)."""
    from dryad_tpu.io.store import _alloc_part_views

    c, path = _resolve(url, client)
    segs, cols = _alloc_part_views(meta["schema"], meta["counts"][p])
    part = hdfs_part_path(path, p)
    if meta.get("compression") == "gzip":
        from dryad_tpu.io.store import fill_segments
        fill_segments(segs, gzip.decompress(c.read_all(part)),
                      f"hdfs part {part!r}")
    else:
        _fill_ranged(c, part, segs)
    return segs, cols


def _write_chunks_hdfs(url: str, chunks, schema: Dict[str, Any],
                       partitioning=None, compression=None,
                       client: Optional[WebHdfsClient] = None
                       ) -> Dict[str, Any]:
    """ooc.write_chunks_to_store for hdfs:// targets: one part file per
    chunk uploaded as it is drained (O(chunk_rows) host memory), meta
    written last, temp-dir rename commit."""
    import uuid

    from dryad_tpu.io.store import (build_meta, chunk_segments,
                                    part_checksums, segments_blob)

    if compression not in (None, "gzip"):
        raise ValueError(f"unknown compression {compression!r}")
    c, path = _resolve(url, client)
    path = path.rstrip("/")
    tmp = path + ".tmp-" + uuid.uuid4().hex[:12]
    c.mkdirs(tmp)
    counts: List[int] = []
    checksums: List[str] = []
    leaf_checksums: List[List[str]] = []
    p = 0
    for chunk in chunks:
        segs = chunk_segments(schema, chunk.cols)
        sums, leaves, _ = part_checksums(schema, [chunk.n], [segs])
        checksums += sums
        leaf_checksums += leaves
        c.create(hdfs_part_path(tmp, p), segments_blob(segs, compression))
        counts.append(chunk.n)
        p += 1
    meta = build_meta(schema, counts, checksums,
                      partitioning=partitioning, compression=compression,
                      leaf_checksums=leaf_checksums)
    c.create(tmp + "/meta.json", json.dumps(meta, indent=1).encode())
    c.delete(path, recursive=True)
    c.rename(tmp, path)
    return meta


def _read_exact(c: WebHdfsClient, path: str, off: int, ln: int,
                block: int = _RANGE_BLOCK) -> bytes:
    """Exactly ``ln`` bytes at ``off`` via bounded ranged reads (servers
    and proxies may clamp a requested length)."""
    out: List[bytes] = []
    while ln > 0:
        piece = c.open(path, offset=off, length=min(block, ln))
        if not piece:
            raise WebHdfsError(
                f"webhdfs read of {path!r} truncated at offset {off}")
        out.append(piece)
        off += len(piece)
        ln -= len(piece)
    return b"".join(out)


def hdfs_part_chunks(url: str, meta: Dict[str, Any], p: int,
                     chunk_rows: int,
                     client: Optional[WebHdfsClient] = None):
    """Yield one partition's rows as (column dict, n) chunks of at most
    ``chunk_rows`` rows, each fetched by PER-SEGMENT ranged reads — host
    memory stays O(chunk_rows) even when the partition itself exceeds
    RAM (the channelbufferhdfs.cpp:69-97 block-read pattern applied to
    the columnar part layout: rows [s, e) of column segment j live at
    one contiguous byte range, so a chunk is k ranges, k = segments).

    Uncompressed parts only (a gzip stream has no independently
    decompressible ranges — callers fall back to whole-part reads); the
    store's per-partition checksums cover whole segments and are NOT
    verifiable on this path."""
    if meta.get("compression"):
        raise WebHdfsError(
            "hdfs_part_chunks streams uncompressed parts only")
    c, path = _resolve(url, client)
    schema = meta["schema"]
    cnt = int(meta["counts"][p])
    part = hdfs_part_path(path, p)
    # segment layout in file order: sorted columns, strings as
    # (data, lengths) — must match io/store.fetch_part_segments
    layout: List[Tuple[str, Optional[int], Any, Tuple[int, ...], int, int]] \
        = []   # (col, str_part, dtype, row_shape, row_bytes, base_off)
    base = 0
    for k in sorted(schema):
        spec = schema[k]
        if spec["kind"] == "str":
            for part_i, (dt, tail) in enumerate(
                    ((np.dtype(np.uint8), (int(spec["max_len"]),)),
                     (np.dtype(np.int32), ()))):
                rb = dt.itemsize
                for d in tail:
                    rb *= d
                layout.append((k, part_i, dt, tail, rb, base))
                base += cnt * rb
        else:
            dt = np.dtype(spec["dtype"])
            tail = tuple(int(d) for d in spec.get("shape", ()))
            rb = dt.itemsize
            for d in tail:
                rb *= d
            layout.append((k, None, dt, tail, rb, base))
            base += cnt * rb
    import concurrent.futures

    from dryad_tpu.io.providers import retry_transient

    def fetch(args, s, e):
        _k, _sp, dt, tail, rb, base_off = args
        # route MID-STREAM ranged reads through the provider
        # retry/backoff path whole-partition reads already enjoy: the
        # whole segment range re-issues from scratch (ranged GETs are
        # idempotent), so one flaky datanode hop — an empty 200, a
        # truncated body, a dropped connection past the per-request
        # retries — costs a backoff, not a multi-hour streamed job
        raw = retry_transient(
            lambda: _read_exact(c, part, base_off + s * rb,
                                (e - s) * rb),
            what=f"hdfs ranged read {part!r}", retries=2)
        # bytearray copy -> writable array (frombuffer over bytes
        # would hand downstream kernels read-only buffers)
        return np.frombuffer(bytearray(raw), dt).reshape((e - s,) + tail)

    # a chunk's per-segment ranges are independent — fetch them in
    # parallel (each costs a namenode redirect + datanode GET; serial
    # fetches would be latency-bound, per-channel IO thread role)
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, max(len(layout), 1))) as pool:
        for s in range(0, cnt, chunk_rows):
            e = min(s + chunk_rows, cnt)
            arrs = list(pool.map(lambda a: fetch(a, s, e), layout))
            cols: Dict[str, Any] = {}
            str_parts: Dict[str, Dict[int, np.ndarray]] = {}
            for (k, str_part, *_rest), arr in zip(layout, arrs):
                if str_part is None:
                    cols[k] = arr
                else:
                    str_parts.setdefault(k, {})[str_part] = arr
            for k, parts in str_parts.items():
                cols[k] = (parts[0], parts[1])
            yield cols, e - s


# -- block locality ----------------------------------------------------------


def hdfs_preferred_hosts(url: str, partitions: Sequence[int],
                         client: Optional[WebHdfsClient] = None
                         ) -> List[str]:
    """Ordered locality hints for the given store partitions: hosts
    holding more of the partitions' block bytes first (the reference's
    weighted affinity lists built from block locations,
    ClusterInterface/Interfaces.cs:98-152; DrHdfsClient.cpp feeds them).
    Empty when the namenode doesn't expose block locations — locality
    degrades to a no-op hint, never an error."""
    import concurrent.futures

    c, path = _resolve(url, client)
    parts = list(partitions)
    # one namenode round trip per partition — run them concurrently so
    # building a big store's farm specs isn't serialized on RTTs
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(8, max(len(parts), 1))) as pool:
        per_part = list(pool.map(
            lambda p: c.block_locations(hdfs_part_path(path, p)), parts))
    weight: Dict[str, int] = {}
    for blocks in per_part:
        for bl in blocks:
            for h in bl["hosts"]:
                weight[h] = weight.get(h, 0) + max(int(bl["length"]), 1)
    return [h for h, _w in sorted(weight.items(),
                                  key=lambda kv: (-kv[1], kv[0]))]


# -- text data provider (ctx.read("hdfs://...")) -----------------------------


def hdfs_provider(ctx, rest: str, column: str = "line",
                  max_line_len: Optional[int] = None):
    """io.providers entry: every FILE under a directory path is a text
    partition (one record per line, DrPartitionFile.cpp:607 enumeration
    role); a file path is a single partition.  Bodies arrive via bounded
    ranged reads, partitions fetched in parallel (per-channel IO thread
    role, the shared remote-provider tail)."""
    from dryad_tpu.io.providers import text_dataset_from_fetches

    url = "hdfs://" + rest
    c, path = hdfs_client(url)
    path = path.rstrip("/") or "/"
    st = c.status(path)
    if st.get("type") == "DIRECTORY":
        names = sorted(e["pathSuffix"] for e in c.list_status(path)
                       if e.get("type") == "FILE")
        if not names:
            raise FileNotFoundError(f"hdfs directory {url!r} has no files")
        base = "" if path == "/" else path   # no "//f" under the root
        paths = [base + "/" + n for n in names]
    else:
        paths = [path]
    return text_dataset_from_fetches(
        ctx, [lambda p=p: c.read_all(p) for p in paths],
        column, max_line_len)
