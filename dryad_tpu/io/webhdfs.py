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
* the ``hdfs://`` byte target of io/store.py (``HdfsDir``: names and
  bytes of part-NNNNN.bin + meta.json, committed atomically via HDFS's
  rename — the same temp-dir rename commit the local store uses,
  DrVertex.h:325-351); the format itself is io/store.py's alone.

``hdfs://namenode:port/path`` URIs address the WebHDFS endpoint
``http://namenode:port/webhdfs/v1/path``; io/store.py routes any
``hdfs://`` store path here, io/providers.py registers the scheme for
``ctx.read``.
"""

from __future__ import annotations

import json
import os
import socket
import time
import urllib.error
import urllib.parse
import urllib.request
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["WebHdfsClient", "WebHdfsError", "parse_hdfs_url",
           "hdfs_client", "HdfsDir", "hdfs_part_path",
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


# -- the hdfs:// byte target of the partitioned store (io/store.py) ----------


def hdfs_part_path(path: str, p: int) -> str:
    return path.rstrip("/") + f"/part-{p:05d}.bin"


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


class HdfsDir:
    """Names and bytes of one store in an ``hdfs://`` directory (io/store.py
    ``_target``).  HDFS has an atomic rename, so the commit is the same
    temp-dir rename the local store uses: parts + meta under a temp
    directory (``<path>.tmp-<nonce>``; ``<path>.tmp`` where several
    processes share it), then RENAME onto ``<path>`` — a reader never
    observes a half-written store."""

    ranged = True

    def __init__(self, url: str):
        self.c, path = hdfs_client(url)
        self.path = self.dir = path.rstrip("/")

    def read_meta(self) -> bytes:
        return self.c.read_all(self.path + "/meta.json")

    def clear_stale(self) -> None:
        self.c.delete(self.path + ".tmp", recursive=True)

    def begin(self, shared: bool = False) -> None:
        self.dir = self.path + (".tmp" if shared
                                else ".tmp-" + uuid.uuid4().hex[:12])
        self.c.mkdirs(self.dir)   # idempotent; shared writers may race

    def put(self, p: int, data: bytes) -> None:
        self.c.create(hdfs_part_path(self.dir, p), data)

    def get(self, p: int) -> bytes:
        return self.c.read_all(hdfs_part_path(self.dir, p))

    def fill(self, p: int, segs: List[np.ndarray]) -> None:
        _fill_ranged(self.c, hdfs_part_path(self.dir, p), segs)

    def get_range(self, p: int, off: int, ln: int) -> bytes:
        return _read_exact(self.c, hdfs_part_path(self.dir, p), off, ln)

    def what(self, p: int) -> str:
        return f"hdfs part {hdfs_part_path(self.dir, p)!r}"

    def commit(self, manifest: bytes) -> None:
        self.c.create(self.dir + "/meta.json", manifest)
        self.c.delete(self.path, recursive=True)   # False = nothing to remove
        self.c.rename(self.dir, self.path)


# -- block locality ----------------------------------------------------------


def hdfs_preferred_hosts(url: str, partitions: Sequence[int]
                         ) -> List[str]:
    """Ordered locality hints for the given store partitions: hosts
    holding more of the partitions' block bytes first (the reference's
    weighted affinity lists built from block locations,
    ClusterInterface/Interfaces.cs:98-152; DrHdfsClient.cpp feeds them).
    Empty when the namenode doesn't expose block locations — locality
    degrades to a no-op hint, never an error."""
    import concurrent.futures

    c, path = hdfs_client(url)
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
