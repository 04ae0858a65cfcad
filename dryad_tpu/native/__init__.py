"""ctypes bindings for the native IO engine (native/dryad_io.cpp).

Builds on first use, and again whenever ``dryad_io.cpp`` is newer than the
library (g++ via make), and degrades to pure-Python forms of the same
functions only when no library can be built at all — `available()` reports
which path is active.  pybind11 is not in this environment, so the binding layer is
ctypes over a plain C ABI.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO, "native")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False
_error: Optional[Exception] = None

_P, _I64, _I32, _U64 = (ctypes.c_void_p, ctypes.c_int64, ctypes.c_int32,
                        ctypes.c_uint64)
# every entry point of native/dryad_io.cpp this module calls
_SIGNATURES = {
    "dryad_pack_lines": (_I64, [_P, _I64, _I64, _P, _P, _I64]),
    "dryad_pack_bytes": (_I64, [ctypes.POINTER(_P), _P, _I64, _I64, _P, _P,
                                _I64]),
    "dryad_file_jobs": (_I64, [ctypes.POINTER(ctypes.c_char_p), _I64,
                               ctypes.POINTER(_P), _P, _P, _I32, _I32]),
    "dryad_read_ranges": (_I64, [ctypes.POINTER(ctypes.c_char_p), _I64,
                                 ctypes.POINTER(_P), _P, _P, _P, _I32]),
    "dryad_compact_rows": (_I64, [_P, _P, _I64, _I64, _P, _P]),
    "dryad_fingerprint_seed": (_U64, [_P, _I64, _U64]),
    "dryad_digest_parts": (_I64, [ctypes.POINTER(_P), _P, _P, _P, _P, _I64,
                                  _I64, _P, _P, _P]),
}


def _open_library(native_dir: str) -> Optional[ctypes.CDLL]:
    """Build (when ``dryad_io.cpp`` is newer than the library, or the
    library is absent: a ``make`` with nothing to do costs milliseconds)
    and open ``libdryad_io.so`` of ``native_dir``.  None only when there
    is no library and none can be built (no toolchain): the numpy forms
    then compute the same functions.  A library that is there but lacks an
    entry point is an error, never a silent fallback — the byte-at-a-time
    Python chain takes hours where the native one takes a second."""
    so = os.path.join(native_dir, "libdryad_io.so")
    try:
        subprocess.run(["make", "-C", native_dir, "-s"],
                       check=True, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError):
        pass            # no make, no compiler: whatever library is there
    if not os.path.exists(so):
        return None
    lib = ctypes.CDLL(so)
    missing = [n for n in _SIGNATURES if not hasattr(lib, n)]
    if missing:
        raise RuntimeError(
            f"{so} lacks {', '.join(missing)}: it is older than "
            f"dryad_io.cpp and could not be rebuilt (make -C {native_dir})")
    for name, (restype, argtypes) in _SIGNATURES.items():
        fn = getattr(lib, name)
        fn.restype, fn.argtypes = restype, argtypes
    return lib


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried, _error
    with _lock:
        if not _tried:
            _tried = True
            try:
                _lib = _open_library(_NATIVE_DIR)
            except Exception as e:      # raised again on every later call
                _error = e
        if _error is not None:
            raise _error
        return _lib


def available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# record packing


def pack_lines(buf: bytes, max_len: int,
               capacity: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Split a newline-delimited buffer into (data [n, max_len] u8,
    lengths [n] i32).  Native when built; numpy fallback otherwise."""
    lib = _load()
    if lib is not None:
        cap = capacity or (buf.count(b"\n") + 2)
        data = np.zeros((cap, max_len), np.uint8)
        lens = np.zeros((cap,), np.int32)
        src = np.frombuffer(buf, np.uint8)
        n = lib.dryad_pack_lines(
            src.ctypes.data_as(ctypes.c_void_p), len(buf), max_len,
            data.ctypes.data_as(ctypes.c_void_p),
            lens.ctypes.data_as(ctypes.c_void_p), cap)
        if n < 0:
            raise ValueError("pack_lines capacity exceeded")
        return data[:n], lens[:n]
    # fallback mirrors dryad_pack_lines exactly: split ONLY on b"\n"
    # (bytes.splitlines also splits on \x0b, \x0c, \x1c-\x1e, lone \r —
    # which would make ingest differ from the native path), trim a
    # trailing \r (CRLF), drop only the final empty piece.
    lines = buf.split(b"\n")
    if lines and lines[-1] == b"":
        lines.pop()
    lines = [l[:-1] if l.endswith(b"\r") else l for l in lines]
    n = len(lines)
    data = np.zeros((n, max_len), np.uint8)
    lens = np.zeros((n,), np.int32)
    for i, l in enumerate(lines):
        l = l[:max_len]
        data[i, : len(l)] = np.frombuffer(l, np.uint8)
        lens[i] = len(l)
    return data, lens


def pack_bytes_list(items: Sequence[bytes], max_len: int, capacity: int
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Pack a list of bytes into padded (data [capacity, max_len], lens)."""
    n = len(items)
    if n > capacity:
        raise ValueError(f"{n} items > capacity {capacity}")
    data = np.zeros((capacity, max_len), np.uint8)
    lens = np.zeros((capacity,), np.int32)
    lib = _load()
    if lib is not None and n > 0:
        ptrs = (ctypes.c_void_p * n)()
        lens64 = np.empty((n,), np.int64)
        # keep refs alive
        bufs = [i if isinstance(i, bytes) else bytes(i) for i in items]
        for i, b in enumerate(bufs):
            ptrs[i] = ctypes.cast(ctypes.c_char_p(b), ctypes.c_void_p)
            lens64[i] = len(b)
        rc = lib.dryad_pack_bytes(
            ptrs, lens64.ctypes.data_as(ctypes.c_void_p), n, max_len,
            data.ctypes.data_as(ctypes.c_void_p),
            lens.ctypes.data_as(ctypes.c_void_p), capacity)
        if rc < 0:
            raise ValueError("pack_bytes capacity exceeded")
        return data, lens
    for i, b in enumerate(items):
        b = (b if isinstance(b, bytes) else bytes(b))[:max_len]
        data[i, : len(b)] = np.frombuffer(b, np.uint8)
        lens[i] = len(b)
    return data, lens


# ---------------------------------------------------------------------------
# parallel scatter-gather file IO


def _file_jobs(paths: List[str], segments: List[List[np.ndarray]],
               write: bool, nthreads: int = 8, compress: bool = False,
               offsets: Optional[List[List[int]]] = None) -> None:
    n = len(paths)
    if n == 0:
        return
    if offsets is not None and (write or compress):
        raise ValueError("file offsets go with a plain read only (ranges "
                         "of a gzip stream do not decompress alone)")
    lib = _load()
    if lib is None:
        import gzip as _gz

        opener = (lambda p, m: _gz.open(p, m, compresslevel=1)) \
            if compress else open
        for i, (p, segs) in enumerate(zip(paths, segments)):
            if write:
                with opener(p, "wb") as f:
                    for s in segs:
                        f.write(memoryview(np.ascontiguousarray(s)).cast("B"))
            else:
                with opener(p, "rb") as f:
                    for j, s in enumerate(segs):
                        if not s.nbytes:    # cast refuses an empty shape
                            continue
                        mv = memoryview(s).cast("B")
                        if offsets is not None:
                            f.seek(offsets[i][j])
                        if compress:
                            mv[:] = f.read(mv.nbytes)
                        elif f.readinto(mv) != mv.nbytes:
                            raise IOError(f"file job failed: {p} ends "
                                          "inside a segment")
        return
    flat_ptrs, flat_lens, bounds = [], [], [0]
    keep = []
    for segs in segments:
        for s in segs:
            s = np.ascontiguousarray(s)
            keep.append(s)
            flat_ptrs.append(s.ctypes.data)
            flat_lens.append(s.nbytes)
        bounds.append(len(flat_ptrs))
    c_paths = (ctypes.c_char_p * n)(*[p.encode() for p in paths])
    nseg = len(flat_ptrs)
    c_ptrs = (ctypes.c_void_p * nseg)(*flat_ptrs)
    lens_arr = np.asarray(flat_lens, np.int64)
    offs_arr = np.asarray(bounds, np.int64)
    if offsets is not None:
        at_arr = np.asarray([o for offs in offsets for o in offs], np.int64)
        if at_arr.size != nseg:
            raise ValueError(f"{at_arr.size} file offsets for {nseg} "
                             "segments")
        rc = lib.dryad_read_ranges(
            c_paths, n, c_ptrs, lens_arr.ctypes.data_as(ctypes.c_void_p),
            at_arr.ctypes.data_as(ctypes.c_void_p),
            offs_arr.ctypes.data_as(ctypes.c_void_p), nthreads)
    else:
        mode = (1 if write else 0) + (2 if compress else 0)
        rc = lib.dryad_file_jobs(
            c_paths, n, c_ptrs, lens_arr.ctypes.data_as(ctypes.c_void_p),
            offs_arr.ctypes.data_as(ctypes.c_void_p), mode, nthreads)
    if rc != 0:
        raise IOError(f"native file job failed: {paths[int(rc) - 1]}")


def write_files(paths: List[str], segments: List[List[np.ndarray]],
                nthreads: int = 8, compress: bool = False) -> None:
    _file_jobs(paths, segments, write=True, nthreads=nthreads,
               compress=compress)


def read_files(paths: List[str], segments: List[List[np.ndarray]],
               nthreads: int = 8, compress: bool = False,
               offsets: Optional[List[List[int]]] = None) -> None:
    """Read each file's bytes into the given (preallocated, writable)
    arrays: contiguously from the file's first byte, or — the ranged form,
    plain files only — ``segments[i][j]`` from file offset
    ``offsets[i][j]``, nothing between two ranges touched.  One job a file
    either way."""
    _file_jobs(paths, segments, write=False, nthreads=nthreads,
               compress=compress, offsets=offsets)


def compact_rows(data: np.ndarray, lens: np.ndarray
                 ) -> Tuple[bytes, np.ndarray]:
    """Compact a padded [n, max_len] u8 matrix into (packed bytes,
    offsets[n+1] i64): row i is packed[offs[i]:offs[i+1]].  Native single
    pass when built; numpy mask-gather fallback.  The egress counterpart of
    pack_bytes_list — collect()'s string columns avoid copying padding."""
    n, L = data.shape
    lens = np.ascontiguousarray(lens[:n], np.int32)
    data = np.ascontiguousarray(data)
    lib = _load()
    if lib is not None:
        out = np.empty(int(np.clip(lens, 0, L).sum()), np.uint8)
        offs = np.empty(n + 1, np.int64)
        lib.dryad_compact_rows(
            data.ctypes.data_as(ctypes.c_void_p),
            lens.ctypes.data_as(ctypes.c_void_p), n, L,
            out.ctypes.data_as(ctypes.c_void_p),
            offs.ctypes.data_as(ctypes.c_void_p))
        return out.tobytes(), offs
    cl = np.clip(lens, 0, L)
    mask = np.arange(L)[None, :] < cl[:, None]
    packed = data[mask].tobytes()
    offs = np.concatenate([[0], np.cumsum(cl, dtype=np.int64)])
    return packed, offs


def unpack_rows(data: np.ndarray, lens: np.ndarray) -> List[bytes]:
    """Padded byte matrix -> list of per-row bytes (native compaction +
    zero-padding-free slicing)."""
    packed, offs = compact_rows(data, lens)
    return [packed[offs[i]: offs[i + 1]] for i in range(data.shape[0])]


_FNV_BASIS = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3


def _fnv_py(data: bytes, seed: int = _FNV_BASIS) -> int:
    h = seed
    for b in data:
        h = ((h ^ b) * _FNV_PRIME) & 0xFFFFFFFFFFFFFFFF
    return h


def checksum_segments(segments: Sequence[np.ndarray]) -> int:
    """The store's digest in its first form (manifest ``fnv64``): ONE
    64-bit FNV-1a chained over a partition's segment list, byte i waiting
    for byte i - 1.  Kept for the stores that were written with it; the
    Python fallback computes the SAME function (a fallback must never
    change a digest that any environment has to be able to verify)."""
    lib = _load()
    h = _FNV_BASIS
    for s in segments:
        s = np.ascontiguousarray(s)
        if lib is None:
            h = _fnv_py(s.tobytes(), h)
        else:
            h = int(lib.dryad_fingerprint_seed(s.ctypes.data, s.nbytes, h))
    return h


def _fnv_words(words: Sequence[int]) -> int:
    """FNV-1a over 64-bit digests as 8-byte little-endian words."""
    return _fnv_py(np.asarray(words, "<u8").tobytes())


def _digest_parts_np(parts: Sequence[Sequence[np.ndarray]],
                     leaf_nbytes: Sequence[Sequence[int]], block: int
                     ) -> Tuple[List[int], List[List[int]], Dict[str, int]]:
    """``digest_parts`` without the library: every block of every leaf of
    every partition side by side in one padded [blocks, block] matrix, one
    numpy step a byte position (the blocks are independent, so the step is
    a vector one)."""
    rows: List[np.ndarray] = []         # one block each
    shape: List[List[int]] = []         # blocks a leaf, a partition
    for segs, sizes in zip(parts, leaf_nbytes):
        flat = np.concatenate(
            [np.ascontiguousarray(s).reshape(-1).view(np.uint8)
             for s in segs] + [np.empty(0, np.uint8)])
        off, counts = 0, []
        for n in sizes:
            leaf = flat[off:off + n]
            rows.extend(leaf[i:i + block] for i in range(0, n, block))
            counts.append(-(-n // block))
            off += n
        shape.append(counts)
    lens = np.asarray([r.size for r in rows], np.int64)
    padded = np.zeros((len(rows), int(lens.max(initial=0))), np.uint8)
    for i, r in enumerate(rows):
        padded[i, :r.size] = r
    h = np.full(len(rows), _FNV_BASIS, np.uint64)
    prime = np.uint64(_FNV_PRIME)
    for i in range(padded.shape[1]):
        live = lens > i
        h[live] = (h[live] ^ padded[live, i]) * prime
    digests, at = h.tolist(), 0
    leaf_out: List[List[int]] = []
    for counts in shape:
        leaves = []
        for c in counts:
            leaves.append(_fnv_words(digests[at:at + c]))
            at += c
        leaf_out.append(leaves)
    return ([_fnv_words(l) for l in leaf_out], leaf_out,
            {"blocks": len(rows), "threads": 1})


def digest_parts(parts: Sequence[Sequence[np.ndarray]],
                 leaf_nbytes: Sequence[Sequence[int]], block: int
                 ) -> Tuple[List[int], List[List[int]], Dict[str, int]]:
    """The store's digest as independent blocks (manifest
    ``fnv64-blocks``), for every partition of a read or a write in ONE
    native call: ``(partition digests, leaf digests a partition,
    {"blocks", "threads"})``.

    ``parts[p]`` are partition p's bytes as contiguous arrays cut
    anywhere — the chunks a column came off the device in, an array a
    column, one blob — and ``leaf_nbytes[p]`` the byte length of each of
    its leaves in file order.  A leaf is cut into blocks of ``block``
    bytes (the last one short), each digested byte-wise by FNV-1a from
    the basis; a leaf's digest is FNV-1a over its block digests as
    8-byte little-endian words, a partition's the same over its leaf
    digests.  The blocks run several in lockstep a worker on a pool sized
    from the cores the process may use (native/dryad_io.cpp); a few
    blocks start no thread.  The numpy form computes the same function."""
    if block < 1:
        raise ValueError(f"digest block of {block} bytes")
    keep = [[np.ascontiguousarray(s) for s in segs] for segs in parts]
    for p, (segs, sizes) in enumerate(zip(keep, leaf_nbytes)):
        have, want = sum(s.nbytes for s in segs), sum(sizes)
        if have != want:
            raise ValueError(f"partition {p}: {have} bytes handed over, "
                             f"its leaves hold {want}")
    lib = _load()
    if lib is None:
        return _digest_parts_np(keep, leaf_nbytes, block)
    flat = [s for segs in keep for s in segs]
    seg_ptrs = (ctypes.c_void_p * len(flat))(*[s.ctypes.data for s in flat])
    seg_lens = np.asarray([s.nbytes for s in flat], np.int64)
    seg_offs = np.cumsum([0] + [len(segs) for segs in keep], dtype=np.int64)
    leaf_lens = np.asarray([n for sizes in leaf_nbytes for n in sizes],
                           np.int64)
    leaf_offs = np.cumsum([0] + [len(sizes) for sizes in leaf_nbytes],
                          dtype=np.int64)
    leaf_out = np.empty(leaf_lens.size, np.uint64)
    part_out = np.empty(len(keep), np.uint64)
    stats = np.zeros(2, np.int64)
    rc = lib.dryad_digest_parts(
        seg_ptrs, seg_lens.ctypes.data, seg_offs.ctypes.data,
        leaf_lens.ctypes.data, leaf_offs.ctypes.data, len(keep), block,
        leaf_out.ctypes.data, part_out.ctypes.data, stats.ctypes.data)
    if rc != 0:
        raise ValueError(f"native digest refused its input (rc {rc})")
    leaves = leaf_out.tolist()
    return (part_out.tolist(),
            [leaves[a:b] for a, b in zip(leaf_offs[:-1], leaf_offs[1:])],
            {"blocks": int(stats[0]), "threads": int(stats[1])})
