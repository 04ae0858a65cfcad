"""Persistent XLA compilation cache.

The reference pays its per-job codegen cost once: csc compiles the vertex
DLL in seconds and the artifact is reused for every vertex of the job
(DryadLinqCodeGen.cs:2140-2257 BuildAssembly).  Our counterpart cost is XLA
compilation of stage programs — tens of seconds to minutes per app on
the TPU compiler — and by default it was paid again on EVERY driver
restart, because jit/AOT caches are per-process.

This module turns on JAX's persistent (on-disk) compilation cache so stage
programs are compiled once per (program, shapes, device kind) and then
loaded from disk in milliseconds by every later process: driver restarts,
bench re-runs, and all cluster worker processes (they share the directory;
the cache is multi-process safe — writes go through atomic renames).

Wired from Context.__init__ and the executor (driver and workers alike),
placed by ``JAX_COMPILATION_CACHE_DIR`` when set and else by
``JobConfig.compilation_cache_dir`` (default ``<repo>/.jax_cache``; None
disables).

The key holds the program's metadata (``op_name`` with its named scopes,
source file and line): by default JAX hashes a module stripped of it, so a
program that differs from a cached one only by its scopes loads the
cached executable and a device profile shows the OLD program's op names.
The cost: the first run after an edit that moves a traced source line
compiles again, the same as a program change.  Source files are keyed
relative to the checkout (``jax_hlo_source_file_canonicalization_regex``,
unless already set), so the same commit hits from any directory.

:class:`FileCache` is the framework's OWN shared on-disk artifact cache
(serialized plans, lowered specs — anything bytes) with the same
concurrency contract the XLA cache relies on, made explicit: commits go
through same-directory atomic renames so a reader can never observe a
torn entry, every entry carries a content checksum so a corrupt or
crash-truncated file reads as a MISS (never as garbage), and concurrent
writers of one key are last-writer-wins.  The multi-tenant job service
(dryad_tpu/service) keys its per-app plan cache here so the Nth user of
an app pays zero planning, and per-JOB hit/miss counters land in the
metrics registry (the "did this tenant pay compile" dashboard signal).
"""

from __future__ import annotations

import hashlib
import os
import re
import threading
from typing import Optional

__all__ = ["enable_persistent_cache", "DEFAULT_CACHE_DIR", "FileCache"]

# ONE fixed path inside the checkout: the directory is part of the
# cache key's reach (a directory that moves never hits), and a fresh
# machine has no ~/.cache.  ``JAX_COMPILATION_CACHE_DIR`` places it from
# outside.
_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_CACHE_DIR = os.path.join(_ROOT, ".jax_cache")

_lock = threading.Lock()
_enabled_dir: Optional[str] = None


def enable_persistent_cache(path: Optional[str] = DEFAULT_CACHE_DIR) -> Optional[str]:
    """Turn on JAX's persistent compilation cache and return the
    directory in use (None when disabled).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the environment owns the
    directory: JAX already reads it, and NO directory is set in code
    whatever ``path`` says (only the thresholds are).  Otherwise the
    cache goes to ``path`` (created if missing), or is DISABLED for this
    process when ``path`` is None (the JAX config is process-global, so
    a None-configured Context must undo what an earlier Context
    enabled).  JAX's own cache key separates backends, so CPU workers
    and an accelerator-attached driver share one directory safely.
    Idempotent.  Safe to call before or after device init — the cache
    is consulted at compile time, not backend-init time."""
    global _enabled_dir
    from dryad_tpu.obs.metrics import REGISTRY, family_gauge
    with _lock:
        import jax

        env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if path is None and not env_dir:
            if _enabled_dir is not None:
                jax.config.update("jax_compilation_cache_dir", None)
                _enabled_dir = None
            family_gauge(REGISTRY, "persistent_cache").set(0)
            return None
        resolved = env_dir or os.path.abspath(os.path.expanduser(path))
        if _enabled_dir == resolved:
            return resolved
        if not env_dir:
            os.makedirs(resolved, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", resolved)
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        if not jax.config.jax_hlo_source_file_canonicalization_regex:
            jax.config.update("jax_hlo_source_file_canonicalization_regex",
                              "^" + re.escape(_ROOT + os.sep))
        # cache every compile: stage programs are small but numerous, and
        # even a 0.3 s compile is worth skipping across worker processes
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _enabled_dir = resolved
        family_gauge(REGISTRY, "persistent_cache").set(1)
        return resolved


# 8-byte magic + sha256 of the payload, prefixed so a reader validates
# BEFORE trusting the bytes; bumping the version invalidates old entries
_FC_MAGIC = b"DRYDFC1\n"


class FileCache:
    """Concurrent-writer-safe on-disk bytes cache (get/put by string key).

    * **Atomic commit:** ``put`` writes to a uniquely-named temp file in
      the SAME directory, fsyncs, then ``os.replace``s it into place —
      readers observe either the old complete entry or the new complete
      entry, never a partial write (the rename-commit contract the
      reference's partitioned stores and the XLA persistent cache both
      rely on).
    * **No torn reads:** every entry is ``magic + sha256(payload) +
      payload``; a file that fails the checksum (crash-truncated write
      on a filesystem without atomic rename, e.g. some NFS modes) is a
      MISS and is unlinked best-effort.
    * **Concurrent writers:** two processes putting the same key race
      benignly — both renames are atomic, last writer wins, and both
      committed values are valid (cache values must be deterministic
      functions of the key, which plans are).

    Hit/miss counters land in the canonical metrics families
    (``cache_hits``/``cache_misses``, labeled ``cache="file"`` plus the
    optional per-job label) so the service dashboard can show per-tenant
    amortization."""

    def __init__(self, root: str):
        self.root = os.path.abspath(os.path.expanduser(root))
        os.makedirs(self.root, exist_ok=True)

    def _path(self, key: str) -> str:
        h = hashlib.sha256(key.encode()).hexdigest()
        return os.path.join(self.root, h[:2], h[2:])

    def _count(self, hit: bool, job: Optional[str]) -> None:
        from dryad_tpu.obs.metrics import REGISTRY, family_counter
        labels = {"cache": "file"}
        if job is not None:
            labels["job"] = job
        family_counter(REGISTRY, "cache_hits" if hit else "cache_misses",
                       **labels).inc()

    def get(self, key: str, job: Optional[str] = None) -> Optional[bytes]:
        """The committed payload for ``key``, or None (miss / torn)."""
        p = self._path(key)
        try:
            with open(p, "rb") as f:
                blob = f.read()
                ino = os.fstat(f.fileno()).st_ino
        except OSError:
            self._count(False, job)
            return None
        head = len(_FC_MAGIC) + 32
        if (len(blob) < head or not blob.startswith(_FC_MAGIC)
                or hashlib.sha256(blob[head:]).digest()
                != blob[len(_FC_MAGIC):head]):
            # corrupt/torn entry: a miss, never garbage — and evict it
            # so the next writer's rename starts clean.  Only evict the
            # INODE we read: a concurrent put may have os.replace()d a
            # fresh valid entry in since, and unlinking that would throw
            # away a just-committed value (the remaining stat→unlink
            # window is benign: worst case one extra rebuildable miss)
            try:
                if os.stat(p).st_ino == ino:
                    os.unlink(p)
            except OSError:
                pass
            self._count(False, job)
            return None
        self._count(True, job)
        return blob[head:]

    def put(self, key: str, data: bytes, job: Optional[str] = None) -> None:
        """Commit ``data`` under ``key`` atomically (rename commit)."""
        from dryad_tpu.utils.atomic import atomic_write_bytes
        blob = _FC_MAGIC + hashlib.sha256(data).digest() + data
        atomic_write_bytes(self._path(key), blob)
