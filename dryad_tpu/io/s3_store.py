"""The ``s3://`` byte target of the partitioned store.

The store's format (layout, digest, manifest, the order of a commit) is
io/store.py's alone; this module knows object NAMES and BYTES under one
prefix — ``<prefix>/<generation>/part-00000.bin`` ... +
``<prefix>/meta.json`` — and what makes a commit atomic there.  S3 has no
atomic rename, so the COMMIT POINT is the meta.json PUT, done LAST: a
reader that finds meta sees only fully-written parts (the role of the
local store's temp-dir rename / DrVertex.h:325-351 job-end commit).

Reference parity: the GM/vertex cloud adapters
(GraphManager/filesystem/DrHdfsClient.cpp, DrAzureBlobClient.cpp,
channelbufferhdfs.cpp) read/write partitioned datasets against remote
object stores; io/store.py routes any ``s3://`` path here, so
``to_store("s3://...")``, ``from_store``, and ``read_store_stream`` all
work against object storage unchanged.
"""

from __future__ import annotations

import json
import uuid
from typing import Optional

from dryad_tpu.io.s3 import S3Client, S3Config, parse_s3_url

__all__ = ["S3Prefix", "s3_client"]

_CLIENT: Optional[S3Client] = None


def s3_client(config: Optional[S3Config] = None) -> S3Client:
    """Process-default client (env-configured) unless given a config."""
    global _CLIENT
    if config is not None:
        return S3Client(config)
    if _CLIENT is None:
        _CLIENT = S3Client()
    return _CLIENT


def _part_key(prefix: str, p: int, gen: str = "") -> str:
    """Part object key; ``gen`` is the write-generation subprefix recorded
    in meta.json.  Parts of different generations never collide, which is
    what makes OVERWRITING an existing store prefix atomic at the meta
    swap: a concurrent reader holding the old meta keeps resolving the old
    generation's objects, and a mid-write failure leaves the old meta
    pointing at fully intact old parts (ADVICE r4: without this, new part
    bytes replaced old ones before the new meta landed).  Empty gen reads
    legacy stores written before generations existed."""
    g = f"{gen}/" if gen else ""
    return f"{prefix.rstrip('/')}/{g}part-{p:05d}.bin"


def _part_gen(rel: str) -> Optional[str]:
    """The inverse of ``_part_key`` under a prefix: the generation of a
    part object's relative key ("" for a pre-generation legacy part), None
    for any other object."""
    if not rel.endswith(".bin"):
        return None
    if "/" in rel:
        return rel.split("/", 1)[0]
    return "" if rel.startswith("part-") else None


class S3Prefix:
    """Names and bytes of one store under an ``s3://`` prefix (io/store.py
    ``_target``).  ``gen`` is the generation part names resolve through: the
    one a manifest records for a reader, a fresh one after ``begin``."""

    ranged = False

    def __init__(self, url: str, gen: str = ""):
        self.c = s3_client()
        self.bucket, prefix = parse_s3_url(url)
        self.prefix = prefix.rstrip("/")
        self.gen = gen

    def read_meta(self) -> bytes:
        return self.c.get_object(self.bucket, self.prefix + "/meta.json")

    def begin(self, shared: bool = False) -> str:
        """A fresh generation for this write's parts; the writer records
        it in the manifest."""
        self.gen = uuid.uuid4().hex[:12]
        return self.gen

    def put(self, p: int, data: bytes) -> None:
        self.c.put_object(self.bucket, _part_key(self.prefix, p, self.gen),
                          data)

    def get(self, p: int) -> bytes:
        return self.c.get_object(self.bucket,
                                 _part_key(self.prefix, p, self.gen))

    def get_range(self, p: int, off: int, ln: int) -> bytes:
        """``ln`` bytes of a part at ``off`` (one ranged GET) — a read of
        some columns fetches a leaf this way.  ``ranged`` stays False: a
        whole part is still one object, one GET."""
        return self.c.get_object(self.bucket,
                                 _part_key(self.prefix, p, self.gen),
                                 rng=(off, off + ln - 1))

    def what(self, p: int) -> str:
        return "s3 object"

    def commit(self, manifest: bytes) -> None:
        c, bucket, meta_key = self.c, self.bucket, self.prefix + "/meta.json"
        # the PREVIOUS meta (if any) names the generation readers may still
        # be holding — it survives this overwrite; anything older is garbage
        prev_gen = None
        try:
            prev_gen = json.loads(c.get_object(bucket, meta_key)
                                  ).get("generation", "")
        except Exception:
            pass
        # meta LAST = the commit (readers resolve parts via meta.generation,
        # so the swap is atomic even over an existing prefix)
        c.put_object(bucket, meta_key, manifest)
        # two-generation retention: keep the just-superseded generation (a
        # reader that captured its meta mid-swap can finish), best-effort
        # delete everything older so daily overwrites do not grow the bucket
        # without bound
        try:
            keep = {self.gen, prev_gen or ""}
            base = self.prefix + "/"
            # materialize the listing BEFORE deleting: deleting while the
            # paginator is live shifts continuation offsets and skips keys
            for key, _sz in list(c.list_objects(bucket, base)):
                g = _part_gen(key[len(base):])
                if g is not None and g not in keep:
                    c.delete_object(bucket, key)
        except Exception:
            pass   # GC must never fail a committed write
