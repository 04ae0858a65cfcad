"""Partitioned-store layout on an S3-compatible object store.

Same LOGICAL format as the local store (io/store.py v3: per-partition
binary of concatenated column segments, optionally gzip, fnv64-
checksummed, meta.json describing schema/counts/partitioning) laid out
as objects ``<prefix>/part-00000.bin`` ... + ``<prefix>/meta.json``.
S3 has no atomic rename, so the COMMIT POINT is the meta.json write,
done LAST: a reader that finds meta sees only fully-written parts (the
role of the local store's temp-dir rename / DrVertex.h:325-351 job-end
commit).

Reference parity: the GM/vertex cloud adapters
(GraphManager/filesystem/DrHdfsClient.cpp, DrAzureBlobClient.cpp,
channelbufferhdfs.cpp) read/write partitioned datasets against remote
object stores; io/store.py routes any ``s3://`` path here, so
``to_store("s3://...")``, ``from_store``, and ``read_store_stream`` all
work against object storage unchanged.
"""

from __future__ import annotations

import gzip
import json
from typing import Any, Dict, List, Optional

import numpy as np

from dryad_tpu.io.s3 import S3Client, S3Config, parse_s3_url

__all__ = ["s3_write_store", "s3_store_meta", "s3_read_part_segments",
           "s3_client"]

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


def s3_store_meta(url: str, client: Optional[S3Client] = None
                  ) -> Dict[str, Any]:
    c = client or s3_client()
    bucket, prefix = parse_s3_url(url)
    body = c.get_object(bucket, prefix.rstrip("/") + "/meta.json")
    return json.loads(body)


def s3_write_store(url: str, pd, partitioning=None, compression=None,
                   client: Optional[S3Client] = None) -> None:
    """write_store for s3:// paths (same segments, checksums, meta)."""
    from dryad_tpu.io.store import (build_meta, fetch_part_segments,
                                    part_checksums, pdata_schema,
                                    segments_blob)

    if compression not in (None, "gzip"):
        raise ValueError(f"unknown compression {compression!r}")
    c = client or s3_client()
    bucket, prefix = parse_s3_url(url)
    counts = np.asarray(pd.counts)
    schema = pdata_schema(pd)
    import uuid
    gen = uuid.uuid4().hex[:12]
    segments = []
    for p, (segs, _, _) in enumerate(
            fetch_part_segments(pd, schema, counts)):
        segments.append(segs)
        c.put_object(bucket, _part_key(prefix, p, gen),
                     segments_blob(segs, compression))
    checksums, leaf_checksums, _ = part_checksums(schema, counts, segments)
    meta = build_meta(schema, counts.tolist(), checksums,
                      partitioning=partitioning, compression=compression,
                      capacity=pd.capacity, leaf_checksums=leaf_checksums)
    meta["generation"] = gen
    # the PREVIOUS meta (if any) names the generation readers may still
    # be holding — it survives this overwrite; anything older is garbage
    prev_gen = None
    try:
        prev = json.loads(c.get_object(bucket,
                                       prefix.rstrip("/") + "/meta.json"))
        prev_gen = prev.get("generation", "")
    except Exception:
        pass
    # meta LAST = the commit (readers resolve parts via meta.generation,
    # so the swap is atomic even over an existing prefix)
    c.put_object(bucket, prefix.rstrip("/") + "/meta.json",
                 json.dumps(meta, indent=1).encode())
    # two-generation retention: keep the just-superseded generation (a
    # reader that captured its meta mid-swap can finish), best-effort
    # delete everything older so daily overwrites do not grow the bucket
    # without bound
    try:
        keep = {gen, prev_gen or ""}
        base = prefix.rstrip("/") + "/"
        # materialize the listing BEFORE deleting: deleting while the
        # paginator is live shifts continuation offsets and skips keys
        for key, _sz in list(c.list_objects(bucket, base)):
            rel = key[len(base):]
            if "/" in rel and rel.endswith(".bin"):
                g = rel.split("/", 1)[0]
                if g not in keep:
                    c.delete_object(bucket, key)
            elif rel.startswith("part-") and rel.endswith(".bin") \
                    and "" not in keep:
                c.delete_object(bucket, key)   # pre-generation legacy
    except Exception:
        pass   # GC must never fail a committed write


def write_partition_objects(url: str, schema, blobs: List[bytes],
                            part_ids: List[int], gen: str = "",
                            client: Optional[S3Client] = None) -> None:
    """Raw per-partition blob upload (parallel cluster writers); the
    coordinator that later commits meta.json must pass the same ``gen``
    it records there."""
    c = client or s3_client()
    bucket, prefix = parse_s3_url(url)
    for p, blob in zip(part_ids, blobs):
        c.put_object(bucket, _part_key(prefix, p, gen), blob)


def _fill_segments(segs: List[np.ndarray], data: bytes) -> None:
    from dryad_tpu.io.store import fill_segments
    fill_segments(segs, data, "s3 object")


def s3_read_part_segments(url: str, meta: Dict[str, Any], p: int,
                          client: Optional[S3Client] = None
                          ) -> List[np.ndarray]:
    """One partition's column segments, decompressed and filled."""
    return s3_read_part_views(url, meta, p, client=client)[0]


def s3_read_part_views(url: str, meta: Dict[str, Any], p: int,
                       client: Optional[S3Client] = None):
    """(segments, column views) for one partition — the read_store /
    ChunkSource building block."""
    from dryad_tpu.io.store import _alloc_part_views

    c = client or s3_client()
    bucket, prefix = parse_s3_url(url)
    segs, cols = _alloc_part_views(meta["schema"], meta["counts"][p])
    data = c.get_object(bucket, _part_key(prefix, p,
                                          meta.get("generation", "")))
    if meta.get("compression") == "gzip":
        data = gzip.decompress(data)
    _fill_segments(segs, data)
    return segs, cols
