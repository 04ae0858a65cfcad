"""Kind ``gensort``: the sort benchmark's 100-byte record (sortbenchmark.org,
``gensort``): a 10-byte key and a 90-byte payload.  The payload's first 8
bytes are the record's number (big-endian), as gensort's own payload
carries it; the rest is random filler."""

from __future__ import annotations

import os

import numpy as np

from perfbench import storeio


def rows(cfg, rehearse=False) -> int:
    return int(cfg["rehearse"]["records"] if rehearse else cfg["records"])


def generate(seed, cfg, rehearse=False):
    n = rows(cfg, rehearse)
    kb, pb = int(cfg["key_bytes"]), int(cfg["payload_bytes"])
    rng = np.random.default_rng([int(seed), 1])
    keys = rng.integers(0, 256, size=(n, kb), dtype=np.uint8)
    payload = rng.integers(0, 256, size=(n, pb), dtype=np.uint8)
    payload[:, :8] = np.arange(n, dtype=">u8").view(np.uint8).reshape(n, 8)
    return {"n": n, "keys": keys, "payload": payload}


def ingest(ctx, data, cfg, workdir):
    n = data["n"]
    src = os.path.join(workdir, "input")
    kb, pb = data["keys"].shape[1], data["payload"].shape[1]
    nbytes = storeio.write_input(
        ctx, src,
        {"key": (data["keys"], np.full(n, kb, np.int32)),
         "payload": (data["payload"], np.full(n, pb, np.int32))}, n)
    return {"tables": {"input": src}, "rows": n,
            "device_bytes": nbytes,
            "stored_bytes": storeio.stored_bytes(src)}
