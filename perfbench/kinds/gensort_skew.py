"""Kind ``gensort_skew``: the sort benchmark's 100-byte record
(``kinds/gensort.py``) under a duplicate-heavy key law, as the Daytona
category's skewed input (``gensort -s``) asks a general-purpose sort to
survive.  The law is the configuration's ``key_distribution``: a Zipf law
over a table of uniform random keys, so the hot keys lie anywhere in the
key space.  The payload is gensort's: bytes 0-7 the record's number
(big-endian), the rest random filler."""

from __future__ import annotations

import numpy as np

from perfbench.kinds.gensort import ingest, rows      # noqa: F401


def generate(seed, cfg, rehearse=False):
    n = rows(cfg, rehearse)
    kb, pb = int(cfg["key_bytes"]), int(cfg["payload_bytes"])
    law = cfg["key_law"]
    distinct, exponent = int(law["distinct_keys"]), float(law["exponent"])
    rng = np.random.default_rng([int(seed), 1])
    # the key of rank r is row r-1 of the table; rank r is drawn with
    # probability proportional to r ** -exponent, by inverse CDF
    table = rng.integers(0, 256, size=(distinct, kb), dtype=np.uint8)
    cdf = np.cumsum(np.arange(1, distinct + 1, dtype=np.float64)
                    ** -exponent)
    rank = np.searchsorted(cdf, rng.random(n) * cdf[-1], side="right")
    keys = table[np.minimum(rank, distinct - 1)]
    payload = rng.integers(0, 256, size=(n, pb), dtype=np.uint8)
    payload[:, :8] = np.arange(n, dtype=">u8").view(np.uint8).reshape(n, 8)
    return {"n": n, "keys": keys, "payload": payload}
