"""TeraSort — BASELINE.md config 2.

The reference path: DryadLinqSampler (DryadLinqSampler.cs:42) samples keys,
DrDynamicRangeDistributionManager picks split points, a range-partition
shuffle redistributes, and each partition sorts locally.  Here: the planner's
OrderBy lowering does exactly that with an all-to-all over ICI
(plan/planner.py OrderBy; parallel/shuffle.range_exchange).  The range
exchange compares the whole key (every sort lane) and splits a run of
equal keys by the rows' global input position (shuffle.range_dest), so
duplicate-heavy keys — the sort benchmark's Daytona (skewed) input —
stay balanced, and equal keys come out in input order.  The streamed
form (terasort_ooc: exec/ooc.external_sort) buckets on the key's first
lane and sorts each bucket whole.

TeraSort records are 10-byte keys + 90-byte payloads; we carry them as a
string key column plus a payload column.
"""

from __future__ import annotations

import numpy as np

from dryad_tpu.api.dataset import Context, Dataset

__all__ = ["gen_records", "terasort_query", "terasort", "terasort_ooc"]


def gen_records(n: int, seed: int = 0, key_len: int = 10):
    """Random printable keys (TeraGen equivalent)."""
    rng = np.random.RandomState(seed)
    keys_arr = rng.randint(ord(" "), ord("~") + 1, (n, key_len),
                           dtype=np.uint8)
    keys = [bytes(k) for k in keys_arr]
    payload = rng.randint(0, 2**31, n).astype(np.int32)
    return {"key": keys, "payload": payload}


def terasort_query(ds: Dataset) -> Dataset:
    return ds.order_by([("key", False)])


def terasort(ctx: Context, n: int, seed: int = 0):
    recs = gen_records(n, seed)
    ds = ctx.from_columns(recs, str_max_len=10)
    return terasort_query(ds).collect()


def terasort_ooc(n: int, chunk_rows: int, out_store: str | None = None,
                 seed: int = 0, n_buckets: int | None = None,
                 spill_dir: str | None = None, depth: int = 2):
    """Out-of-core TeraSort: generate records chunk-wise (never
    materializing the input), externally sort with a bounded device
    working set, optionally stream the sorted output to a store.

    This is the >HBM path to BASELINE.md config 2: device memory use is
    O(chunk_rows) regardless of n.  Returns the output store meta (when
    ``out_store``) or an iterator of sorted host chunks.
    """
    from dryad_tpu.exec import ooc

    n_chunks = -(-n // chunk_rows)

    def gen(i: int):
        rows = min(chunk_rows, n - i * chunk_rows)
        return gen_records(rows, seed=seed * 1_000_003 + i)

    src = ooc.ChunkSource.from_generator(gen, n_chunks, chunk_rows,
                                         str_max_len=10)
    sorted_chunks = ooc.external_sort(src, [("key", False)],
                                      n_buckets=n_buckets,
                                      spill_dir=spill_dir, depth=depth)
    if out_store is None:
        return sorted_chunks
    return ooc.write_chunks_to_store(
        out_store, sorted_chunks, src.schema,
        partitioning={"kind": "range", "keys": ["key"]})
