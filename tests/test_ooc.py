"""Out-of-core chunked execution tests (exec/ooc.py) — every path is
oracle-validated against numpy.  Data sizes are many multiples of the chunk
capacity so device working sets are genuinely bounded."""

import numpy as np
import pytest

import jax.numpy as jnp

from dryad_tpu.exec import ooc
from dryad_tpu.ops import kernels


def _collect(chunks, schema):
    out = ooc._concat_hchunks(schema, list(chunks))
    return out


def _str_list(col):
    data, lens = col
    return [bytes(data[i, : lens[i]]) for i in range(len(lens))]


# ---------------------------------------------------------------------------
# stream_map


def test_stream_map_filter():
    n, chunk = 10_000, 512
    rng = np.random.RandomState(0)
    v = rng.randn(n).astype(np.float32)
    src = ooc.ChunkSource.from_arrays({"v": v}, chunk)

    def fn(b):
        b = kernels.filter_rows(b, lambda c: c["v"] > 0)
        return b.with_columns({"w": b["v"] * 2})

    out = _collect(iter(ooc.stream_map(src, fn)),
                   {"v": {"kind": "dense", "dtype": "float32", "shape": []},
                    "w": {"kind": "dense", "dtype": "float32", "shape": []}})
    exp = v[v > 0]
    assert out.n == len(exp)
    np.testing.assert_allclose(np.asarray(out.cols["v"]), exp)
    np.testing.assert_allclose(np.asarray(out.cols["w"]), exp * 2)


def test_chunk_source_reiterable():
    src = ooc.ChunkSource.from_arrays(
        {"v": np.arange(100, dtype=np.int32)}, 16)
    a = sum(c.n for c in src)
    b = sum(c.n for c in src)
    assert a == b == 100


# ---------------------------------------------------------------------------
# external sort


@pytest.mark.parametrize("n,chunk", [(5_000, 512), (20_000, 1_000)])
def test_external_sort_ints(n, chunk):
    rng = np.random.RandomState(1)
    k = rng.randint(-10**6, 10**6, n).astype(np.int32)
    pay = np.arange(n, dtype=np.int64)
    src = ooc.ChunkSource.from_arrays({"k": k, "pay": pay}, chunk)
    out = _collect(ooc.external_sort(src, [("k", False)]), src.schema)
    assert out.n == n
    got = np.asarray(out.cols["k"])
    assert (got[:-1] <= got[1:]).all()
    # it is a permutation: same multiset of (k, pay)
    exp_order = np.argsort(k, kind="stable")
    np.testing.assert_array_equal(np.sort(got), k[exp_order])
    assert set(zip(got.tolist(), out.cols["pay"].tolist())) == \
        set(zip(k.tolist(), pay.tolist()))


def test_external_sort_floats_descending():
    n, chunk = 8_000, 512
    rng = np.random.RandomState(2)
    v = rng.randn(n).astype(np.float32)
    src = ooc.ChunkSource.from_arrays({"v": v}, chunk)
    out = _collect(ooc.external_sort(src, [("v", True)]), src.schema)
    assert out.n == n
    got = np.asarray(out.cols["v"])
    assert (got[:-1] >= got[1:]).all()
    np.testing.assert_allclose(np.sort(got), np.sort(v))


def test_external_sort_strings():
    n, chunk = 6_000, 500
    rng = np.random.RandomState(3)
    keys = ["".join(chr(rng.randint(97, 123)) for _ in range(8))
            for _ in range(n)]
    src = ooc.ChunkSource.from_arrays({"k": keys}, chunk, str_max_len=8)
    out = _collect(ooc.external_sort(src, [("k", False)]), src.schema)
    assert out.n == n
    got = _str_list(out.cols["k"])
    assert got == sorted(k.encode() for k in keys)


def test_external_sort_skewed_degenerate_lane():
    """90% duplicate key -> degenerate bounds inside the hot bucket -> the
    exact host-merge fallback must kick in and stay correct."""
    n, chunk = 4_000, 256
    rng = np.random.RandomState(4)
    k = np.where(rng.rand(n) < 0.9, 42, rng.randint(0, 1000, n)).astype(
        np.int32)
    src = ooc.ChunkSource.from_arrays({"k": k}, chunk)
    out = _collect(ooc.external_sort(src, [("k", False)]), src.schema)
    assert out.n == n
    got = np.asarray(out.cols["k"])
    np.testing.assert_array_equal(got, np.sort(k))


def test_external_sort_with_disk_spill(tmp_path):
    n, chunk = 5_000, 512
    rng = np.random.RandomState(5)
    k = rng.randint(0, 10**6, n).astype(np.int32)
    s = ["p%06d" % i for i in rng.randint(0, 10**6, n)]
    src = ooc.ChunkSource.from_arrays({"k": k, "s": s}, chunk,
                                      str_max_len=8)
    out = _collect(
        ooc.external_sort(src, [("k", False)],
                          spill_dir=str(tmp_path / "spill")),
        src.schema)
    assert out.n == n
    got = np.asarray(out.cols["k"])
    np.testing.assert_array_equal(got, np.sort(k))
    # payload strings still paired with their keys
    pairs = set(zip(got.tolist(), _str_list(out.cols["s"])))
    exp = set(zip(k.tolist(), (x.encode() for x in s)))
    assert pairs == exp


# ---------------------------------------------------------------------------
# streaming group aggregate


def test_streaming_group_aggregate():
    n, chunk = 30_000, 1_000
    rng = np.random.RandomState(6)
    k = rng.randint(0, 500, n).astype(np.int32)
    v = rng.randn(n).astype(np.float32)
    src = ooc.ChunkSource.from_arrays({"k": k, "v": v}, chunk)
    chunks = list(ooc.streaming_group_aggregate(
        src, ["k"], {"n": ("count", None), "s": ("sum", "v"),
                     "m": ("mean", "v")}, n_buckets=16))
    schema = ooc.chunk_schema(chunks[0])
    out = _collect(chunks, schema)
    keys, counts = np.unique(k, return_counts=True)
    assert out.n == len(keys)
    order = np.argsort(np.asarray(out.cols["k"]))
    np.testing.assert_array_equal(np.asarray(out.cols["k"])[order], keys)
    np.testing.assert_array_equal(np.asarray(out.cols["n"])[order], counts)
    exp_sum = np.array([v[k == kk].sum() for kk in keys], np.float32)
    # atol: f32 group sums ride a COMPENSATED global prefix (boundary-
    # carry group_aggregate + pallas_kernels.prefix_sum2) — error is near
    # ulp(group_sum); the small atol absorbs the remaining reassociation
    np.testing.assert_allclose(np.asarray(out.cols["s"])[order], exp_sum,
                               rtol=2e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(out.cols["m"])[order],
                               exp_sum / counts, rtol=2e-4, atol=1e-4)


def test_streaming_group_aggregate_high_cardinality_compaction():
    """More distinct keys than one chunk holds: buckets must compact
    (device re-aggregation) and still produce exact results."""
    n, chunk = 20_000, 512
    rng = np.random.RandomState(7)
    k = rng.randint(0, 4_000, n).astype(np.int32)
    src = ooc.ChunkSource.from_arrays({"k": k}, chunk)
    chunks = list(ooc.streaming_group_aggregate(
        src, ["k"], {"n": ("count", None)}, n_buckets=32))
    schema = ooc.chunk_schema(chunks[0])
    out = _collect(chunks, schema)
    keys, counts = np.unique(k, return_counts=True)
    assert out.n == len(keys)
    order = np.argsort(np.asarray(out.cols["k"]))
    np.testing.assert_array_equal(np.asarray(out.cols["k"])[order], keys)
    np.testing.assert_array_equal(np.asarray(out.cols["n"])[order], counts)


def test_streaming_group_aggregate_cardinality_overflow():
    n, chunk = 5_000, 64
    k = np.arange(n, dtype=np.int32)  # all distinct
    src = ooc.ChunkSource.from_arrays({"k": k}, chunk)
    with pytest.raises(ooc.OOCError, match="n_buckets"):
        list(ooc.streaming_group_aggregate(
            src, ["k"], {"n": ("count", None)}, n_buckets=2))


# ---------------------------------------------------------------------------
# store round trip + terasort_ooc


def test_write_chunks_to_store_roundtrip(tmp_path):
    from dryad_tpu import Context

    n, chunk = 3_000, 256
    rng = np.random.RandomState(8)
    k = rng.randint(0, 100, n).astype(np.int32)
    src = ooc.ChunkSource.from_arrays({"k": k}, chunk)
    path = str(tmp_path / "ooc_store")
    meta = ooc.write_chunks_to_store(path, iter(src), src.schema)
    assert sum(meta["counts"]) == n
    # read back chunk-wise
    back = _collect(ooc.ChunkSource.from_store(path, 512), src.schema)
    np.testing.assert_array_equal(np.asarray(back.cols["k"]), k)
    # and through the in-memory engine
    ctx = Context()
    t = ctx.from_store(path).collect()
    np.testing.assert_array_equal(np.sort(np.asarray(t["k"])), np.sort(k))


def test_terasort_ooc_oracle(tmp_path):
    """End-to-end OOC TeraSort: generated chunk-wise, sorted externally,
    streamed to a store; oracle = numpy sort of the same generated data."""
    from dryad_tpu.apps.terasort import gen_records, terasort_ooc

    n, chunk = 20_000, 1_024
    out = str(tmp_path / "sorted")
    meta = terasort_ooc(n, chunk, out_store=out, seed=3)
    assert sum(meta["counts"]) == n

    # oracle: regenerate the same chunks, sort on host
    n_chunks = -(-n // chunk)
    all_keys = []
    for i in range(n_chunks):
        rows = min(chunk, n - i * chunk)
        all_keys.extend(gen_records(rows, seed=3 * 1_000_003 + i)["key"])
    exp = sorted(all_keys)

    back = _collect(ooc.ChunkSource.from_store(out, 4_096),
                    {"key": {"kind": "str", "max_len": 10},
                     "payload": {"kind": "dense", "dtype": "int32",
                                 "shape": []}})
    got = _str_list(back.cols["key"])
    assert got == exp


def test_autotune_chunk_rows_model():
    """pick_chunk_rows amortizes a measured dispatch floor against the
    measured link rate (chunk_rows was hand-set before)."""
    from dryad_tpu.exec.autotune import pick_chunk_rows

    # slow link: 0.1 s floor, 5 MB/s link, 18 B rows -> big chunks:
    # transfer must be >= 0.1 * 0.85/0.15 = 0.57 s -> ~157k rows
    rows = pick_chunk_rows(18, rates=(5e6, 0.1))
    assert 120_000 <= rows <= 200_000
    # local link: microsecond floor -> lower clamp
    assert pick_chunk_rows(18, rates=(1e9, 2e-6)) == 4096
    # program-size guard caps wide rows
    rows = pick_chunk_rows(18, rates=(1e9, 10.0), row_lanes=8)
    from dryad_tpu.ops.kernels import _VALOPS_MAX_ELEMS
    assert rows * 8 <= _VALOPS_MAX_ELEMS
