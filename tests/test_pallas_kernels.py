"""ops/pallas_kernels: the hand-written TPU kernels, exercised on CPU in
interpreter mode (the REAL kernel bodies run, instruction by
instruction) and via their XLA fallbacks.  The compiled TPU path is
covered by the bench's device-truth rows (benchmarks/pallas_probe.py)."""

import numpy as np
import pytest

import jax.numpy as jnp

from dryad_tpu.ops.pallas_kernels import (force_interpret, hist_buckets,
                                          pallas_active, prefix_max,
                                          prefix_sum, slot_compact,
                                          slot_expand)


def _modes():
    return ["fallback", "interpret"]


def _run(mode, fn):
    if mode == "interpret":
        with force_interpret():
            assert pallas_active() == "interpret"
            return fn()
    assert pallas_active() in (None, "compiled")
    return fn()


@pytest.mark.parametrize("mode", _modes())
def test_hist_matches_bincount(mode):
    rng = np.random.RandomState(0)
    bid = jnp.asarray(rng.randint(0, 37, 20_000).astype(np.int32))
    h = np.asarray(_run(mode, lambda: hist_buckets(bid, 37)))
    assert (h == np.bincount(np.asarray(bid), minlength=37)).all()


@pytest.mark.parametrize("mode", _modes())
def test_hist_ignores_out_of_range(mode):
    """The invalid-row sentinel (== n_buckets) and negatives don't count."""
    rng = np.random.RandomState(1)
    bid = rng.randint(0, 8, 5_000).astype(np.int32)
    bid[::7] = 8          # sentinel
    bid[::11] = -3
    h = np.asarray(_run(mode, lambda: hist_buckets(jnp.asarray(bid), 8)))
    ref = np.bincount(bid[(bid >= 0) & (bid < 8)], minlength=8)
    assert (h == ref).all()


@pytest.mark.parametrize("mode", _modes())
def test_hist_unpadded_sizes(mode):
    """Sizes that don't divide the kernel tile exercise the pad path."""
    for n in (1, 127, 129, 16384, 16385):
        bid = jnp.asarray((np.arange(n) % 5).astype(np.int32))
        h = np.asarray(_run(mode, lambda: hist_buckets(bid, 5)))
        assert (h == np.bincount(np.arange(n) % 5, minlength=5)).all(), n


def test_hist_wide_bucket_fallback():
    """n_buckets beyond the VMEM accumulator budget uses bincount."""
    bid = jnp.asarray((np.arange(4_000) % 600).astype(np.int32))
    h = np.asarray(hist_buckets(bid, 600))
    assert (h == np.bincount(np.arange(4_000) % 600, minlength=600)).all()


@pytest.mark.parametrize("mode", _modes())
@pytest.mark.parametrize("dtype", [np.int32, np.uint32, np.float32])
def test_prefix_sum(mode, dtype):
    rng = np.random.RandomState(2)
    if dtype == np.float32:
        x = rng.rand(40_000).astype(dtype)
    else:
        x = rng.randint(0, 100, 40_000).astype(dtype)
    y = np.asarray(_run(mode, lambda: prefix_sum(jnp.asarray(x))))
    ref = np.cumsum(x.astype(np.float64 if dtype == np.float32 else
                             np.int64))
    if dtype == np.float32:
        assert np.abs(y - ref).max() < np.abs(ref).max() * 1e-5
    else:
        assert (y.astype(np.int64) == (ref & 0xFFFFFFFF if dtype ==
                np.uint32 else ref)).all() or \
            (y == ref.astype(dtype)).all()


@pytest.mark.parametrize("mode", _modes())
def test_prefix_sum_unpadded_sizes(mode):
    for n in (1, 5, 128, 32768, 32769, 70_000):
        x = jnp.ones((n,), jnp.int32)
        y = np.asarray(_run(mode, lambda: prefix_sum(x)))
        assert (y == np.arange(1, n + 1)).all(), n


@pytest.mark.parametrize("mode", _modes())
@pytest.mark.parametrize("n", [1, 128, 32768, 32769, 70_000])
def test_prefix_max(mode, n):
    """The running max across tiles (the SMEM carry) and within one (the
    lanes, then the rows), negatives and the pad path included."""
    rng = np.random.RandomState(n)
    x = rng.randint(-(1 << 30), 1 << 30, n).astype(np.int32)
    x[n // 3:] = np.minimum(x[n // 3:], 0)      # a max the carry holds
    y = np.asarray(_run(mode, lambda: prefix_max(jnp.asarray(x))))
    assert (y == np.maximum.accumulate(x)).all()


def test_boundary_group_path_used_and_matches_scan():
    """The boundary-carry group path (which consumes prefix_sum) agrees
    with the segmented-scan path on the full agg surface."""
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels as k

    rng = np.random.RandomState(3)
    n = 4_000
    b = Batch({"k": jnp.asarray(rng.randint(0, 97, n).astype(np.int32)),
               "v": jnp.asarray(rng.randn(n).astype(np.float32)),
               "w": jnp.asarray(rng.randint(-50, 50, n).astype(np.int32)),
               "f": jnp.asarray(rng.rand(n) < 0.5)},
              jnp.asarray(n - 7, jnp.int32))
    aggs = {"n": ("count", None), "s": ("sum", "v"), "m": ("mean", "v"),
            "lo": ("min", "v"), "hi": ("max", "v"), "ws": ("sum", "w"),
            "anyf": ("any", "f"), "allf": ("all", "f")}
    ok, mm = k._boundary_eligible(b, aggs)
    assert ok and mm == "v"
    got = k._group_aggregate_boundary(b, ["k"], aggs, mm)
    ref = k._group_aggregate_scan(b, ["k"], aggs)
    ng = int(ref.count)
    assert int(got.count) == ng
    go = np.argsort(np.asarray(got.columns["k"])[:ng])
    ro = np.argsort(np.asarray(ref.columns["k"])[:ng])
    for c in ("k", "n", "ws", "anyf", "allf"):
        np.testing.assert_array_equal(np.asarray(got.columns[c])[:ng][go],
                                      np.asarray(ref.columns[c])[:ng][ro])
    for c in ("s", "m", "lo", "hi"):
        np.testing.assert_allclose(np.asarray(got.columns[c])[:ng][go],
                                   np.asarray(ref.columns[c])[:ng][ro],
                                   rtol=1e-4, atol=1e-4)


def test_boundary_group_string_keys():
    """Hash-path boundary grouping (string keys ride as packed carries)."""
    from dryad_tpu.data.columnar import batch_from_numpy
    from dryad_tpu.ops import kernels as k

    rng = np.random.RandomState(4)
    words = [f"w{i:03d}" for i in range(40)]
    keys = [words[i] for i in rng.randint(0, 40, 3_000)]
    vals = rng.rand(3_000).astype(np.float32)
    b = batch_from_numpy({"t": keys, "v": vals}, str_max_len=8)
    aggs = {"n": ("count", None), "s": ("sum", "v")}
    ok, mm = k._boundary_eligible(b, aggs)
    assert ok and mm is None
    out = k.group_aggregate(b, ["t"], aggs)
    ng = int(out.count)
    assert ng == 40
    got = {}
    tc = out.columns["t"]
    for i in range(ng):
        L = int(np.asarray(tc.lengths)[i])
        w = bytes(np.asarray(tc.data)[i, :L]).decode()
        got[w] = (int(np.asarray(out.columns["n"])[i]),
                  float(np.asarray(out.columns["s"])[i]))
    for w in words:
        mask = np.array([kk == w for kk in keys])
        assert got[w][0] == mask.sum()
        np.testing.assert_allclose(got[w][1], vals[mask].sum(), rtol=1e-4)


def test_boundary_ineligible_falls_back():
    """2-D value columns and i64 sums stay on the scan path."""
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels as k

    n = 500
    b = Batch({"k": jnp.asarray(np.arange(n) % 7, dtype=jnp.int32),
               "x": jnp.ones((n, 3), jnp.float32)},
              jnp.asarray(n, jnp.int32))
    ok, _ = k._boundary_eligible(b, {"m": ("mean", "x")})
    assert not ok
    out = k.group_aggregate(b, ["k"], {"m": ("mean", "x")})
    assert int(out.count) == 7
    np.testing.assert_allclose(
        np.asarray(out.columns["m"])[:7], np.ones((7, 3)), rtol=1e-6)


def test_smallkey_matmul_group_matches_scan():
    """The one-hot MXU group path agrees with the sort paths, including
    the runtime wide-span fallback inside the same compiled fn."""
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels as k

    rng = np.random.RandomState(5)
    n = 3_000
    aggs = {"n": ("count", None), "m": ("mean", "x"), "s": ("sum", "w")}

    def run(keys):
        b = Batch({"k": jnp.asarray(keys),
                   "x": jnp.asarray(rng.rand(n, 4).astype(np.float32)),
                   "w": jnp.asarray(rng.randn(n).astype(np.float32))},
                  jnp.asarray(n - 11, jnp.int32))
        assert k._matmul_group_eligible(b, ["k"], aggs)
        got = k.group_aggregate(b, ["k"], aggs)
        ref = k._group_aggregate_scan(b, ["k"], aggs)
        ng = int(ref.count)
        assert int(got.count) == ng
        go = np.argsort(np.asarray(got.columns["k"])[:ng])
        ro = np.argsort(np.asarray(ref.columns["k"])[:ng])
        np.testing.assert_array_equal(
            np.asarray(got.columns["k"])[:ng][go],
            np.asarray(ref.columns["k"])[:ng][ro])
        np.testing.assert_array_equal(
            np.asarray(got.columns["n"])[:ng][go],
            np.asarray(ref.columns["n"])[:ng][ro])
        for c in ("m", "s"):
            np.testing.assert_allclose(
                np.asarray(got.columns[c])[:ng][go],
                np.asarray(ref.columns[c])[:ng][ro], rtol=1e-5, atol=1e-5)

    run(rng.randint(-40, 77, n).astype(np.int32))      # small span (MXU)
    run(rng.randint(-2**30, 2**30, n).astype(np.int32))  # wide (fallback)
    run(np.full(n, 2**31 - 5, np.int32))               # near-overflow span


def test_smallkey_matmul_empty_and_single():
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels as k

    aggs = {"n": ("count", None), "s": ("sum", "v")}
    b = Batch({"k": jnp.zeros((64,), jnp.int32),
               "v": jnp.ones((64,), jnp.float32)},
              jnp.asarray(0, jnp.int32))
    out = k.group_aggregate(b, ["k"], aggs)
    assert int(out.count) == 0
    b1 = Batch({"k": jnp.full((64,), 7, jnp.int32),
                "v": jnp.ones((64,), jnp.float32)},
               jnp.asarray(5, jnp.int32))
    o1 = k.group_aggregate(b1, ["k"], aggs)
    assert int(o1.count) == 1
    assert int(np.asarray(o1.columns["k"])[0]) == 7
    assert int(np.asarray(o1.columns["n"])[0]) == 5
    assert float(np.asarray(o1.columns["s"])[0]) == 5.0


def test_smallkey_matmul_nan_padding():
    """Padding rows holding inf/NaN must not contaminate group sums
    (0 x NaN = NaN in the one-hot contraction)."""
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels as k

    v = np.full(64, np.nan, np.float32)
    v[:5] = [1.0, 2.0, 3.0, 4.0, 5.0]
    kk = np.full(64, 9, np.int32)
    b = Batch({"k": jnp.asarray(kk), "v": jnp.asarray(v)},
              jnp.asarray(5, jnp.int32))
    out = k.group_aggregate(b, ["k"], {"s": ("sum", "v")})
    assert int(out.count) == 1
    assert float(np.asarray(out.columns["s"])[0]) == 15.0


def _short_keys_table(case, n, rng):
    """Key columns of one short-key set (the rows' first few hold each
    key's extremes, so the live spans are exactly the ones named) and
    two float columns."""
    def spread(lo, hi, dtype):
        v = rng.randint(lo, hi + 1, n).astype(dtype)
        v[:2] = (lo, hi)
        return v

    def chars(values):
        out = [values[i] for i in rng.randint(0, len(values), n)]
        out[:len(values)] = values
        return out

    if case == "two-strings":      # Q1's CHAR(1) pair, one key empty
        t = {"f": chars([b"", b"A", b"N"]), "g": chars([b"N", b"O"])}
    elif case == "bool-int8":
        t = {"b": spread(0, 1, np.bool_), "i": spread(-5, 5, np.int8)}
    elif case == "slots-512":      # spans 2 x 16 x 16
        t = {"b": spread(0, 1, np.bool_), "h": spread(-8, 7, np.int16),
             "c": chars([bytes([c]) for c in range(ord("A"), ord("Q"))])}
    else:                          # "slots-513": spans 3 x 9 x 19
        t = {"i": spread(-1, 1, np.int8), "c": chars([b"", b"\x07"]),
             "u": spread(5, 23, np.uint8)}
    keys = list(t)
    t["x"] = rng.uniform(1, 100, n).astype(np.float32)
    t["y"] = rng.uniform(0.5, 2, n).astype(np.float32)
    return t, keys


def _host_rows(table, keys):
    cols = [table[k] for k in keys]
    return [tuple(c[i] if isinstance(c[i], bytes) else int(c[i])
                  for c in cols) for i in range(len(cols[0]))]


@pytest.mark.parametrize("case,one_hot", [
    ("two-strings", True), ("bool-int8", True), ("slots-512", True),
    ("slots-513", False)])
def test_smallkey_short_keys_match_the_boundary_path(case, one_hot):
    """Short keys (bools, integers of at most 16 bits, one-byte strings)
    share one mixed-radix slot: the groups come out in the keys'
    lexicographic order, each equal to the boundary lowering's group of
    the same key (whose order is its key hash's) — keys and counts
    exact, sums within 1e-6.  Spans that multiply past the slots run the
    boundary lowering itself, row for row."""
    import jax
    from dryad_tpu.data import batch_from_numpy, batch_to_numpy
    from dryad_tpu.ops import kernels as k

    cap, n = 4096, 4000
    table, keys = _short_keys_table(case, cap, np.random.RandomState(9))
    b = batch_from_numpy(table, capacity=cap, str_max_len=1).with_count(n)
    aggs = {"n": ("count", None), "s": ("sum", "x"), "m": ("mean", "y")}
    assert k._matmul_group_eligible(b, keys, aggs)

    @jax.jit
    def both(b):
        with k.onehot_tally() as ran:
            got = k.group_aggregate(b, keys, aggs)
        return got, k._group_aggregate_boundary(b, keys, aggs, None), ran[0]

    got, ref, ran = both(b)
    assert bool(ran) == one_hot
    got, ref = batch_to_numpy(got), batch_to_numpy(ref)
    gk, rk = _host_rows(got, keys), _host_rows(ref, keys)
    if one_hot:
        assert gk == sorted(gk)
        at = sorted(range(len(rk)), key=rk.__getitem__)
        ref = {c: [v[i] for i in at] if isinstance(v, list) else v[at]
               for c, v in ref.items()}
        rk = [rk[i] for i in at]
    assert gk == rk
    # every live row is in a group, and the groups are the host's
    want = {}
    for i, key in enumerate(_host_rows(table, keys)[:n]):
        want[key] = want.get(key, 0) + 1
    assert dict(zip(gk, got["n"].tolist())) == want
    np.testing.assert_array_equal(got["n"], ref["n"])
    assert got["n"].dtype == np.int32
    for c in ("s", "m"):
        np.testing.assert_allclose(got[c], ref[c], rtol=1e-6)


def test_wide_keys_trace_the_boundary_program():
    """Three 32-bit keys (TPC-H Q3's group) fail the one-hot gate from
    their dtypes alone: no span check, no ``cond``, the boundary
    lowering's program whatever the data."""
    import jax
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels as k

    keys = ["a", "b", "c"]
    aggs = {"n": ("count", None), "s": ("sum", "x")}

    def batch(seed):
        rng = np.random.RandomState(seed)
        cols = {c: jnp.asarray(rng.randint(0, 3, 512).astype(np.int32))
                for c in keys}
        cols["x"] = jnp.asarray(rng.rand(512).astype(np.float32))
        return Batch(cols, jnp.asarray(500, jnp.int32))

    def prims(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from prims(inner)

    assert not k._matmul_group_eligible(batch(0), keys, aggs)
    traced = [jax.make_jaxpr(lambda b: k.group_aggregate(b, keys, aggs))(
        batch(s)) for s in (0, 1)]
    assert "cond" not in set(prims(traced[0].jaxpr))
    boundary = jax.make_jaxpr(lambda b: k._group_aggregate_boundary(
        b, keys, aggs, None))(batch(2))
    assert str(traced[0]) == str(traced[1]) == str(boundary)


def test_smallkey_sums_are_compensated(monkeypatch):
    """The chunk sums of a one-hot group-by accumulate in a compensated
    pair: over 1,024 chunks, where one plain ``float32`` accumulator (the
    lowering's before) is off by more than 1e-6 — 5.8e-6 here: every
    line of the column has one price, so every chunk adds the same sum
    and its roundings all lean one way — the group sums stay within 1e-7
    of float64; counts are exact ``int32``."""
    import jax
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels as k

    chunk, nb = 64, 1024
    monkeypatch.setattr(k, "_SMALLKEY_CHUNK", chunk)
    n = chunk * nb
    key = (np.arange(n) % 4).astype(np.int32)
    x = np.full(n, 38_000.3, np.float32)
    b = Batch({"k": jnp.asarray(key), "x": jnp.asarray(x)},
              jnp.asarray(n, jnp.int32))
    out = jax.jit(lambda b: k.group_aggregate(
        b, ["k"], {"s": ("sum", "x"), "n": ("count", None)}))(b)
    assert int(out.count) == 4
    got_k = np.asarray(out.columns["k"])[:4]
    got_s = np.asarray(out.columns["s"])[:4]
    got_n = np.asarray(out.columns["n"])[:4]
    assert got_n.dtype == np.int32
    for g, s, c in zip(got_k, got_s, got_n):
        rows = x[key == g]
        assert c == len(rows)
        exact = rows.astype(np.float64).sum()
        # the plain accumulator: each chunk's sum (in float64, then
        # rounded, as the chunk's matmul gives it) added into one float32
        plain = np.float32(0)
        for part in np.where(key == g, x, 0).reshape(nb, chunk) \
                .astype(np.float64).sum(axis=1).astype(np.float32):
            plain = np.float32(plain + part)
        assert abs(float(plain) - exact) > 1e-6 * exact, (g, plain, exact)
        assert abs(float(s) - exact) <= 1e-7 * exact, (g, s, exact)


def test_tokenize_group_count_matches_unfused():
    """The fused SelectMany+GroupBy+Count equals split_tokens + lower +
    group_aggregate on real text, including the NEED channel."""
    import collections
    from dryad_tpu.data.columnar import batch_from_numpy
    from dryad_tpu.ops.text import tokenize_group_count

    rng = np.random.RandomState(6)
    words = ["Apple", "fig", "KIWI", "pear-x", "plum", "a"]
    lines = [" ".join(words[j] for j in rng.randint(0, 6, rng.randint(1, 9)))
             for _ in range(800)]
    lines[5] = ""                       # empty line
    lines[6] = "   "                    # delimiters only
    b = batch_from_numpy({"line": lines}, str_max_len=64)
    out, need = tokenize_group_count(b, "line", out_capacity=8192,
                                     vocab_capacity=256, count_name="n",
                                     lower=True)
    assert int(need) == 0
    ref = collections.Counter(w.lower() for ln in lines for w in ln.split())
    ng = int(out.count)
    assert ng == len(ref)
    got = {}
    tc = out.columns["line"]
    for i in range(ng):
        L = int(np.asarray(tc.lengths)[i])
        got[bytes(np.asarray(tc.data)[i, :L]).decode()] = \
            int(np.asarray(out.columns["n"])[i])
    assert got == dict(ref)


def test_tokenize_group_count_vocab_overflow_need():
    from dryad_tpu.data.columnar import batch_from_numpy
    from dryad_tpu.ops.text import tokenize_group_count

    lines = [f"w{i}" for i in range(64)]   # 64 distinct tokens
    b = batch_from_numpy({"line": lines}, str_max_len=8)
    out, need = tokenize_group_count(b, "line", out_capacity=256,
                                     vocab_capacity=16, count_name="n")
    assert int(need) > 0                   # vocabulary didn't fit
    out2, need2 = tokenize_group_count(b, "line", out_capacity=256,
                                       vocab_capacity=128, count_name="n")
    assert int(need2) == 0 and int(out2.count) == 64


def test_executor_fuses_tokens_group():
    """The peephole rewrites [flat_tokens, count-group] and the fused
    query answers identically through the public API."""
    import collections
    from dryad_tpu import Context
    from dryad_tpu.exec.executor import _fuse_stage_ops
    from dryad_tpu.plan.stages import StageOp

    ops = [StageOp("flat_tokens", {"column": "line", "out_capacity": 1024,
                                   "max_token_len": 24, "delims": b" ",
                                   "lower": True}),
           StageOp("group", {"keys": ["line"],
                             "aggs": {"n": ("count", None)}})]
    fused = _fuse_stage_ops(ops)
    assert [o.kind for o in fused] == ["tokens_group_count"]
    # non-matching shapes stay unfused
    ops2 = [ops[0], StageOp("group", {"keys": ["line"],
                                      "aggs": {"s": ("sum", "x")}})]
    assert [o.kind for o in _fuse_stage_ops(ops2)] == \
        ["flat_tokens", "group"]

    ctx = Context()
    lines = ["b a a", "c B b", "a"] * 50
    q = (ctx.from_columns({"line": lines}, str_max_len=16)
         .split_words("line", out_capacity=2048, lower=True)
         .group_by(["line"], {"n": ("count", None)}))
    got = q.collect()
    ref = collections.Counter(w.lower() for ln in lines for w in ln.split())
    res = {}
    for i, w in enumerate(got["line"]):
        w = w.decode() if isinstance(w, bytes) else str(w)
        res[w] = int(np.asarray(got["n"])[i])
    assert res == dict(ref)


def _seq(kinds):
    """A stage-op list from short names: ``filter`` / ``fn`` carry SQL
    row-expression programs, ``filter!`` / ``fn!`` opaque lambdas."""
    from dryad_tpu.plan.stages import StageOp
    from dryad_tpu.sql.rowexpr import Predicate, Projector
    make = {
        "filter": lambda: StageOp("filter", {"fn": Predicate(
            ["bin", "<", ["col", "x"], ["lit", 5, "int"]])}),
        "filter!": lambda: StageOp("filter",
                                   {"fn": lambda c: c["x"] < 5}),
        "fn": lambda: StageOp("fn", {"fn": Projector(
            {"x": ["col", "x"], "k": ["col", "k"]})}),
        "fn!": lambda: StageOp("fn", {"fn": lambda c: dict(c)}),
        "group": lambda: StageOp("group", {"keys": ["k"],
                                           "aggs": {"s": ("sum", "x")}}),
        "sort": lambda: StageOp("sort", {"keys": [("k", False)]}),
        "take": lambda: StageOp("take", {"n": 3}),
        "distinct": lambda: StageOp("distinct", {"keys": ["k"]}),
        "dgroup_local": lambda: StageOp("dgroup_local", {
            "keys": ["k"], "decs": {}, "box": None}),
    }
    return [make[k]() for k in kinds]


@pytest.mark.parametrize("kinds,fused", [
    # a filter that feeds a group-by through row-wise ops is its mask
    (["filter", "group"], ["where_group"]),
    (["filter!", "group"], ["where_group"]),        # nothing between
    (["fn", "filter", "fn", "fn", "group", "fn", "sort"],
     ["fn", "where_group", "fn", "sort"]),          # Q1's stage
    (["filter", "fn", "filter", "group"], ["where_group"]),
    (["filter", "filter", "group"], ["where_group"]),
    # an opaque function behind the filter may look at positions
    (["filter", "fn!", "group"], ["filter", "fn!", "group"]),
    # ... but the filter behind IT feeds the group through nothing
    (["filter", "fn!", "filter!", "group"],
     ["filter", "fn!", "where_group"]),
    (["filter", "filter!", "group"], ["filter", "where_group"]),
    # everything else behind a filter wants the compacted batch
    (["filter", "sort"], ["filter", "sort"]),
    (["filter", "take"], ["filter", "take"]),
    (["filter", "distinct"], ["filter", "distinct"]),
    (["filter", "dgroup_local"], ["filter", "dgroup_local"]),
    (["fn", "filter", "fn"], ["fn", "filter", "fn"]),   # a join's leg
    (["filter", "fn", "sort", "group"], ["filter", "fn", "sort", "group"]),
    (["fn", "group"], ["fn", "group"]),
])
def test_which_filters_become_a_group_mask(kinds, fused):
    from dryad_tpu.exec.executor import _fuse_stage_ops
    ops = _seq(kinds)
    out = _fuse_stage_ops(ops)
    assert [o.kind for o in out] == [k.rstrip("!") for k in fused]
    for o in out:
        if o.kind == "where_group":
            # the plan's own ops, in the plan's order, group excluded
            steps = o.params["steps"]
            i = ops.index(steps[0])
            assert steps == ops[i:i + len(steps)]
            assert steps[0].kind == "filter"
            assert o.params["group"] is ops[i + len(steps)]
            assert o.params["group"].kind == "group"
    assert [o.kind for o in ops] == [k.rstrip("!") for k in kinds]  # no edit


@pytest.mark.parametrize("kinds", [["filter", "group"],
                                   ["filter", "fn", "filter", "group"],
                                   ["filter!", "group"],
                                   ["filter", "fn!", "group"]])
def test_masked_and_compacted_stage_ops_answer_alike(kinds):
    """The fused op list against the plan's own, op by op through
    ``_apply_op``, over a batch whose padding rows hold NaN."""
    from dryad_tpu.data.columnar import batch_from_numpy, batch_to_numpy
    from dryad_tpu.exec.executor import _apply_op, _fuse_stage_ops
    rng = np.random.default_rng(5)
    x = rng.integers(0, 10, 64).astype(np.float32)
    x[48:] = np.nan
    b = batch_from_numpy({"x": x, "k": rng.integers(0, 1000, 64)
                          .astype(np.int32) * 7919}).with_count(48)
    ops = _seq(kinds)

    def run(seq):
        cur = b
        for op in seq:
            cur, _needs = _apply_op(cur, op, 1, [])
        t = batch_to_numpy(cur)
        return dict(zip(t["k"].tolist(), t["s"].tolist()))

    want = {}
    for k, v in zip(np.asarray(b["k"])[:48].tolist(), x[:48].tolist()):
        if v < 5:
            want[k] = want.get(k, 0.0) + v
    assert run(_fuse_stage_ops(ops)) == run(ops) == want


def test_sql_plans_keep_their_names_and_fingerprints():
    """Plans ship unfused: the stage program names and the fingerprints
    of Q1, Q3 and Q6 on one partition are what they were before the
    peephole learned the pattern (pinned from the parent commit), so
    compile-cache keys, cluster shipping and traces' module names stay."""
    import hashlib

    import jax

    from dryad_tpu import sql
    from dryad_tpu.api.dataset import Context
    from dryad_tpu.exec.executor import (_filter_counts,
                                         stage_program_name)
    from dryad_tpu.parallel.mesh import make_mesh
    from dryad_tpu.plan.planner import plan_query
    from test_sql_tpch_join import Q1, Q3, Q6, _catalog, _tables_q1

    ctx = Context(mesh=make_mesh(jax.devices()[:1]))
    cat = _catalog(_tables_q1())
    pinned = {
        "Q1": (Q1, ["stage_output_fn_filter_fn_group_fn_sort"],
               "066956d4ca173f14", (1, 0)),
        "Q3": (Q3, ["stage_join_fn_filter_fn_filter_fn_join",
                    "stage_join_fn_filter_fn_join",
                    "stage_output_fn_group_fn_sort_take"],
               "7dc8824c480b75ca", (0, 3)),
        "Q6": (Q6, ["stage_output_fn_filter_fn_group_fn"],
               "0c3720dc4eba0949", (1, 0)),
    }
    for name, (text, names, digest, counts) in pinned.items():
        g = plan_query(sql.query(ctx, cat, text).node, ctx.nparts,
                       config=ctx.config)
        assert [stage_program_name(s) for s in g.stages] == names, name
        fp = "\n".join(s.fingerprint() for s in g.stages)
        assert hashlib.sha256(fp.encode()).hexdigest()[:16] == digest, name
        got = [_filter_counts(s) for s in g.stages]
        assert (sum(c.get("filters_masked", 0) for c in got),
                sum(c.get("filters_compacted", 0) for c in got)) == counts


def test_tokenize_letter_delims_match_unfused():
    """Letter delimiters + lower: classification must see RAW bytes on
    both paths (review finding: lowering before classification split
    'aXb' differently across the fused/unfused lowerings)."""
    import collections
    from dryad_tpu.data.columnar import batch_from_numpy
    from dryad_tpu.ops.text import (lower_ascii, split_tokens,
                                    tokenize_group_count)
    from dryad_tpu.data.columnar import Batch

    lines = ["aXb CXd", "eXf", "gh"]
    b = batch_from_numpy({"line": lines}, str_max_len=16)
    toks, _ = split_tokens(b, "line", out_capacity=64, delims=b" X")
    lc = lower_ascii(toks.columns["line"])
    unfused = collections.Counter()
    for i in range(int(toks.count)):
        L = int(np.asarray(lc.lengths)[i])
        unfused[bytes(np.asarray(lc.data)[i, :L]).decode()] += 1
    out, need = tokenize_group_count(b, "line", out_capacity=64,
                                     vocab_capacity=32, count_name="n",
                                     delims=b" X", lower=True)
    fused = {}
    tc = out.columns["line"]
    for i in range(int(out.count)):
        L = int(np.asarray(tc.lengths)[i])
        fused[bytes(np.asarray(tc.data)[i, :L]).decode()] = \
            int(np.asarray(out.columns["n"])[i])
    assert fused == dict(unfused)
    assert int(need) == 0


def test_lookup_join_matches_general():
    """right_unique joins (merge-fill path) equal the general hash_join
    for inner and left, including unmatched-left zero fill; a duplicated
    right side runtime-falls-back to the general path."""
    from dryad_tpu.data.columnar import Batch, batch_from_numpy
    from dryad_tpu.ops import kernels as k

    rng = np.random.RandomState(7)
    nl, nr = 3_000, 400
    lk = rng.randint(0, 500, nl).astype(np.int32)   # some keys unmatched
    left = Batch({"k": jnp.asarray(lk),
                  "a": jnp.asarray(rng.randn(nl).astype(np.float32))},
                 jnp.asarray(nl - 9, jnp.int32))
    right = Batch({"k": jnp.asarray(np.arange(nr, dtype=np.int32)),
                   "lab": jnp.asarray(rng.randint(0, 99, nr)
                                      .astype(np.int32))},
                  jnp.asarray(nr, jnp.int32))

    def rows(b):
        n = int(b.count)
        return sorted(
            (int(np.asarray(b.columns["k"])[i]),
             round(float(np.asarray(b.columns["a"])[i]), 5),
             int(np.asarray(b.columns["lab"])[i])) for i in range(n))

    for how in ("inner", "left"):
        gen, gneed = k.hash_join(left, right, ["k"], ["k"], 6000, how=how)
        fast, fneed = k.hash_join(left, right, ["k"], ["k"], 6000,
                                  how=how, right_unique=True)
        assert rows(gen) == rows(fast), how
        assert int(gneed) == int(fneed) == 0

    # duplicate right keys: hint present, runtime falls back — result
    # must still match the general path (with its multi-match expansion)
    rdup = Batch({"k": jnp.asarray((np.arange(nr) // 2).astype(np.int32)),
                  "lab": jnp.asarray(np.arange(nr, dtype=np.int32))},
                 jnp.asarray(nr, jnp.int32))
    gen, _ = k.hash_join(left, rdup, ["k"], ["k"], 12_000)
    fast, _ = k.hash_join(left, rdup, ["k"], ["k"], 12_000,
                          right_unique=True)
    assert rows(gen) == rows(fast)


def test_lookup_join_string_payload():
    from dryad_tpu.data.columnar import batch_from_numpy
    from dryad_tpu.ops import kernels as k

    left = batch_from_numpy({"k": np.array([3, 1, 2, 1], np.int32),
                             "v": np.array([10, 20, 30, 40], np.int32)})
    right = batch_from_numpy({"k": np.array([1, 2, 3], np.int32),
                              "name": ["one", "two", "three"]},
                             str_max_len=8)
    out, need = k.hash_join(left, right, ["k"], ["k"], 16,
                            right_unique=True)
    assert int(need) == 0 and int(out.count) == 4
    got = {}
    nc = out.columns["name"]
    for i in range(4):
        L = int(np.asarray(nc.lengths)[i])
        got[int(np.asarray(out.columns["v"])[i])] = \
            bytes(np.asarray(nc.data)[i, :L]).decode()
    assert got == {10: "three", 20: "one", 30: "two", 40: "one"}


def test_exact_first_wave_probe_equivalence():
    """A pure repartition with the counts probe forced on (min_mb=0)
    equals the structural-slack run (-1 disables), on the 8-device
    mesh — the exact-first-wave path changes wire sizing only."""
    from dryad_tpu import Context
    from dryad_tpu.utils.config import JobConfig

    rng = np.random.RandomState(8)
    k = rng.randint(0, 5_000, 20_000).astype(np.int32)
    v = rng.randint(0, 1 << 30, 20_000).astype(np.int32)

    def run(min_mb):
        ctx = Context(config=JobConfig(exchange_probe_min_mb=min_mb))
        q = (ctx.from_columns({"k": k, "v": v})
             .hash_partition(["k"])
             .group_by(["k"], {"n": ("count", None), "s": ("sum", "v")}))
        out = q.collect()
        order = np.argsort(np.asarray(out["k"]))
        return {c: np.asarray(out[c])[order] for c in ("k", "n", "s")}

    a = run(-1.0)
    b = run(0.0)
    for c in ("k", "n", "s"):
        np.testing.assert_array_equal(a[c], b[c])


# ---------------------------------------------------------------------------
# exchange pack/unpack: slot_expand / slot_compact


def _oracle_expand(words, offsets, counts, C):
    D = len(offsets)
    out = np.zeros((D * C, words.shape[1]), np.uint32)
    for d in range(D):
        c = min(int(counts[d]), C)
        out[d * C:d * C + c] = words[int(offsets[d]):int(offsets[d]) + c]
    return out


def _slot_layouts(rng, cap, D):
    """Adversarial count layouts: balanced cuts (incl. empty runs), the
    all-one-bucket skew, and sparse partial fills."""
    cuts = np.sort(rng.randint(0, cap + 1, D - 1))
    balanced = np.diff(np.concatenate([[0], cuts, [cap]]))
    skew = np.zeros(D, np.int64)
    skew[rng.randint(D)] = cap
    sparse = rng.randint(0, max(cap // D, 1) + 1, D)
    return [balanced, skew, sparse]


@pytest.mark.parametrize("mode", _modes())
def test_slot_expand_matches_oracle(mode):
    """Valid slots (j < counts[d]) of every destination block equal the
    dest-sorted run — including runs starting past cap-C (the last
    destination of a FULL buffer: a start down-clamp would ship another
    destination's rows) and empty runs."""
    rng = np.random.RandomState(10)
    for D, C, cap, W in [(4, 16, 64, 3), (8, 8, 96, 1), (2, 32, 32, 4),
                         (5, 16, 61, 2)]:   # 61: non-multiple length
        for counts in _slot_layouts(rng, cap, D):
            counts = counts.astype(np.int32)
            offsets = (np.cumsum(counts) - counts).astype(np.int32)
            words = rng.randint(0, 1 << 30, (cap, W)).astype(np.uint32)
            ref = _oracle_expand(words, offsets, counts, C)
            got = np.asarray(_run(mode, lambda: slot_expand(
                jnp.asarray(words), jnp.asarray(offsets), C)))
            for d in range(D):
                c = min(int(counts[d]), C)
                assert (got[d * C:d * C + c] ==
                        ref[d * C:d * C + c]).all(), (D, C, cap, d)


@pytest.mark.parametrize("mode", _modes())
def test_slot_compact_matches_oracle(mode):
    """The first min(total, out_rows) rows are the concatenated valid
    prefixes of the source blocks — exact truncation when out_rows <
    total, zero-extended Batch padding contract past the total."""
    rng = np.random.RandomState(11)
    for D, C, W in [(4, 16, 2), (8, 8, 1), (3, 32, 3)]:
        for trial in range(4):
            counts = np.minimum(rng.randint(0, C + 1, D), C) \
                .astype(np.int32)
            if trial == 1:
                counts[:] = 0
                counts[rng.randint(D)] = C      # one full block
            recv = rng.randint(0, 1 << 30, (D * C, W)).astype(np.uint32)
            total = int(counts.sum())
            dense = (np.concatenate(
                [recv[s * C:s * C + counts[s]] for s in range(D)])
                if total else np.zeros((0, W), np.uint32))
            for out_rows in {max(total, C), total + C,
                             max(total - 3, C), C}:
                got = np.asarray(_run(mode, lambda: slot_compact(
                    jnp.asarray(recv), jnp.asarray(counts), C,
                    out_rows)))
                m = min(total, out_rows)
                assert (got[:m] == dense[:m]).all(), \
                    (D, C, trial, out_rows)


@pytest.mark.parametrize("mode", _modes())
def test_slot_roundtrip(mode):
    """expand -> (block transpose = simulated all_to_all) -> compact
    round-trips every row to the right destination, D x D shards."""
    rng = np.random.RandomState(12)
    D, C, cap, W = 4, 16, 64, 2
    shard_words, shard_counts, shard_offsets = [], [], []
    for _s in range(D):
        counts = _slot_layouts(rng, cap, D)[2].astype(np.int32)
        offsets = (np.cumsum(counts) - counts).astype(np.int32)
        shard_counts.append(counts)
        shard_offsets.append(offsets)
        shard_words.append(
            rng.randint(0, 1 << 30, (cap, W)).astype(np.uint32))
    sends = [np.asarray(_run(mode, lambda: slot_expand(
        jnp.asarray(shard_words[s]), jnp.asarray(shard_offsets[s]), C)))
        for s in range(D)]
    for d in range(D):   # receiver d gets block d of every sender
        recv = np.concatenate([sends[s][d * C:(d + 1) * C]
                               for s in range(D)])
        rc = np.array([min(int(shard_counts[s][d]), C)
                       for s in range(D)], np.int32)
        got = np.asarray(_run(mode, lambda: slot_compact(
            jnp.asarray(recv), jnp.asarray(rc), C, cap)))
        ref = np.concatenate(
            [shard_words[s][shard_offsets[s][d]:
                            shard_offsets[s][d] + rc[s]]
             for s in range(D)])
        assert (got[:len(ref)] == ref).all(), d


def test_exchange_pack_ab_mixed_dtypes():
    """End-to-end A/B: the packed-sort + slot-DMA exchange lowering
    (force_interpret routes it onto this CPU backend, real kernel
    bodies) vs the pre-kernel gather lowering (the non-TPU default,
    also DRYAD_NO_SORT_OPT=1) produce identical rows through a real
    repartition + group over a dtype mix (i32 / f32 / i64 / string)."""
    import os
    from dryad_tpu import Context
    from dryad_tpu.utils.config import JobConfig

    rng = np.random.RandomState(13)
    n = 6_000
    cols = {
        "k": rng.randint(0, 700, n).astype(np.int32),
        "f": rng.rand(n).astype(np.float32),
        "b": rng.randint(0, 1 << 40, n).astype(np.int64),
        "s": ["w%d" % (i % 97) for i in range(n)],
    }

    def run():
        ctx = Context(config=JobConfig(exchange_probe_min_mb=-1.0))
        q = (ctx.from_columns(cols)
             .hash_partition(["k"])
             .group_by(["k"], {"n": ("count", None), "mx": ("max", "f")}))
        out = q.collect()
        order = np.argsort(np.asarray(out["k"]))
        return {c: np.asarray(out[c])[order] for c in ("k", "n", "mx")}

    assert not os.environ.get("DRYAD_NO_SORT_OPT")
    with force_interpret():
        a = run()              # pack path, interpret-mode slot kernels
    b = run()                  # gather path (non-TPU backend default)
    np.testing.assert_array_equal(a["k"], b["k"])
    np.testing.assert_array_equal(a["n"], b["n"])
    np.testing.assert_allclose(a["mx"], b["mx"], rtol=0, atol=0)


def test_group_minmax_nan_lowering_divergence_pinned():
    """Regression-pins the documented NaN divergence (group_by docstring
    / group_aggregate NaN note): the scan path's jnp.minimum/maximum
    PROPAGATE any NaN into both extremes, while the boundary-carry path
    ranks by IEEE totalOrder (-NaN < -inf < ... < +inf < +NaN), so a
    +NaN surfaces only as the max and a -NaN only as the min.  NaN-free
    groups agree exactly either way."""
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels as k

    n = 16
    kcol = np.array([0] * 4 + [1] * 4 + [2] * 4 + [3] * 4, np.int32)
    v = np.array([1., 2., 3., 4.,
                  5., np.nan, 7., 8.,        # +NaN in group 1
                  9., -np.nan, 11., 12.,     # -NaN in group 2
                  13., 14., 15., 16.], np.float32)
    b = Batch({"k": jnp.asarray(kcol), "v": jnp.asarray(v)},
              jnp.asarray(n, jnp.int32))
    aggs = {"lo": ("min", "v"), "hi": ("max", "v")}
    ok, mm = k._boundary_eligible(b, aggs)
    assert ok and mm == "v"

    def rows(out):
        ng = int(out.count)
        kk = np.asarray(out.columns["k"])[:ng]
        o = np.argsort(kk)
        return (kk[o], np.asarray(out.columns["lo"])[:ng][o],
                np.asarray(out.columns["hi"])[:ng][o])

    bk, blo, bhi = rows(k._group_aggregate_boundary(b, ["k"], aggs, mm))
    sk, slo, shi = rows(k._group_aggregate_scan(b, ["k"], aggs))
    np.testing.assert_array_equal(bk, [0, 1, 2, 3])
    np.testing.assert_array_equal(sk, [0, 1, 2, 3])
    # NaN-free groups: exact agreement
    for arr, want in [(blo, [1., 13.]), (bhi, [4., 16.]),
                      (slo, [1., 13.]), (shi, [4., 16.])]:
        np.testing.assert_array_equal([arr[0], arr[3]], want)
    # boundary (totalOrder): +NaN is only the max, -NaN only the min
    assert blo[1] == 5.0 and np.isnan(bhi[1])
    assert np.isnan(blo[2]) and bhi[2] == 12.0
    # scan (jnp.minimum/maximum): NaN propagates to BOTH extremes
    assert np.isnan(slo[1]) and np.isnan(shi[1])
    assert np.isnan(slo[2]) and np.isnan(shi[2])


def test_sort_fused2_matches_general_and_oracle():
    """The runtime key-lane fusion (sort_by_columns 2-key path, TPU
    tier — force_interpret routes it here) agrees with the general
    3-lane sort AND a numpy lexsort oracle, over adversarial spans:
    small spans (fused branch), a span product past 2^32 (the runtime
    cond falls back INSIDE the compiled fn), negatives, descending,
    and a short valid prefix."""
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels as k

    rng = np.random.RandomState(14)
    n = 4_096
    cases = [
        (rng.randint(-500, 500, n), rng.randint(0, 1000, n)),    # fused
        (rng.randint(-(1 << 30), 1 << 30, n),
         rng.randint(0, 1 << 20, n)),                            # wide
        (np.zeros(n, np.int64), rng.randint(0, 3, n)),           # ties
    ]
    for ci, (a, b) in enumerate(cases):
        a = a.astype(np.int32 if ci != 2 else np.int64)
        b = b.astype(np.int32)
        v = rng.randint(0, 1 << 30, n).astype(np.int32)
        cnt = n - 13
        bt = Batch({"a": jnp.asarray(a), "b": jnp.asarray(b),
                    "v": jnp.asarray(v)}, jnp.asarray(cnt, jnp.int32))
        keys = [("a", False), ("b", ci == 1)]   # case 1: b descending
        with force_interpret():
            fused = k.sort_by_columns(bt, keys)
        general = k.sort_by_columns(bt, keys)   # cpu tier: 3-lane sort
        bs = b[:cnt] if ci != 1 else -b[:cnt].astype(np.int64)
        # stable key-only lexsort: ties keep original order, like the
        # stable carry sort (v is PAYLOAD, not a tiebreak)
        order = np.lexsort((bs, a[:cnt]))
        for name, src in (("a", a), ("b", b), ("v", v)):
            ref = src[:cnt][order]
            np.testing.assert_array_equal(
                np.asarray(fused.columns[name])[:cnt], ref,
                err_msg=f"case {ci} fused {name}")
            np.testing.assert_array_equal(
                np.asarray(general.columns[name])[:cnt], ref,
                err_msg=f"case {ci} general {name}")


def test_hash_join_packed_gather_ab():
    """hash_join's output materialization: the packed single-gather
    (TPU tier, force_interpret routes it here) and the per-column
    gather tier produce identical rows — strings and i64 included."""
    from dryad_tpu.data.columnar import batch_from_numpy
    from dryad_tpu.ops import kernels as k

    rng = np.random.RandomState(15)
    nl, nr = 3_000, 500
    lk = rng.randint(0, nr + 100, nl).astype(np.int32)   # some unmatched
    left = batch_from_numpy(
        {"k": lk,
         "s": ["L%d" % (i % 53) for i in range(nl)],
         "big": rng.randint(0, 1 << 40, nl).astype(np.int64)},
        str_max_len=8)
    right = batch_from_numpy(
        {"k": np.arange(nr, dtype=np.int32),
         "w": rng.rand(nr).astype(np.float32)}, str_max_len=8)

    def rows(out):
        ng = int(out.count)
        sc = out.columns["s"]
        ss = [bytes(np.asarray(sc.data)[i,
                    :int(np.asarray(sc.lengths)[i])]).decode()
              for i in range(ng)]
        return sorted(zip(np.asarray(out.columns["k"])[:ng].tolist(),
                          ss,
                          np.asarray(out.columns["big"])[:ng].tolist(),
                          np.asarray(out.columns["w"])[:ng].tolist()))

    with force_interpret():
        a, _ = k.hash_join(left, right, ["k"], ["k"], nl)
        a_rows = rows(a)
    b, _ = k.hash_join(left, right, ["k"], ["k"], nl)
    assert a_rows == rows(b)
    assert len(a_rows) == int((lk < nr).sum())
