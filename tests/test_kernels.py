"""Kernel-level tests against numpy oracles (the reference's LocalDebug-
oracle test pattern, SURVEY.md §4, applied at unit granularity)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dryad_tpu.data import Batch, batch_from_numpy, batch_to_numpy
from dryad_tpu.ops import kernels
from dryad_tpu.ops.hashing import hash_batch_keys
from dryad_tpu.ops.text import split_tokens, lower_ascii


def make_batch(n=100, cap=128, seed=0):
    rng = np.random.RandomState(seed)
    return batch_from_numpy({
        "k": rng.randint(0, 10, n),
        "v": rng.randn(n).astype(np.float32),
        "s": ["item%d" % x for x in rng.randint(0, 7, n)],
    }, capacity=cap)


def test_roundtrip():
    b = make_batch()
    out = batch_to_numpy(b)
    assert len(out["k"]) == 100
    assert out["s"][0].startswith(b"item")


def test_compact():
    b = make_batch()
    keep = jnp.asarray(np.asarray(b["k"]) % 2 == 0)
    out = kernels.compact(b, keep)
    ref_k = np.asarray(b["k"])[:100]
    ref_k = ref_k[ref_k % 2 == 0]
    got = batch_to_numpy(out)
    np.testing.assert_array_equal(got["k"], ref_k)


def test_hash_deterministic_and_spread():
    b = make_batch()
    h1 = hash_batch_keys(b, ["s"])
    h2 = hash_batch_keys(b, ["s"])
    np.testing.assert_array_equal(np.asarray(h1[0]), np.asarray(h2[0]))
    # equal strings hash equal; there are only 7 distinct values
    strs = batch_to_numpy(b)["s"]
    lo = np.asarray(h1[1])[:100]
    mapping = {}
    for s, h in zip(strs, lo):
        assert mapping.setdefault(s, h) == h
    assert len(set(mapping.values())) == len(mapping)


def test_sort_numeric_and_string():
    b = make_batch()
    out = kernels.sort_by_columns(b, [("v", False)])
    got = batch_to_numpy(out)["v"]
    np.testing.assert_allclose(got, np.sort(batch_to_numpy(b)["v"]), rtol=1e-6)

    out2 = kernels.sort_by_columns(b, [("s", False), ("v", True)])
    got2 = batch_to_numpy(out2)
    ref = sorted(zip(batch_to_numpy(b)["s"], batch_to_numpy(b)["v"]),
                 key=lambda t: (t[0], -t[1]))
    assert [r[0] for r in ref] == got2["s"]
    np.testing.assert_allclose([r[1] for r in ref], got2["v"], rtol=1e-6)


def test_group_aggregate():
    b = make_batch()
    out = kernels.group_aggregate(
        b, ["k"], {"n": ("count", None), "sv": ("sum", "v"),
                   "mn": ("min", "v"), "mx": ("max", "v"),
                   "avg": ("mean", "v")})
    got = batch_to_numpy(out)
    raw = batch_to_numpy(b)
    import collections
    groups = collections.defaultdict(list)
    for k, v in zip(raw["k"], raw["v"]):
        groups[int(k)].append(v)
    assert int(out.count) == len(groups)
    for i, k in enumerate(got["k"]):
        vals = groups[int(k)]
        assert got["n"][i] == len(vals)
        np.testing.assert_allclose(got["sv"][i], np.sum(vals), rtol=1e-5)
        np.testing.assert_allclose(got["mn"][i], np.min(vals), rtol=1e-6)
        np.testing.assert_allclose(got["mx"][i], np.max(vals), rtol=1e-6)
        np.testing.assert_allclose(got["avg"][i], np.mean(vals), rtol=1e-5)


def test_group_by_string_key():
    b = make_batch()
    out = kernels.group_aggregate(b, ["s"], {"n": ("count", None)})
    got = batch_to_numpy(out)
    raw = batch_to_numpy(b)
    import collections
    c = collections.Counter(raw["s"])
    assert int(out.count) == len(c)
    for s, n in zip(got["s"], got["n"]):
        assert c[s] == n


def test_distinct():
    b = make_batch()
    out = kernels.distinct(b, ["k"])
    got = batch_to_numpy(out)
    assert sorted(set(got["k"])) == sorted(set(batch_to_numpy(b)["k"]))
    assert int(out.count) == len(set(batch_to_numpy(b)["k"]))


def test_scalar_aggregate():
    b = make_batch()
    out = kernels.scalar_aggregate(
        b, {"n": ("count", None), "s": ("sum", "v"), "m": ("mean", "v"),
            "lo": ("min", "v"), "hi": ("max", "v")})
    raw = batch_to_numpy(b)["v"]
    assert int(out["n"]) == 100
    np.testing.assert_allclose(float(out["s"]), raw.sum(), rtol=1e-5)
    np.testing.assert_allclose(float(out["m"]), raw.mean(), rtol=1e-5)
    np.testing.assert_allclose(float(out["lo"]), raw.min(), rtol=1e-6)
    np.testing.assert_allclose(float(out["hi"]), raw.max(), rtol=1e-6)


def test_hash_join():
    rng = np.random.RandomState(1)
    left = batch_from_numpy({"k": rng.randint(0, 8, 50),
                             "a": np.arange(50)}, capacity=64)
    right = batch_from_numpy({"k": rng.randint(0, 8, 30),
                              "b": np.arange(30) * 10}, capacity=32)
    out, overflow = kernels.hash_join(left, right, ["k"], ["k"], 512)
    assert not bool(overflow)
    got = batch_to_numpy(out)
    lraw, rraw = batch_to_numpy(left), batch_to_numpy(right)
    expected = set()
    for i in range(50):
        for j in range(30):
            if lraw["k"][i] == rraw["k"][j]:
                expected.add((int(lraw["a"][i]), int(rraw["b"][j])))
    got_pairs = set(zip(got["a"].tolist(), got["b"].tolist()))
    assert got_pairs == expected
    assert int(out.count) == len(expected)  # a and b values are unique


def test_join_string_keys():
    left = batch_from_numpy({"w": ["a", "b", "c", "a"],
                             "x": [1, 2, 3, 4]}, capacity=8)
    right = batch_from_numpy({"w": ["a", "c", "d"],
                              "y": [10, 20, 30]}, capacity=4)
    out, overflow = kernels.hash_join(left, right, ["w"], ["w"], 32)
    got = batch_to_numpy(out)
    pairs = sorted(zip([s.decode() for s in got["w"]],
                       got["x"].tolist(), got["y"].tolist()))
    assert pairs == [("a", 1, 10), ("a", 4, 10), ("c", 3, 20)]


def test_concat2():
    a = batch_from_numpy({"x": [1, 2, 3], "s": ["p", "q", "r"]}, capacity=8)
    b = batch_from_numpy({"x": [4, 5], "s": ["tt", "u"]}, capacity=4)
    out = kernels.concat2(a, b)
    got = batch_to_numpy(out)
    assert got["x"].tolist() == [1, 2, 3, 4, 5]
    assert got["s"] == [b"p", b"q", b"r", b"tt", b"u"]


def test_split_tokens():
    b = batch_from_numpy(
        {"line": ["the quick brown fox", "", "the lazy dog  the"]},
        capacity=4, str_max_len=32)
    out, overflow = split_tokens(b, "line", out_capacity=16)
    assert not bool(overflow)
    got = batch_to_numpy(out)
    assert got["line"] == [b"the", b"quick", b"brown", b"fox",
                           b"the", b"lazy", b"dog", b"the"]
    # overflow probe: capacity smaller than token count flags and keeps the
    # first out_capacity tokens intact
    small, of2 = split_tokens(b, "line", out_capacity=4)
    assert bool(of2)
    got2 = batch_to_numpy(small)
    assert got2["line"] == [b"the", b"quick", b"brown", b"fox"]


def test_wordcount_composition():
    lines = ["the quick brown fox jumps over the lazy dog",
             "The dog barks", "a fox and a dog"]
    b = batch_from_numpy({"line": lines}, capacity=4, str_max_len=64)
    toks, _ = split_tokens(b, "line", out_capacity=64)
    toks = Batch({"line": lower_ascii(toks.columns["line"])}, toks.count)
    counts = kernels.group_aggregate(toks, ["line"], {"n": ("count", None)})
    got = batch_to_numpy(counts)
    import collections
    ref = collections.Counter(
        w.lower() for l in lines for w in l.split())
    assert {k.decode(): int(v) for k, v in zip(got["line"], got["n"])} == dict(ref)


def test_jit_composition():
    """A fused pipeline of kernels compiles to one XLA program."""
    b = make_batch()

    @jax.jit
    def stage(b):
        f = kernels.compact(b, b["v"] > 0)
        return kernels.group_aggregate(f, ["k"], {"n": ("count", None)})

    out = stage(b)
    raw = batch_to_numpy(b)
    import collections
    ref = collections.Counter(int(k) for k, v in zip(raw["k"], raw["v"]) if v > 0)
    got = batch_to_numpy(out)
    assert {int(k): int(n) for k, n in zip(got["k"], got["n"])} == dict(ref)


def test_pack_unpack_roundtrip():
    """Packed u32 word transport reassembles every column type exactly
    (strings, f32, i32, bool, trailing-dim arrays)."""
    import numpy as np

    import jax.numpy as jnp

    from dryad_tpu.data.columnar import Batch, StringColumn
    from dryad_tpu.ops.kernels import (_pack_columns_u32,
                                       _unpack_columns_u32)

    n = 17
    rng = np.random.RandomState(5)
    cols = {
        "s": StringColumn(jnp.asarray(rng.randint(0, 256, (n, 7), np.uint8)),
                          jnp.asarray(rng.randint(0, 8, n, np.int32))),
        "f": jnp.asarray(rng.randn(n).astype(np.float32)),
        "i": jnp.asarray(rng.randint(-5, 5, n, np.int32)),
        "b": jnp.asarray(rng.randint(0, 2, n).astype(bool)),
        "m": jnp.asarray(rng.randn(n, 3).astype(np.float32)),
    }
    lanes, spec = _pack_columns_u32(cols)
    out = _unpack_columns_u32(lanes, spec)
    assert np.array_equal(np.asarray(out["s"].data),
                          np.asarray(cols["s"].data))
    assert np.array_equal(np.asarray(out["s"].lengths),
                          np.asarray(cols["s"].lengths))
    for k in ("f", "i", "b", "m"):
        assert out[k].dtype == cols[k].dtype, k
        assert np.array_equal(np.asarray(out[k]), np.asarray(cols[k])), k


@pytest.mark.parametrize("live", [0, 1, 7, 8, 9, 50])
def test_permute_by_sort_wide_fallback(monkeypatch, live):
    """The lexsort+packed-gather fallback (rows wider than
    _VALOPS_MAX_WORDS) produces the same result as the value-carry path
    on the ``live`` valid rows — the rows its bounded gather fetches, a
    chunk of 8 a trip — and zeros behind them."""
    from dryad_tpu.data.columnar import Batch

    n = 50
    rng = np.random.RandomState(6)
    b = Batch({"k": jnp.asarray(rng.randint(0, 9, n, np.int32)),
               "v": jnp.asarray(rng.randn(n).astype(np.float32))},
              jnp.asarray(live, jnp.int32))
    want = kernels.sort_by_columns(b, [("k", False)])
    monkeypatch.setattr(kernels, "_VALOPS_MAX_WORDS", 0)
    monkeypatch.setattr(kernels, "_GATHER_CHUNK", 8)
    got = kernels.sort_by_columns(b, [("k", False)])
    assert np.array_equal(np.asarray(got.columns["k"]),
                          np.asarray(want.columns["k"]))
    assert np.allclose(np.asarray(got.columns["v"])[:live],
                       np.asarray(want.columns["v"])[:live])
    assert not np.asarray(got.columns["v"])[live:].any()


def test_pack_roundtrip_half_precision():
    """f16/bf16 columns survive packed transport BIT-exactly (a numeric
    widening would truncate fractions — code-review r4 finding)."""
    import numpy as np

    import jax.numpy as jnp

    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels

    n = 16
    rng = np.random.RandomState(9)
    k = jnp.asarray(rng.randint(0, 5, n, np.int32))
    h = jnp.asarray(rng.randn(n).astype(np.float16))
    bf = jnp.asarray(rng.randn(n).astype(np.float32)).astype(jnp.bfloat16)
    b = Batch({"k": k, "h": h, "bf": bf}, jnp.asarray(n, jnp.int32))
    out = kernels.sort_by_columns(b, [("k", False)])
    order = np.argsort(np.asarray(k), kind="stable")
    assert np.array_equal(np.asarray(out.columns["h"]),
                          np.asarray(h)[order])
    assert np.array_equal(
        np.asarray(out.columns["bf"].astype(jnp.float32)),
        np.asarray(bf.astype(jnp.float32))[order])
    assert out.columns["h"].dtype == jnp.float16
    assert out.columns["bf"].dtype == jnp.bfloat16


def test_sort_key_reconstruction_all_dtypes():
    """sort_by_columns rebuilds key columns from their sorted lanes instead
    of carrying them as packed values — verify bit-exact round-trips for
    every reconstructible key dtype, ascending and descending, with
    padding rows zeroed."""
    n, cap = 60, 64
    rng = np.random.RandomState(11)
    f = rng.randn(n).astype(np.float32) * 1e3
    f[:4] = [0.0, -0.0, np.inf, -np.inf]
    cols = {
        "f32": f,
        "i32": rng.randint(-(1 << 30), 1 << 30, n, np.int32),
        "i16": rng.randint(-30000, 30000, n).astype(np.int16),
        "u8": rng.randint(0, 255, n).astype(np.uint8),
        "b": (rng.randint(0, 2, n) > 0),
        "s": ["k%04d" % x for x in rng.randint(0, 500, n)],
    }
    b = batch_from_numpy(cols, capacity=cap)
    raw = batch_to_numpy(b)
    def sort_key(name):
        if name != "f32":
            return lambda i: raw[name][i]
        # the device sort uses the IEEE total order: -0.0 < +0.0
        bits = f.view(np.uint32)
        tot = np.where(bits >> 31 == 1, ~bits, bits | np.uint32(1 << 31))
        return lambda i: tot[i]

    for name in cols:
        for desc in (False, True):
            out = kernels.sort_by_columns(b, [(name, desc)])
            got = batch_to_numpy(out)
            order = sorted(range(n), key=sort_key(name), reverse=desc)
            for cname in cols:
                want = [raw[cname][i] for i in order]
                if cname == name or cname in ("f32",):
                    # key column itself must round-trip bit-exactly
                    np.testing.assert_array_equal(
                        np.asarray(got[cname]), np.asarray(want),
                        err_msg=f"key={name} desc={desc} col={cname}")
                else:
                    np.testing.assert_array_equal(got[cname], want)
            # padding rows of the reconstructed key are zeroed
            full = out.columns[name]
            from dryad_tpu.data.columnar import StringColumn
            if isinstance(full, StringColumn):
                assert int(np.asarray(full.lengths[n:]).max(initial=0)) == 0
            else:
                tail = np.asarray(full)[n:]
                assert not tail.any()


def test_sort_reconstruction_stability():
    """Equal keys preserve original row order (stable lax.sort) through
    the lane-reconstruction fast path."""
    n = 40
    k = np.asarray([i % 4 for i in range(n)], np.int32)
    v = np.arange(n, dtype=np.int32)
    b = batch_from_numpy({"k": k, "v": v}, capacity=48)
    out = batch_to_numpy(kernels.sort_by_columns(b, [("k", False)]))
    ref = sorted(range(n), key=lambda i: (k[i], i))
    np.testing.assert_array_equal(out["v"], v[ref])


# ---------------------------------------------------------------------------
# the gather behind a sort fetches the live rows (ISSUE 37): every kernel
# whose sorted rows past a count are padding, on the index sort + bounded
# gather (_VALOPS_MAX_ELEMS patched to 0, a chunk of 8 rows a trip),
# against its own value-carry path

_CHUNK, _CAP = 8, 29
_LIVES = (0, 1, _CHUNK - 1, _CHUNK, _CHUNK + 1, _CAP)
# past three quarters live the one whole take runs in the loop's place
_DENSE = _CAP - _CAP // 4
_JITS = {}


def _both_paths(monkeypatch, name, fn, *args):
    """``fn(*args)``, jitted, on the value-carry path and on the bounded
    gather path.  ``name`` names ``fn`` and every static choice in it: a
    program is traced once a name and path, whatever the counts."""
    outs = []
    for path in ("carry", "gather"):
        with monkeypatch.context() as m:
            if path == "gather":
                m.setattr(kernels, "_VALOPS_MAX_ELEMS", 0)
                m.setattr(kernels, "_GATHER_CHUNK", _CHUNK)
            if (name, path) not in _JITS:
                _JITS[name, path] = jax.jit(lambda *a, _fn=fn: _fn(*a))
            outs.append(jax.tree.map(np.asarray, _JITS[name, path](*args)))
    return outs


def _assert_live_rows(got, want, zeros=True, ordered=True):
    """Same count, same valid rows (row for row, or as a multiset where a
    kernel leaves their order to an unstable sort), zeros in every leaf
    past the count."""
    n = int(want.count)
    assert int(got.count) == n
    assert sorted(got.columns) == sorted(want.columns)

    def rows(b):
        leaves = [x.reshape(x.shape[0], -1)[:n].tolist()
                  for k in sorted(b.columns)
                  for x in jax.tree.leaves(b.columns[k])]
        out = list(zip(*leaves))
        return out if ordered else sorted(out)

    assert rows(got) == rows(want)
    if zeros:
        for k, col in got.columns.items():
            for g in jax.tree.leaves(col):
                assert not g[n:].any(), k


def _wide_batch(count, seed=0, cap=_CAP):
    """``cap`` rows of which ``count`` are valid; the padding rows hold
    data like any other (nothing may read it)."""
    from dryad_tpu.data.columnar import Batch, StringColumn
    rng = np.random.RandomState(seed)
    lens = rng.randint(1, 3, cap).astype(np.int32)
    s = rng.randint(97, 100, (cap, 5)).astype(np.uint8)
    s = np.where(np.arange(5)[None, :] < lens[:, None], s, 0).astype(np.uint8)
    return Batch({
        "k": jnp.asarray(rng.randint(0, 4, cap).astype(np.int32)),
        "f": jnp.asarray(rng.randint(-8, 8, cap).astype(np.float32)),
        "i": jnp.asarray(rng.randint(-2**30, 2**30, cap).astype(np.int32)),
        "s": StringColumn(jnp.asarray(s), jnp.asarray(lens)),
    }, jnp.asarray(count, jnp.int32))


def _mask_of(live, seed=3, cap=_CAP):
    return jnp.asarray(np.random.RandomState(seed).permutation(cap) < live)


@pytest.mark.parametrize("live", _LIVES + (_DENSE, _DENSE + 1))
def test_gather_live_rows_and_zeros(monkeypatch, live):
    """_gather_live itself: the take in rows [0, live), zeros behind, for
    a word matrix and for a 1-D source; the tally says what it fetched
    (whole chunks, or all of it where the whole take ran)."""
    rng = np.random.RandomState(1)
    src = jnp.asarray(rng.randint(1, 99, (_CAP, 3)).astype(np.uint32))
    idx = jnp.asarray(rng.permutation(_CAP).astype(np.int32))

    def fn(src, idx, n):
        with kernels.gather_tally() as tally:
            out = kernels._gather_live(src, idx, n)
            out1 = kernels._gather_live(src[:, 0] > 50, idx, n)
        return out, out1, sum(f for f, _ in tally), sum(c for _, c in tally)

    monkeypatch.setattr(kernels, "_GATHER_CHUNK", _CHUNK)
    out, out1, fetched, unbounded = jax.jit(fn)(src, idx, live)
    want = np.array(src)[np.asarray(idx)]
    want[live:] = 0
    np.testing.assert_array_equal(np.asarray(out), want)
    np.testing.assert_array_equal(np.asarray(out1), want[:, 0] > 50)
    trips = -(-live // _CHUNK)
    assert int(fetched) == 2 * (_CAP if live > _DENSE
                                else min(trips * _CHUNK, _CAP))
    assert int(unbounded) == 2 * _CAP
    # no count: the one take, and nothing tallied
    with kernels.gather_tally() as tally:
        full = kernels._gather_live(src, idx)
    np.testing.assert_array_equal(np.asarray(full),
                                  np.asarray(src)[np.asarray(idx)])
    assert tally == []


def test_gather_tally_leaves_out_a_cond_branch(monkeypatch):
    """A bounded gather traced inside a ``lax.cond`` branch works and is
    not tallied (its scalars cannot leave the branch)."""
    monkeypatch.setattr(kernels, "_GATHER_CHUNK", _CHUNK)
    src = jnp.arange(_CAP, dtype=jnp.uint32) + 1
    idx = jnp.arange(_CAP, dtype=jnp.int32)[::-1]

    def fn(src, idx, n):
        with kernels.gather_tally() as tally:
            out = jax.lax.cond(
                n > 3, lambda: kernels._gather_live(src, idx, n),
                lambda: jnp.zeros_like(src))
        return out, len(tally)

    out, tallied = jax.jit(fn)(src, idx, 5)
    assert tallied == 0
    np.testing.assert_array_equal(
        np.asarray(out)[:5], np.arange(_CAP, 0, -1, dtype=np.uint32)[:5])
    assert not np.asarray(out)[5:].any()


@pytest.mark.parametrize("live", _LIVES + (_DENSE, _DENSE + 1))
def test_bounded_compact(monkeypatch, live):
    b = _wide_batch(_CAP)
    want, got = _both_paths(monkeypatch, "compact", kernels.compact, b,
                            _mask_of(live))
    assert int(want.count) == live
    _assert_live_rows(got, want)


@pytest.mark.parametrize("live", _LIVES)
@pytest.mark.parametrize("keys", [
    [("k", False)], [("k", False), ("i", True)], [("s", False)],
    [("f", True)]], ids=["one", "two", "string", "descending"])
def test_bounded_sort_by_columns(monkeypatch, keys, live):
    want, got = _both_paths(
        monkeypatch, ("sort", str(keys)),
        lambda b: kernels.sort_by_columns(b, keys), _wide_batch(live))
    assert int(want.count) == live
    _assert_live_rows(got, want)


_BOUNDARY_AGGS = {
    "sum-mean": (["k", "s"], {"n": ("count", None), "sf": ("sum", "f"),
                              "mf": ("mean", "f")}),
    "min-max": (["k", "s"], {"mn": ("min", "i"), "mx": ("max", "i")}),
    "min-max-dense-key": (["k"], {"mn": ("min", "f"), "mx": ("max", "f"),
                                  "n": ("count", None)}),
    "sum64": (["k", "s"], {"s64": ("sum64", "i"), "si": ("sum", "i")}),
}


@pytest.mark.parametrize("live", _LIVES)
@pytest.mark.parametrize("where", [False, True], ids=["count", "where"])
@pytest.mark.parametrize("case", list(_BOUNDARY_AGGS))
def test_bounded_group_aggregate_boundary(monkeypatch, case, where, live):
    keys, aggs = _BOUNDARY_AGGS[case]
    b = _wide_batch(_CAP if where else live)
    assert kernels._boundary_eligible(b, aggs)[0]
    assert not kernels._matmul_group_eligible(b, keys, aggs)
    if where:
        fn = lambda b, m: kernels.group_aggregate(  # noqa: E731
            b, keys, aggs, where=m)
        args = (b, _mask_of(live))
    else:
        fn = lambda b: kernels.group_aggregate(b, keys, aggs)  # noqa: E731
        args = (b,)
    want, got = _both_paths(monkeypatch, ("group", case, where), fn, *args)
    assert (int(want.count) > 0) == (live > 0)
    _assert_live_rows(got, want)


@pytest.mark.parametrize("live", _LIVES)
@pytest.mark.parametrize("keys", [["k", "s"], None], ids=["keys", "rows"])
def test_bounded_distinct(monkeypatch, keys, live):
    want, got = _both_paths(monkeypatch, ("distinct", str(keys)),
                            lambda b: kernels.distinct(b, keys),
                            _wide_batch(live))
    assert int(want.count) <= live
    _assert_live_rows(got, want)


def _dimension(seed, str_len, count=6, cap=8):
    """A right side of ``cap`` rows, ``count`` valid, unique in ``rk`` and
    in ``rs`` (the keys of _wide_batch's ``k`` and ``s``)."""
    from dryad_tpu.data.columnar import Batch, StringColumn
    rng = np.random.RandomState(seed)
    names = [b"a", b"b", b"c", b"aa", b"ab", b"ca", b"bb", b"cc"]
    data = np.zeros((cap, str_len), np.uint8)
    for r, nm in enumerate(names):
        data[r, :len(nm)] = np.frombuffer(nm, np.uint8)
    lens = np.array([len(nm) for nm in names], np.int32)
    return Batch({
        "rk": jnp.asarray(np.arange(cap, dtype=np.int32)),
        "rs": StringColumn(jnp.asarray(data), jnp.asarray(lens)),
        "p": jnp.asarray(rng.randint(1, 1000, cap).astype(np.int32)),
        "f": jnp.asarray(rng.randint(1, 9, cap).astype(np.float32)),
    }, jnp.asarray(count, jnp.int32))


@pytest.mark.parametrize("live", _LIVES)
@pytest.mark.parametrize("how", ["inner", "left"])
@pytest.mark.parametrize("verify", ["bytes", "hash"])
def test_bounded_lookup_join(monkeypatch, verify, how, live):
    """_lookup_join: the union sort is bounded by both sides' counts and
    the closing compaction by the rows kept; ``need`` says an overflow of
    ``out_capacity`` (24 of the 29 left rows) the same."""
    if verify == "bytes":           # both keys pack alike: byte-verified
        lk, rk, right = ["k"], ["rk"], _dimension(2, 5)
    else:                           # max_len 5 against 7: hash-verified
        lk, rk, right = ["s"], ["rs"], _dimension(2, 7)
    left = _wide_batch(live)
    (want, wneed), (got, gneed) = _both_paths(
        monkeypatch, ("lookup", verify, how),
        lambda l, r: kernels._lookup_join(l, r, lk, rk, 24, "_r", how),
        left, right)
    assert int(gneed) == int(wneed)
    if how == "left":
        assert int(want.count) == min(live, 24)
        assert (int(wneed) > 0) == (live > 24)
    # the union's sort is unstable: the order of a key's left rows is
    # its own, and so is which of them an overflow cuts
    if int(wneed):
        assert int(got.count) == int(want.count) == 24
    else:
        _assert_live_rows(got, want, ordered=False)


@pytest.mark.parametrize("live", _LIVES)
@pytest.mark.parametrize("how", ["inner", "left", "right", "full"])
@pytest.mark.parametrize("tier", ["columns", "packed"])
def test_bounded_hash_join(monkeypatch, tier, how, live):
    """hash_join's general body: every gather over the output slots is
    bounded by the candidate pairs, the closing compaction by the
    verified ones; 24 slots overflow at 29 left rows (``need``).  Both
    tiers of _packed_gather: a gather a column, and (the TPU's, routed
    here by force_interpret) one gather of the packed word matrix."""
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import pallas_kernels
    if tier == "packed":
        monkeypatch.setattr(pallas_kernels, "_FORCE_INTERPRET", True)
    right = Batch({"rk": jnp.asarray(np.array([0, 0, 1, 2, 5, 5, 3, 3],
                                               np.int32)),
                   "p": jnp.arange(8, dtype=jnp.int32) + 10,
                   "f": jnp.arange(8, dtype=jnp.float32) + 0.5},
                  jnp.asarray(6, jnp.int32))
    (want, wneed), (got, gneed) = _both_paths(
        monkeypatch, ("hash_join", tier, how),
        lambda l, r: kernels.hash_join(l, r, ["k"], ["rk"], 24, how=how),
        _wide_batch(live), right)
    assert int(gneed) == int(wneed)
    if live == _CAP:
        assert int(wneed) > 24
    # right / full append the unmatched right rows through a concat that
    # may repeat a row past the count
    _assert_live_rows(got, want, zeros=how in ("inner", "left"))


# -- hash_join's search phase: merge-sort ranges, slot owners --------------

_SENT = 0xFFFFFFFF


def _search_case(case, rng):
    """(sorted right hashes with the sentinel past the valid rows, left
    hashes, left validity, how="left" synthetic rows?, out_capacity)."""
    n, m = 24, 40
    nval = {"empty_right": 0}.get(case, 18)
    rh = np.sort(rng.integers(0, 6, nval).astype(np.uint32))
    lh = rng.integers(0, 8, m).astype(np.uint32)
    lvalid = np.arange(m) < {"empty_left": 0}.get(case, 33)
    if case == "sentinel":      # valid rows of both sides hash to it
        rh[-2:] = _SENT
        lh[[3, 7]] = _SENT
    if case == "total_zero":
        lh = rng.integers(100, 200, m).astype(np.uint32)
    rkey = np.concatenate([rh, np.full(n - nval, _SENT, np.uint32)])
    total = np.where(lvalid, np.searchsorted(rkey, lh, "right")
                     - np.searchsorted(rkey, lh, "left"), 0).sum()
    oc = max(int(total) // 2, 1) if case == "overflow" else 96
    return rkey, lh, lvalid, case == "left_synth", oc


@pytest.mark.parametrize("budget", ["one_sort_back", "a_sort_a_bound"])
@pytest.mark.parametrize("case", ["duplicates", "sentinel", "empty_left",
                                  "empty_right", "total_zero", "overflow",
                                  "left_synth"])
def test_candidate_ranges_and_slot_owners(monkeypatch, case, budget):
    """The search phase against np.searchsorted: ``start`` / ``stop`` are
    its left / right sides over the sorted right hashes (sentinel
    padding included, as the old three-search phase had them), and the
    slot owners are ``searchsorted(cum, t, "right")`` at every slot below
    ``min(total, out_capacity)`` — the slots anything reads."""
    if budget == "a_sort_a_bound":
        monkeypatch.setattr(kernels, "_VALOPS_MAX_ELEMS", 0)
    rkey, lh, lvalid, synth, oc = _search_case(
        case, np.random.default_rng(len(case)))
    start, stop = jax.jit(kernels._candidate_ranges)(jnp.asarray(rkey),
                                                     jnp.asarray(lh))
    np.testing.assert_array_equal(start, np.searchsorted(rkey, lh, "left"))
    np.testing.assert_array_equal(stop, np.searchsorted(rkey, lh, "right"))
    mult = np.where(lvalid, np.asarray(stop) - np.asarray(start), 0)
    if synth:
        mult = np.where(lvalid & (mult == 0), 1, mult)
    cum = np.cumsum(mult).astype(np.int32)
    lid = jax.jit(kernels._slot_owners, static_argnums=2)(
        jnp.asarray(cum), jnp.asarray(mult.astype(np.int32)), oc)
    live = min(int(cum[-1]), oc)
    want = np.searchsorted(cum, np.arange(oc), "right")
    np.testing.assert_array_equal(np.asarray(lid)[:live], want[:live])
    assert {"empty_left": live == 0, "empty_right": live == 0,
            "total_zero": live == 0,
            "overflow": int(cum[-1]) > oc}.get(case, live > 0)


def _np_hash_join(left, right, how, oc):
    """hash_join's output by hand: slots in left-row order, each row's
    candidates in (hash, right row) order, a row of the left side with
    none of them one synthetic slot (left / full), the first ``oc`` slots
    kept where the keys are equal, then (right / full) the right rows no
    kept slot matched; and ``need``."""
    def h(b, keys):
        hi, lo = (np.asarray(x) for x in hash_batch_keys(b, keys))
        return hi ^ ((lo.astype(np.uint64) * 0x9E3779B9)
                     & 0xFFFFFFFF).astype(np.uint32)
    lh, rh = h(left, ["k"]), h(right, ["rk"])
    lk, la = np.asarray(left["k"]), np.asarray(left["a"])
    rk, rp = np.asarray(right["rk"]), np.asarray(right["p"])
    nl, nr = int(left.count), int(right.count)
    order = sorted(range(nr), key=lambda j: (rh[j], j))
    rkey = [rh[j] for j in order] + [_SENT] * (right.capacity - nr)
    slots = []
    for i in range(nl):
        cand = [s for s, key in enumerate(rkey) if key == lh[i]]
        if not cand and how in ("left", "full"):
            slots.append((i, None))
        slots += [(i, s) for s in cand]
    total = len(slots)
    rows, matched = [], set()
    for i, s in slots[:oc]:
        if s is None:
            rows.append((lk[i], la[i], 0))
        elif s < nr and lk[i] == rk[order[s]]:
            rows.append((lk[i], la[i], rp[order[s]]))
            matched.add(order[s])
    need = total if total > oc else 0
    if how in ("right", "full"):
        extra = [(rk[j], 0, rp[j]) for j in range(nr) if j not in matched]
        rows += extra
        need = total + len(extra) if total + len(extra) > oc else need
    return rows[:oc], need


@pytest.mark.parametrize("oc", [200, 30], ids=["fits", "overflows"])
@pytest.mark.parametrize("budget", ["one_sort_back", "a_sort_a_bound"])
@pytest.mark.parametrize("how", ["inner", "left", "right", "full"])
def test_hash_join_matches_numpy(monkeypatch, how, budget, oc):
    """hash_join's general body gives the numpy reference's rows, in its
    order, and its ``need``, whichever way the search phase sorts back."""
    if budget == "a_sort_a_bound":
        monkeypatch.setattr(kernels, "_VALOPS_MAX_ELEMS", 0)
    rng = np.random.default_rng(11)
    left = batch_from_numpy({"k": rng.integers(0, 10, 40).astype(np.int32),
                             "a": np.arange(40, dtype=np.int32) + 1},
                            capacity=48)
    right = batch_from_numpy({"rk": rng.integers(0, 8, 12).astype(np.int32),
                              "p": np.arange(12, dtype=np.int32) + 100},
                             capacity=16)
    out, need = jax.jit(lambda l, r: kernels.hash_join(
        l, r, ["k"], ["rk"], oc, how=how))(left, right)
    rows, want_need = _np_hash_join(left, right, how, oc)
    got = batch_to_numpy(out)
    assert int(out.count) == len(rows)
    assert list(zip(got["k"].tolist(), got["a"].tolist(),
                    got["p"].tolist())) == [tuple(map(int, r)) for r in rows]
    assert int(need) == want_need
    assert (want_need > 0) == (oc == 30)


def test_hash_join_search_phase_scatters_once():
    """An inner general hash_join's program holds two sorts and ONE
    scatter under the ``search`` scope (the slot owners' marks): no rank
    scatter of a sort-based searchsorted is left."""
    import re
    left = batch_from_numpy({"k": np.arange(40, dtype=np.int32) % 7,
                             "a": np.arange(40, dtype=np.int32)}, capacity=64)
    right = batch_from_numpy({"rk": np.arange(20, dtype=np.int32) % 5,
                              "p": np.arange(20, dtype=np.int32)}, capacity=32)
    text = jax.jit(lambda l, r: kernels.hash_join(
        l, r, ["k"], ["rk"], 128)).lower(left, right).compile().as_text()
    ops = [m.group(1) for ln in text.splitlines()
           if "/search/" in ln and (m := re.search(r"\s(scatter|sort)\(", ln))]
    assert sorted(ops) == ["scatter", "sort", "sort"]
    assert kernels.search_sort_rows(64, 32) == 2 * (64 + 32)
