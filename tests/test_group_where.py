"""``group_aggregate(batch, keys, aggs, where=mask)``: a filter in front of
a group-by is the group-by's row mask.  For each lowering (one-hot small
key, boundary carry, segmented scan) and key shape, the masked form
answers as ``group_aggregate(compact(batch, mask), ...)`` does and as the
sequential oracle does on the kept rows, whatever the dropped and the
padding rows hold; and it traces one sort fewer."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dryad_tpu.data import batch_from_numpy, batch_to_numpy
from dryad_tpu.oracle import run_oracle
from dryad_tpu.ops import kernels
from dryad_tpu.plan import expr as E

CAP, N = 256, 200           # rows of capacity, valid rows

# aggregate sets that select a lowering (asserted below from the
# lowerings' own static gates)
_AGGS = {
    "smallkey": {"s": ("sum", "x"), "m": ("mean", "y"), "n": ("count", None)},
    "boundary": {"s": ("sum", "x"), "m": ("mean", "y"), "n": ("count", None),
                 "lo": ("min", "x"), "hi": ("max", "x")},
    "scan": {"s": ("sum", "x"), "n": ("count", None),
             "lo": ("min", "x"), "hi": ("max", "y")},
}
_KEYS = {"dense": ["k"], "strings": ["f", "g"], "const": ["c"]}
# the one-hot path takes a single integer key, or short keys such as two
# one-byte strings (that is Q1); min/max send them to the sort paths
_CASES = [("smallkey", "dense"), ("smallkey", "const"),
          ("smallkey", "strings"),
          ("boundary", "dense"), ("boundary", "strings"),
          ("boundary", "const"),
          ("scan", "dense"), ("scan", "strings"), ("scan", "const")]
_MASKS = ["random", "none-kept", "all-kept", "padding-dropped"]


def _table(seed=7):
    rng = np.random.default_rng(seed)
    return {
        # wide span for the sort lowerings, narrow for the one-hot one
        "k": rng.integers(0, 12, CAP).astype(np.int32),
        "f": [(b"A", b"N", b"R")[i] for i in rng.integers(0, 3, CAP)],
        "g": [(b"F", b"O")[i] for i in rng.integers(0, 2, CAP)],
        "c": np.zeros(CAP, np.int32),
        "x": rng.uniform(1, 100, CAP).astype(np.float32),
        "y": rng.uniform(1, 100, CAP).astype(np.float32),
    }


def _mask(kind, seed=11):
    rng = np.random.default_rng(seed)
    valid = np.arange(CAP) < N
    if kind == "random":
        return rng.random(CAP) < 0.4
    if kind == "none-kept":
        return np.zeros(CAP, bool)
    if kind == "all-kept":
        return np.ones(CAP, bool)
    return valid                                  # padding-dropped


def _batch(keys, wide, mask, poison):
    t = _table()
    if wide:
        t["k"] = t["k"] * 100_003                 # span > the one-hot slots
    if poison:
        # what a dropped or a padding row holds is nobody's business
        dead = ~(mask & (np.arange(CAP) < N))
        for name, bad in (("x", np.nan), ("y", np.inf)):
            t[name] = np.where(dead, np.float32(bad), t[name])
        t["y"] = np.where(dead & (np.arange(CAP) % 2 == 0),
                          np.float32(-np.inf), t["y"])
        t["k"] = np.where(dead, np.int32(2**31 - 1), t["k"])
        # a dropped string key far from the kept ones: a span taken over
        # all rows would no longer fit the one-hot slots
        for name in ("f", "g"):
            t[name] = [b"~" if d else v for d, v in zip(dead, t[name])]
    return batch_from_numpy(t, capacity=CAP, str_max_len=1).with_count(N), t


@functools.lru_cache(maxsize=None)
def _fns(lowering, key):
    keys, aggs = _KEYS[key], _AGGS[lowering]

    @jax.jit
    def masked(b, m):
        # and how many group-bys took the one-hot branch
        with kernels.onehot_tally() as onehot:
            out = kernels.group_aggregate(b, keys, aggs, where=m)
        return out, sum(onehot, jnp.zeros((), jnp.int32))

    compacted = jax.jit(lambda b, m: kernels.group_aggregate(
        kernels.compact(b, m), keys, aggs))
    return masked, compacted


def _groups(table, keys):
    """{key tuple: {aggregate: value}} of a host table of groups."""
    n = len(table[keys[0]])
    return {tuple(table[k][i] if isinstance(table[k][i], bytes)
                  else int(table[k][i]) for k in keys):
            {a: table[a][i] for a in table if a not in keys}
            for i in range(n)}


def _route(batch, keys, aggs):
    if kernels._matmul_group_eligible(batch, keys, aggs):
        return "smallkey"
    return "boundary" if kernels._boundary_eligible(batch, aggs)[0] \
        else "scan"


@pytest.mark.parametrize("poison", [False, True], ids=["clean", "nan-inf"])
@pytest.mark.parametrize("mask_kind", _MASKS)
@pytest.mark.parametrize("lowering,key", _CASES,
                         ids=[f"{a}-{b}" for a, b in _CASES])
def test_masked_group_equals_compact_then_group(lowering, key, mask_kind,
                                                poison):
    keys, aggs = _KEYS[key], _AGGS[lowering]
    mask = _mask(mask_kind)
    batch, table = _batch(keys, wide=lowering != "smallkey", mask=mask,
                          poison=poison)
    # two string keys under the one-hot aggregates are Q1: one-hot path
    assert _route(batch, keys, aggs) == lowering
    masked, compacted = _fns(lowering, key)
    out, onehot = masked(batch, jnp.asarray(mask))
    got = _groups(batch_to_numpy(out), keys)
    ref = _groups(batch_to_numpy(compacted(batch, jnp.asarray(mask))), keys)

    kept = np.nonzero(mask & (np.arange(CAP) < N))[0]
    # the kept rows' span fits the slots whatever the dropped rows hold
    assert int(onehot) == (lowering == "smallkey" and len(kept) > 0)
    host = {k: ([v[i] for i in kept] if isinstance(v, list) else v[kept])
            for k, v in table.items()}
    want = {}
    if len(kept):
        node = E.GroupByAgg(
            parents=(E.Source(parents=(), data=None, _npartitions=1,
                              host=host),),
            keys=tuple(keys), aggs=dict(aggs))
        want = _groups(run_oracle(node), keys)

    assert set(got) == set(ref) == set(want)
    for g, vals in got.items():
        for name, (kind, _col) in aggs.items():
            a, b, w = vals[name], ref[g][name], want[g][name]
            if kind in ("count", "min", "max"):
                assert a == b == w, (g, name)
            else:
                assert abs(a - b) <= 1e-6 * abs(b), (g, name, a, b)
                assert abs(a - w) <= 1e-5 * abs(w), (g, name, a, w)


def _sorts(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "sort"
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    n += _sorts(inner)
    return n


@pytest.mark.parametrize("lowering,key", [("smallkey", "const"),
                                          ("boundary", "strings"),
                                          ("scan", "dense")])
def test_the_mask_traces_one_sort_fewer(lowering, key):
    keys, aggs = _KEYS[key], _AGGS[lowering]
    mask = _mask("random")
    batch, _ = _batch(keys, wide=lowering != "smallkey", mask=mask,
                      poison=False)
    m = jnp.asarray(mask)
    fused = jax.make_jaxpr(lambda b, m: kernels.group_aggregate(
        b, keys, aggs, where=m))(batch, m)
    plain = jax.make_jaxpr(lambda b: kernels.group_aggregate(
        b, keys, aggs))(batch)
    both = jax.make_jaxpr(lambda b, m: kernels.group_aggregate(
        kernels.compact(b, m), keys, aggs))(batch, m)
    assert _sorts(both.jaxpr) == _sorts(fused.jaxpr) + 1
    # and the mask adds none to the group-by's own
    assert _sorts(fused.jaxpr) == _sorts(plain.jaxpr)


def test_no_mask_traces_the_program_it_traced_before():
    """``where=None`` is every other caller's call (ooc, the streamed
    engines, the cost model): its jaxpr does not know the mask exists."""
    batch, _ = _batch(["f", "g"], wide=True, mask=_mask("all-kept"),
                      poison=False)
    aggs = _AGGS["boundary"]
    a = jax.make_jaxpr(lambda b: kernels.group_aggregate(
        b, ["f", "g"], aggs))(batch)
    b = jax.make_jaxpr(lambda b: kernels.group_aggregate(
        b, ["f", "g"], aggs, where=None))(batch)
    assert str(a) == str(b)
    masked = jax.make_jaxpr(lambda b, m: kernels.group_aggregate(
        b, ["f", "g"], aggs, where=m))(batch, jnp.ones(CAP, bool))
    assert len(masked.jaxpr.eqns) > len(a.jaxpr.eqns)
