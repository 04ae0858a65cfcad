"""``SUM`` over integers is exact to 64 bits.

The package runs without ``jax_enable_x64``: a sum of ``int32`` values
used to accumulate in ``int32`` and wrap with no diagnostic.  Through SQL
``SUM(<int expression>)`` now has a 64-bit result whatever the data
(aggregate kind ``sum64``, a ``data.columnar.Int64Column`` of two 32-bit
words), on the boundary and the scan lowering of the group-by and as a
global aggregate, with and without the ``where=`` mask; the column can
be ordered by, collected as numpy ``int64``, stored and read back.
Seeded data against numpy's ``int64`` sums."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from dryad_tpu import sql  # noqa: E402
from dryad_tpu.api.dataset import Context  # noqa: E402
from dryad_tpu.data.columnar import (Batch, Int64Column,  # noqa: E402
                                     batch_to_numpy)
from dryad_tpu.ops import kernels  # noqa: E402
from dryad_tpu.sql.errors import SqlError  # noqa: E402

N, CAP = 3000, 4096
I32 = np.iinfo(np.int32)


def _values(shape, rng):
    """int32 values by shape of the trouble: group totals past 2**31,
    past 2**32, negative, of mixed sign at the type's ends."""
    if shape == "past_2_31":
        return rng.integers(2**29, 2**30, N)
    if shape == "past_2_32":
        return rng.integers(2**30, I32.max, N, endpoint=True)
    if shape == "negative":
        return rng.integers(I32.min, -2**30, N)
    if shape == "mixed_ends":
        return rng.choice(np.array([I32.min, I32.max, -1, 0, 1]), N)
    raise ValueError(shape)


def _keys(groups, rng):
    if groups == "one_group":
        return np.zeros(N, np.int64)
    if groups == "one_row_each":
        return rng.permutation(N)
    return rng.integers(0, 7, N)


def _pad(a):
    return jnp.asarray(np.pad(np.asarray(a), (0, CAP - len(a))))


def _want(keys, values, keep=None):
    keep = np.ones(len(keys), bool) if keep is None else keep
    out = {}
    for k, v in zip(keys[keep].tolist(), values[keep].tolist()):
        out[k] = out.get(k, 0) + v
    return out


# aggregate sets by the lowering they reach: one min/max column rides the
# boundary path's sort key; two cannot, so the segmented scans run
LOWERINGS = {
    "boundary": {"s": ("sum64", "v"), "n": ("count", None),
                 "hi": ("max", "y")},
    "scan": {"s": ("sum64", "v"), "lo": ("min", "y"), "hi": ("max", "z")},
}


@pytest.mark.parametrize("masked", [False, True], ids=["all", "where"])
@pytest.mark.parametrize("groups", ["few_groups", "one_group",
                                    "one_row_each"])
@pytest.mark.parametrize("shape", ["past_2_31", "past_2_32", "negative",
                                   "mixed_ends"])
@pytest.mark.parametrize("lowering", list(LOWERINGS))
def test_group_sums_equal_numpy_int64(lowering, shape, groups, masked):
    rng = np.random.default_rng([N, len(shape), len(groups), masked])
    keys, values = _keys(groups, rng), _values(shape, rng)
    keep = rng.random(N) < 0.6 if masked else None
    batch = Batch({"k": _pad(keys.astype(np.int32)),
                   "v": _pad(values.astype(np.int32)),
                   "y": _pad(rng.random(N).astype(np.float32)),
                   "z": _pad(rng.integers(0, 99, N).astype(np.int32))},
                  jnp.asarray(N, jnp.int32))
    aggs = LOWERINGS[lowering]
    assert kernels._boundary_eligible(batch, aggs)[0] == \
        (lowering == "boundary")
    out = batch_to_numpy(jax.jit(
        lambda b, w: kernels.group_aggregate(b, ["k"], aggs, where=w))(
        batch, None if keep is None else _pad(keep)))
    want = _want(keys, values, keep)
    assert out["s"].dtype == np.int64
    assert dict(zip(out["k"].tolist(), out["s"].tolist())) == want
    if shape != "mixed_ends" and groups != "one_row_each":
        assert max(abs(s) for s in want.values()) > 2**31


@pytest.mark.parametrize("lowering", list(LOWERINGS))
def test_partial_sums_merge_by_the_wide_sum(lowering):
    """``sum`` over an Int64Column (what a multi-partition group-by's
    final stage does with the partial 64-bit sums) stays 64 bits."""
    rng = np.random.default_rng(7)
    keys = rng.integers(0, 5, N)
    values = rng.integers(-2**52, 2**52, N)
    w = Int64Column.from_numpy(np.pad(values, (0, CAP - N)))
    batch = Batch({"k": _pad(keys.astype(np.int32)),
                   "w": Int64Column(jnp.asarray(w.hi), jnp.asarray(w.lo)),
                   "y": _pad(rng.random(N).astype(np.float32)),
                   "z": _pad(rng.integers(0, 99, N).astype(np.int32))},
                  jnp.asarray(N, jnp.int32))
    aggs = {"boundary": {"s": ("sum", "w")},
            "scan": {"s": ("sum", "w"), "lo": ("min", "y"),
                     "hi": ("max", "z")}}[lowering]
    out = batch_to_numpy(jax.jit(
        lambda b: kernels.group_aggregate(b, ["k"], aggs))(batch))
    assert dict(zip(out["k"].tolist(), out["s"].tolist())) == \
        _want(keys, values)


def test_the_one_hot_path_keeps_refusing_integer_sums():
    batch = Batch({"k": jnp.zeros(CAP, jnp.int32),
                   "v": jnp.ones(CAP, jnp.int32)},
                  jnp.asarray(N, jnp.int32))
    assert not kernels._matmul_group_eligible(batch, ["k"],
                                              {"s": ("sum64", "v")})
    with pytest.raises(ValueError, match="sum64"):
        kernels.group_aggregate(
            Batch({"k": batch.columns["k"],
                   "v": jnp.ones(CAP, jnp.float32)}, batch.count),
            ["k"], {"s": ("sum64", "v")})


# -- through SQL, on the eight-partition mesh --------------------------------

def _fact(seed=5, n=6000):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, 40, n).astype(np.int32),
            "g": rng.integers(0, 4, n).astype(np.int32),
            "v": rng.integers(2**29, I32.max, n).astype(np.int32),
            "u": rng.integers(I32.min, I32.max, n).astype(np.int32),
            "x": rng.random(n).astype(np.float32)}


def _catalog(t):
    return sql.Catalog().register_columns("t", t)


def test_sql_group_sum_is_int64_and_equal(devices8):
    t = _fact()
    got = sql.query(Context(), _catalog(t),
                    "select k, sum(v) as s, sum(u) as su, count(*) as n "
                    "from t group by k").collect()
    assert got["s"].dtype == np.int64 and got["su"].dtype == np.int64
    assert dict(zip(got["k"].tolist(), got["s"].tolist())) == \
        _want(t["k"], t["v"])
    assert dict(zip(got["k"].tolist(), got["su"].tolist())) == \
        _want(t["k"], t["u"])
    assert min(got["s"]) > 2**32            # every total wrapped before


def test_sql_sum_of_an_integer_expression(devices8):
    t = _fact()
    got = sql.query(Context(), _catalog(t),
                    "select g, sum(v - u) as d from t group by g").collect()
    want = _want(t["g"], (t["v"].astype(np.int64) - t["u"])
                 .astype(np.int32))         # the expression itself is int32
    assert dict(zip(got["g"].tolist(), got["d"].tolist())) == want


@pytest.mark.parametrize("where", ["", "where g = 2", "where x < 0.5 and "
                                   "g != 1"],
                         ids=["all", "one-conjunct", "two-conjuncts"])
def test_sql_global_sum(devices8, where):
    t = _fact()
    keep = {"": np.ones(len(t["g"]), bool), "where g = 2": t["g"] == 2,
            "where x < 0.5 and g != 1": (t["x"] < 0.5) & (t["g"] != 1)}
    got = sql.query(Context(), _catalog(t),
                    f"select sum(v) as s, count(*) as n from t {where}"
                    ).collect()
    assert got["s"].dtype == np.int64
    assert got["s"].tolist() == [int(t["v"][keep[where]]
                                     .astype(np.int64).sum())]
    assert got["n"].tolist() == [int(keep[where].sum())]


def test_sql_sum_with_the_scan_lowering(devices8):
    """Two min/max columns beside the sum: the segmented-scan path."""
    t = _fact()
    got = sql.query(Context(), _catalog(t),
                    "select g, sum(u) as s, min(x) as lo, max(k) as hi "
                    "from t group by g").collect()
    assert dict(zip(got["g"].tolist(), got["s"].tolist())) == \
        _want(t["g"], t["u"])
    for g, lo, hi in zip(got["g"], got["lo"], got["hi"]):
        assert lo == t["x"][t["g"] == g].min()
        assert hi == t["k"][t["g"] == g].max()


@pytest.mark.parametrize("way", ["asc", "desc"])
def test_order_by_a_64_bit_sum(devices8, way):
    t = _fact()
    got = sql.query(Context(), _catalog(t),
                    f"select k, sum(u) as s from t group by k "
                    f"order by s {way}").collect()
    want = sorted(_want(t["k"], t["u"]).items(), key=lambda kv: kv[1],
                  reverse=(way == "desc"))
    assert list(zip(got["k"].tolist(), got["s"].tolist())) == want
    assert want[0][1] * want[-1][1] < 0      # both signs were sorted


def test_order_by_a_key_then_the_sum_descending(devices8):
    t = _fact()
    got = sql.query(Context(), _catalog(t),
                    "select g, k, sum(v) as s from t group by g, k "
                    "order by g asc, s desc limit 25").collect()
    sums = {}
    for g, k, v in zip(t["g"].tolist(), t["k"].tolist(), t["v"].tolist()):
        sums[(g, k)] = sums.get((g, k), 0) + v
    want = sorted(((g, k, s) for (g, k), s in sums.items()),
                  key=lambda r: (r[0], -r[2]))[:25]
    assert list(zip(got["g"].tolist(), got["k"].tolist(),
                    got["s"].tolist())) == want


def test_the_oracle_agrees(devices8):
    t = _fact(n=500)
    q = "select k, sum(v) as s from t group by k order by k"
    got = sql.query(Context(), _catalog(t), q).collect()
    oracle = sql.query(Context(local_debug=True), _catalog(t), q).collect()
    assert got["s"].tolist() == [int(s) for s in oracle["s"]]


def test_through_to_store_and_back(devices8, tmp_path):
    from dryad_tpu.io.store import store_meta
    t = _fact()
    ctx = Context()
    path = str(tmp_path / "sums")
    sql.query(ctx, _catalog(t), "select k, sum(u) as s from t group by k "
              "order by s desc").to_store(path)
    assert store_meta(path)["schema"]["s"] == {"kind": "int64"}
    back = ctx.from_store(path).collect()
    assert back["s"].dtype == np.int64
    assert dict(zip(back["k"].tolist(), back["s"].tolist())) == \
        _want(t["k"], t["u"])
    # registered again it is a bigint: ordered by, summed on, selected
    cat = sql.Catalog().register_store("r", path)
    assert cat.get("r").schema["s"] == {"kind": "num", "dtype": "bigint"}
    asc = sql.query(ctx, cat, "select k, s from r order by s").collect()
    assert asc["s"].tolist() == sorted(_want(t["k"], t["u"]).values())
    total = sql.query(ctx, cat, "select sum(s) as t from r").collect()
    assert total["t"].tolist() == [int(t["u"].astype(np.int64).sum())]


def test_reading_some_columns_of_a_store_with_a_wide_one(devices8,
                                                          tmp_path):
    t = _fact()
    ctx = Context()
    path = str(tmp_path / "sums")
    sql.query(ctx, _catalog(t), "select k, sum(u) as s, count(*) as n "
              "from t group by k").to_store(path)
    got = ctx.from_store(path, columns=["s"]).collect()
    assert list(got) == ["s"] and got["s"].dtype == np.int64
    assert sorted(got["s"].tolist()) == sorted(_want(t["k"],
                                                     t["u"]).values())


def test_avg_over_integers_does_not_wrap(devices8):
    t = _fact()
    got = sql.query(Context(), _catalog(t),
                    "select g, avg(v) as a from t group by g").collect()
    for g, a in zip(got["g"], got["a"]):
        want = t["v"][t["g"] == g].astype(np.float64).mean()
        assert abs(a - want) / want < 1e-6   # near 1.3e9: a wrapped total
        #                                      would not even be positive


@pytest.mark.parametrize("text,what", [
    ("select k, sum(v) as s from t group by k having s > 5", "HAVING"),
    ("select k from r where s > 5", "comparison"),
    ("select s + 1 as x from r", "arithmetic"),
    ("select -s as x from r", "negation"),
    ("select s, count(*) as n from r group by s", "group key"),
    ("select avg(s) as a from r", "AVG"),
    ("select min(s) as a from r", "MIN"),
    ("select distinct s from r", "DISTINCT"),
    ("select r.k from r join t on r.s = t.k", "join key"),
], ids=lambda v: v if len(v) < 12 else None)
def test_what_a_64_bit_sum_cannot_do_rejects_at_bind_time(devices8, text,
                                                           what, tmp_path):
    t = _fact(n=200)
    cat = _catalog(t)
    cat.register_schema("r", {"k": "int32", "s": {"kind": "int64"}})
    with pytest.raises(SqlError) as e:
        sql.compile_query(cat, text)
    assert "DTA305" in str(e.value)


def test_dataset_api_sum64(devices8):
    """``("sum", <int32 column>)`` keeps its column's type (CHANGES.md, PR
    36); ``sum64`` is the exact one, on every partition count."""
    t = _fact()
    got = Context().from_columns(t).group_by(
        ["g"], {"s": ("sum64", "v"), "w": ("sum", "x")}).collect()
    assert got["s"].dtype == np.int64
    assert dict(zip(got["g"].tolist(), got["s"].tolist())) == \
        _want(t["g"], t["v"])


def test_no_x64():
    assert not jax.config.jax_enable_x64
    assert jnp.asarray(np.int64(2**40)).dtype == jnp.int32
