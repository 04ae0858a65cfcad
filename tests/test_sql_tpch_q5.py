"""TPC-H Q5 through ``sql/`` as published (``perfbench/queries/tpch_q5.sql``):
six tables joined by WHERE's equalities, one of which closes a cycle
(``c_nationkey = s_nationkey``, the second key of the ``supplier`` join),
the selectivity two hops away in ``region``.  The benchmark's own
generator at the rehearsal's size, the tables written to stores as the
benchmark writes them (five with their key declared), the answer
compared with the plain numpy reference (``perfbench/ref/``): the same
five groups in the same order, every revenue within 1e-5 of float64.

Every join is the lookup kernel: four build sides are a table joined on
its key, and the ``lineitem`` join's build side — ``orders`` joined to
its customers — still carries ``o_orderkey`` as a key, because each
order met at most one customer (``sql/binder._mark_unique``)."""

import json
import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, _REPO)

from dryad_tpu import make_mesh, sql  # noqa: E402
from dryad_tpu.api.dataset import Context  # noqa: E402
from dryad_tpu.sql import binder, parser  # noqa: E402
from perfbench.kinds import tpch_q5_tables  # noqa: E402
from perfbench.ref import relational_join  # noqa: E402
from utils import assert_same_rows  # noqa: E402


def _load(*parts):
    with open(os.path.join(_REPO, "perfbench", *parts)) as f:
        return f.read()


CFG = json.loads(_load("configs", "tpch_q5_tables_sf2_1chip.json"))
TRAFFIC = json.loads(_load("traffic", "tpch_q5_collect.json"))
SPEC = TRAFFIC["reference"]
Q5 = _load(TRAFFIC["query_file"])
ASIA = {b"INDIA", b"INDONESIA", b"JAPAN", b"CHINA", b"VIETNAM"}
# seeds whose tables at the rehearsal's size hold lines of all five nations
SEEDS = [1, 7, 2**31 + 40]


def _stored(seed, tmp_path_factory):
    import jax
    data = tpch_q5_tables.generate(seed, CFG, rehearse=True)
    events = []
    ctx = Context(mesh=make_mesh(jax.devices()[:1]),
                  event_log=events.append)
    state = tpch_q5_tables.ingest(ctx, data, CFG, str(
        tmp_path_factory.mktemp(f"q5-{seed}")))
    cat = sql.Catalog()
    for name, path in state["tables"].items():
        cat.register_store(name, path)
    return data, ctx, cat, state, events


@pytest.fixture(scope="module")
def stored(tmp_path_factory):
    return _stored(SEEDS[0], tmp_path_factory)


def test_the_tables_and_their_keys(stored):
    data, _, cat, state, _ = stored
    assert {t: cat.get(t).unique for t in cat.names()} == {
        "region": ("r_regionkey",), "nation": ("n_nationkey",),
        "supplier": ("s_suppkey",), "customer": ("c_custkey",),
        "orders": ("o_orderkey",), "lineitem": None}
    assert CFG["keys"] == {t: [k] for t, k in
                           tpch_q5_tables.KEYS.items()}
    assert state["rows"] == sum(tpch_q5_tables.sizes(CFG, True).values())
    assert sum(CFG["tables"].values()) == 15320030
    assert tpch_q5_tables.sizes(CFG) == CFG["tables"]
    for t, cols in data["tables"].items():
        assert list(cols) == list(CFG["schemas"][t]), t
    t = data["tables"]
    # every line has its supplier; the nations and regions are the
    # specification's
    assert np.isin(t["lineitem"]["l_suppkey"],
                   t["supplier"]["s_suppkey"]).all()
    names = [bytes(d[:n]) for d, n in zip(*t["nation"]["n_name"])]
    asia = int(np.flatnonzero(
        [bytes(d[:n]) == b"ASIA" for d, n in zip(*t["region"]["r_name"])])[0])
    assert {names[i] for i in np.flatnonzero(
        t["nation"]["n_regionkey"] == asia)} == ASIA


@pytest.mark.parametrize("seed", SEEDS)
def test_q5_as_published_equals_the_reference(seed, tmp_path_factory):
    data, ctx, cat, _, events = _stored(seed, tmp_path_factory)
    got = sql.query(ctx, cat, Q5).collect()
    compared = relational_join.check({"collected": got}, data, SPEC, 1)
    assert all(compared[k] <= SPEC["limits"][k] for k in compared), compared
    assert set(compared) == set(SPEC["limits"])
    ref = relational_join.reference(data, SPEC)
    assert [bytes(k) for k in got["n_name"]] == [k[0] for k in ref["keys"]]
    assert set(got["n_name"]) == ASIA
    np.testing.assert_allclose(got["revenue"], ref["columns"]["revenue"],
                               rtol=1e-5)


def test_every_q5_join_is_marked_and_one_inherits_its_key(stored):
    _, _, cat, _, _ = stored
    b = binder.bind(cat, parser.parse(Q5))
    assert [(j.table, j.swap, j.unique_by) for j in b.joins] == [
        ("orders", True, "table"), ("lineitem", True, "inherited"),
        ("supplier", False, "table"), ("nation", False, "table"),
        ("region", False, "table")]
    assert all(j.unique for j in b.joins)
    # the cycle's equality is the second key of the supplier join
    sup = b.joins[2]
    assert sup.left_keys == ["lineitem.l_suppkey", "customer.c_nationkey"]
    assert sup.right_keys == ["supplier.s_suppkey", "supplier.s_nationkey"]


def test_every_q5_join_stage_runs_the_lookup_kernel(stored):
    import jax
    cat = stored[2]
    events = []
    ctx = Context(mesh=make_mesh(jax.devices()[:1]),
                  event_log=events.append)
    sql.query(ctx, cat, Q5).collect()
    done = sorted({e["stage"]: e for e in events
                   if e.get("event") == "stage_done"
                   and not e["overflow"]}.values(), key=lambda e: e["stage"])
    joins = [e for e in done if "join_kernel" in e]
    assert [e["join_kernel"] for e in joins] == ["lookup"] * 5
    assert all(e["right_unique"] for e in joins)
    lower = [e for e in events if e.get("event") == "span"
             and e.get("name") == "sql.lower"]
    assert lower[-1]["attrs"]["unique_joins"] == 5
    assert lower[-1]["attrs"]["inherited_unique_joins"] == 1


def _q5_tables(seed=5):
    """The Q5 tables of the kind at the rehearsal's size, cut to the
    columns the join queries below read, as host columns."""
    t = tpch_q5_tables.generate(seed, CFG, rehearse=True)["tables"]
    keep = {"customer": ("c_custkey", "c_nationkey"),
            "orders": ("o_orderkey", "o_custkey", "o_orderdate"),
            "lineitem": ("l_orderkey", "l_suppkey", "l_linenumber",
                         "l_extendedprice"),
            "supplier": ("s_suppkey", "s_nationkey")}
    return {n: {c: t[n][c] for c in cols} for n, cols in keep.items()}


def _catalog(t, keyed=True):
    cat = sql.Catalog()
    for name, cols in t.items():
        key = tpch_q5_tables.KEYS.get(name) if keyed else None
        cat.register_columns(name, cols, unique=[key] if key else None)
    return cat


Q_CYCLE = """
select l_orderkey, l_linenumber, l_extendedprice, s_suppkey, s_nationkey
from customer, orders, lineitem, supplier
where c_custkey = o_custkey and l_orderkey = o_orderkey
  and l_suppkey = s_suppkey and c_nationkey = s_nationkey
  and o_orderdate < 9000"""


def test_the_composite_key_join_agrees_with_hash_join_row_for_row():
    """The supplier join on (l_suppkey, c_nationkey) = (s_suppkey,
    s_nationkey): the lookup kernel on a key of two columns against
    hash_join's general body and the sequential oracle, where most lines'
    customers live in another nation than their supplier."""
    t = _q5_tables()
    events = []
    ctx = Context(event_log=events.append)
    lookup = sql.query(ctx, _catalog(t), Q_CYCLE).collect()
    kernels = {e["stage"]: e["join_kernel"] for e in events
               if e.get("event") == "stage_done" and "join_kernel" in e
               and not e["overflow"]}
    assert list(kernels.values()) == ["lookup"] * 3
    general = sql.query(Context(), _catalog(t, keyed=False),
                        Q_CYCLE).collect()
    oracle = sql.query(Context(local_debug=True), _catalog(t, keyed=False),
                       Q_CYCLE).collect()
    assert_same_rows(lookup, general)
    assert_same_rows(lookup, oracle)
    # the cycle's key left out: far more lines, most of them across nations
    open_cycle = sql.query(Context(), _catalog(t), Q_CYCLE.replace(
        "and c_nationkey = s_nationkey", "")).collect()
    n, n_all = len(lookup["l_orderkey"]), len(open_cycle["l_orderkey"])
    assert 20 < n < n_all / 10
