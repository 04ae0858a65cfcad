"""A SQL join to a key the store carries is the lookup kernel alone.

``sql/binder`` marks exactly the inner and left joins whose build side is
one base table (its own filter and projection may lie between) joined on
columns that cover the key its catalog entry carries, or what is joined
so far joined on a key it kept through marked joins; ``sql/lower`` hands
them to ``Dataset.join(right_unique="verified")``; the stage's program is
then ``kernels._lookup_join`` and nothing else — no duplicate check, no
``cond``, no general hash-join body — and says so on its ``stage_done``
event.  A table without a key joins by ``hash_join`` as before, and the
two kernels agree row for row."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from dryad_tpu import sql  # noqa: E402
from dryad_tpu.api.dataset import Context  # noqa: E402
from dryad_tpu.data.columnar import Batch, batch_from_numpy  # noqa: E402
from dryad_tpu.ops import kernels  # noqa: E402
from dryad_tpu.plan.planner import plan_query  # noqa: E402
from dryad_tpu.sql import binder, parser  # noqa: E402
from utils import assert_same_rows  # noqa: E402

REGIONS = (b"AFRICA", b"AMERICA", b"ASIA", b"EUROPE", b"MIDDLE EAST")


def _star(seed=4, n=4000, n_part=300, n_supp=60, n_date=40):
    """A fact with three foreign keys, some of which resolve nowhere
    (key 0 and keys past the dimension), and three dimensions with string
    payloads."""
    rng = np.random.default_rng(seed)
    fact = {"f_part": rng.integers(0, n_part + 20, n).astype(np.int32),
            "f_supp": rng.integers(0, n_supp + 5, n).astype(np.int32),
            "f_date": (19920100 + rng.integers(0, n_date + 3, n))
            .astype(np.int32),
            "f_rev": rng.integers(2**29, 2**31 - 1, n).astype(np.int32)}
    part = {"p_key": rng.permutation(n_part).astype(np.int32) + 1,
            "p_cat": [b"CAT#%d" % (i % 5) for i in range(n_part)],
            "p_brand": [b"BRAND#%03d" % (i % 37) for i in range(n_part)]}
    supp = {"s_key": np.arange(1, n_supp + 1, dtype=np.int32),
            "s_region": [REGIONS[i % 5] for i in range(n_supp)],
            "s_nation": [b"NATION%02d" % (i % 25) for i in range(n_supp)]}
    date = {"d_key": (19920101 + np.arange(n_date)).astype(np.int32),
            "d_year": (1992 + np.arange(n_date) % 7).astype(np.int32)}
    return {"fact": fact, "part": part, "supp": supp, "date": date}


KEYS = {"part": ["p_key"], "supp": ["s_key"], "date": ["d_key"]}


def _catalog(tables, keyed=("part", "supp", "date")):
    cat = sql.Catalog()
    for name, cols in tables.items():
        cat.register_columns(
            name, cols, unique=KEYS[name] if name in keyed else None)
    return cat


Q_STAR = """
select d_year, p_brand, sum(f_rev) as rev, count(*) as n
from fact, date, part, supp
where f_date = d_key and f_part = p_key and f_supp = s_key
  and p_cat = 'CAT#2' and s_region = 'ASIA'
group by d_year, p_brand order by d_year, p_brand"""
Q_DIM_FIRST = """
select s_nation, d_year, sum(f_rev) as rev
from supp, fact, date
where f_supp = s_key and f_date = d_key and s_region = 'EUROPE'
  and d_year >= 1993
group by s_nation, d_year order by d_year asc, rev desc"""
Q_ROWS = """
select f_rev, p_brand, s_nation, d_year from fact, part, supp, date
where f_part = p_key and f_supp = s_key and f_date = d_key
  and p_cat = 'CAT#1' and s_region = 'AMERICA'"""


def _bound(cat, text):
    return binder.bind(cat, parser.parse(text))


def _joins(ctx, cat, text):
    graph = plan_query(sql.query(ctx, cat, text).node, ctx.nparts,
                       config=ctx.config)
    return [(st, op) for st in graph.stages for op in st.body
            if op.kind == "join"]


# -- which joins are marked ---------------------------------------------------

def test_every_join_of_a_star_query_is_marked(devices8):
    t = _star()
    for text in (Q_STAR, Q_DIM_FIRST, Q_ROWS):
        b = _bound(_catalog(t), text)
        assert [j.unique for j in b.joins] == [True] * len(b.joins)
    # the dimension listed first is still the build side: the fact probes
    b = _bound(_catalog(t), Q_DIM_FIRST)
    assert b.base_table == "supp" and b.joins[0].swap


def test_only_the_joins_to_a_keyed_table_are_marked(devices8):
    t = _star()
    b = _bound(_catalog(t, keyed=("part",)), Q_STAR)
    assert {j.table: j.unique for j in b.joins} == \
        {"date": False, "part": True, "supp": False}
    assert not any(j.unique for j in _bound(_catalog(t, keyed=()),
                                            Q_STAR).joins)


def test_the_join_columns_must_cover_the_key(devices8):
    t = _star()
    two = dict(t["part"], p_ver=np.zeros(len(t["part"]["p_key"]),
                                         np.int32))
    cat = _catalog({k: v for k, v in t.items() if k != "part"})
    cat.register_columns("part", two, unique=["p_key", "p_ver"])
    q = ("select f_rev, p_brand from fact, part where f_part = p_key")
    assert [j.unique for j in _bound(cat, q).joins] == [False]
    # joined on more than the key is still at most one row a key
    cat.register_columns("part", two, unique=["p_key"])
    fact = dict(t["fact"], f_ver=np.zeros(len(t["fact"]["f_rev"]),
                                          np.int32))
    cat.register_columns("fact", fact)
    q2 = ("select f_rev, p_brand from fact, part where f_part = p_key "
          "and f_ver = p_ver")
    assert [j.unique for j in _bound(cat, q2).joins] == [True]


def test_the_build_side_must_be_one_base_table(devices8):
    """The small fact below probes nothing: with the fact listed last and
    smaller than what is joined so far, the build side of its join is the
    fact, which has no key; and a right or full outer join is never the
    lookup join."""
    t = _star(n=50, n_part=300)
    q = ("select f_rev, p_brand, s_nation from part, supp, fact "
         "where f_part = p_key and f_supp = s_key")
    b = _bound(_catalog(t), q)
    assert [(j.table, j.unique) for j in b.joins][0][1] is False
    q_outer = ("select f_rev, p_brand from fact right join part "
               "on f_part = p_key")
    assert [j.unique for j in _bound(_catalog(t), q_outer).joins] == [False]
    q_left = ("select f_rev, p_brand from fact left join part "
              "on f_part = p_key")
    assert [j.unique for j in _bound(_catalog(t), q_left).joins] == [True]


# -- a key kept through the joins before ---------------------------------------

def _chain(keys=("c", "o", "d")):
    """Schemas alone: ``c`` (100 rows, key c_k), ``o`` (1,000, key o_k,
    foreign key o_c), ``d`` (60, key d_k, foreign key d_o), ``b`` (50, no
    key), ``g`` (5,000, no key, foreign key g_o) and ``h`` (20,000, no
    key, foreign key h_o): a larger table probes what is joined so far."""
    def num(*cols):
        return {c: {"kind": "num", "dtype": "int32"} for c in cols}
    tables = {"c": (num("c_k", "c_x"), 100, ["c_k"]),
              "o": (num("o_k", "o_c", "o_x"), 1000, ["o_k"]),
              "d": (num("d_k", "d_o", "d_x"), 60, ["d_k"]),
              "b": (num("b_x", "b_y"), 50, None),
              "g": (num("g_o", "g_y"), 5000, None),
              "h": (num("h_o", "h_y"), 20000, None)}
    cat = sql.Catalog()
    for name, (schema, rows, key) in tables.items():
        cat.register_schema(name, schema, rows=rows,
                            unique=key if name in keys else None)
    return cat


def _marks(cat, text):
    return [(j.table, j.swap, j.unique_by) for j in _bound(cat, text).joins]


@pytest.mark.parametrize("text,want", [
    # o probes the keyed c: o_k is still a key of what is joined, and
    # the larger g probes it on o_k
    ("select g_y from c, o, g where o_c = c_k and g_o = o_k",
     [("o", True, "table"), ("g", True, "inherited")]),
    # a join to a table on its key (unswapped) keeps the probe's key
    ("select g_y from c join o on o_c = c_k join d on d_k = o_k "
     "join g on g_o = o_k",
     [("o", True, "table"), ("d", False, "table"), ("g", True, "inherited")]),
    ("select g_y from c join o on o_c = c_k left join d on d_k = o_k "
     "join g on g_o = o_k",
     [("o", True, "table"), ("d", False, "table"), ("g", True, "inherited")]),
], ids=["from-list", "inner-key-join-between", "left-key-join-between"])
def test_a_key_is_kept_through_a_marked_join(text, want):
    assert _marks(_chain(), text) == want


@pytest.mark.parametrize("text,want", [
    # general hash join between: an o row may meet many b rows
    ("select g_y from o, b, g where o_x = b_x and g_o = o_k",
     [("b", False, None), ("g", True, None)]),
    # the same join without b: o is the build side, and one base table
    ("select g_y from o, g where g_o = o_k", [("g", True, "table")]),
    # a right or a full join between: the result is no longer o's rows
    ("select g_y from c join o on o_c = c_k right join d on d_k = o_k "
     "join g on g_o = o_k",
     [("o", True, "table"), ("d", False, None), ("g", True, None)]),
    ("select g_y from c join o on o_c = c_k full join d on d_k = o_k "
     "join g on g_o = o_k",
     [("o", True, "table"), ("d", False, None), ("g", True, None)]),
    # the keyed o was the build side: what is joined so far carries the
    # key of its probe side, g, which has none: o_k repeats
    ("select h_y from g, o, h where g_o = o_k and h_o = o_k",
     [("o", False, "table"), ("h", True, None)]),
    ("select g_y from o, d, g where d_o = o_k and g_o = o_k",
     [("d", False, None), ("g", True, None)]),
], ids=["hash-join", "control", "right", "full", "keyed-build-side",
        "unkeyed-probe"])
def test_no_key_survives_a_join_that_may_repeat_rows(text, want):
    assert _marks(_chain(), text) == want


def test_an_inherited_key_needs_its_base_key():
    """Without c's key nothing is marked; without o's, the first join is
    still marked but nothing is inherited: o's rows have no key to keep."""
    q = "select g_y from c, o, g where o_c = c_k and g_o = o_k"
    assert _marks(_chain(keys=("o",)), q) == [("o", True, None),
                                               ("g", True, None)]
    assert _marks(_chain(keys=("c",)), q) == [("o", True, "table"),
                                               ("g", True, None)]


def test_lower_hands_the_mark_to_the_plan_and_counts_it(devices8):
    t = _star()
    ctx = Context()
    for keyed, want in ((("part", "supp", "date"), ["verified"] * 3),
                        (("supp",), [False, False, "verified"]),
                        ((), [False] * 3)):
        joins = _joins(ctx, _catalog(t, keyed), Q_STAR)
        assert [op.params["right_unique"] for _, op in joins] == want
    events = []
    ctx = Context(event_log=events.append)
    sql.query(ctx, _catalog(t, ("part", "date")), Q_STAR)
    lower = [e for e in events if e.get("event") == "span"
             and e.get("name") == "sql.lower"]
    assert lower and lower[-1]["attrs"]["unique_joins"] == 2


# -- what the marked stage's program holds ------------------------------------

def _stage_text(ctx, stage, inputs):
    """The stage's compiled program as text: its ops carry the scopes'
    names (``.../lookup_join/sort``), a ``cond`` is a ``conditional``."""
    fn = ctx.executor._build_stage_fn(stage, 1, ctx.config.initial_send_slack,
                                      len(inputs), False)
    return fn.lower(*inputs).compile().as_text()


def _leg_shapes(ctx, cat, text):
    """Each join stage of the query with its inputs' shapes, by running
    the stages before it abstractly."""
    ds = sql.query(ctx, cat, text)
    graph = plan_query(ds.node, ctx.nparts, config=ctx.config)
    results, out = {}, []
    for st in graph.stages:
        args = [results[leg.src] if isinstance(leg.src, int)
                else leg.src[1].batch for leg in st.legs]
        fn = ctx.executor._build_stage_fn(
            st, 1, ctx.config.initial_send_slack, len(args), False)
        results[st.id] = jax.eval_shape(fn, *args)[0]
        out.append((st, args))
    return out


def test_the_marked_stage_holds_no_cond_and_no_second_join(devices8):
    from dryad_tpu import make_mesh
    t = _star()
    ctx = Context(mesh=make_mesh(jax.devices()[:1]))     # one partition
    marked = [(st, a) for st, a in _leg_shapes(ctx, _catalog(t), Q_STAR)
              if any(op.kind == "join" for op in st.body)]
    plain = [(st, a) for st, a in _leg_shapes(ctx, _catalog(t, ()), Q_STAR)
             if any(op.kind == "join" for op in st.body)]
    assert len(marked) == len(plain) == 3
    for (ms, ma), (ps, pa) in zip(marked, plain):
        lookup, general = _stage_text(ctx, ms, ma), _stage_text(ctx, ps, pa)
        assert "conditional(" not in lookup + general
        # every op of the kernel's body lies in its scope; hash_join's
        # general body (the build side's sort, its search phase's merge of
        # both sides and the sort back) is not in the marked program
        assert "/lookup_join/" in lookup and "/lookup_join/" not in general
        assert "/search/" in general and "/search/" not in lookup
        assert general.count(" sort(") > lookup.count(" sort(")

    # asked for blindly the program holds both kernels and a cond
    two = [Batch({"k": jnp.zeros(64, jnp.int32), n: jnp.zeros(64, jnp.int32)},
                 jnp.asarray(3, jnp.int32)) for n in ("x", "y")]
    checked = jax.jit(lambda a, b: kernels.hash_join(
        a, b, ["k"], ["k"], 64, right_unique=True)).lower(
        *two).compile().as_text()
    assert "conditional(" in checked and "/lookup_join/" in checked
    alone = jax.jit(lambda a, b: kernels.hash_join(
        a, b, ["k"], ["k"], 64, right_unique="verified")).lower(
        *two).compile().as_text()
    assert "conditional(" not in alone and "/lookup_join/" in alone
    assert alone.count(" sort(") < checked.count(" sort(")


def test_stage_done_says_which_kernel_ran(devices8):
    t = _star()
    for keyed, kernels_want in ((("part", "supp", "date"),
                                 ["lookup", "lookup", "lookup"]),
                                (("part",), ["hash", "lookup", "hash"]),
                                ((), ["hash", "hash", "hash"])):
        events = []
        ctx = Context(event_log=events.append)
        sql.query(ctx, _catalog(t, keyed), Q_STAR).collect()
        # the attempt that settled, of each stage (an overflow replays)
        done = sorted({e["stage"]: e for e in events
                       if e.get("event") == "stage_done"
                       and not e["overflow"]}.values(),
                      key=lambda e: e["stage"])
        joins = [e for e in done if "join_kernel" in e]
        assert [e["join_kernel"] for e in joins] == kernels_want
        assert [e["right_unique"] for e in joins] == \
            [k == "lookup" for k in kernels_want]
        assert all(e["build_rows"] > 0 and e["join_in_bytes"] > 0
                   for e in joins)
        # hash_join's general body says what its search phase sorts: at
        # least both inputs' rows of capacity, twice
        assert all(("search_sort_rows" in e) == (e["join_kernel"] != "lookup")
                   for e in joins)
        assert all(e["search_sort_rows"] > 2 * e["build_rows"]
                   for e in joins if e["join_kernel"] != "lookup")
        # the stage with the group-by says how many sums ran in 64 bits
        assert sum(e.get("int64_sums", 0) for e in done) >= 1
        assert all("int64_sums" not in e for e in joins)
        spans = [e for e in events if e.get("event") == "span"
                 and str(e.get("name", "")).startswith("stage ")
                 and "join_kernel" in (e.get("attrs") or {})]
        assert {s["attrs"]["join_kernel"] for s in spans} == \
            set(kernels_want)
        assert all(("search_sort_rows" in s["attrs"])
                   == (s["attrs"]["join_kernel"] != "lookup") for s in spans)


def test_dataset_join_right_unique_true_keeps_the_checked_form(devices8):
    t = _star()
    events = []
    ctx = Context(event_log=events.append)
    fact, part = ctx.from_columns(t["fact"]), ctx.from_columns(t["part"])
    got = fact.join(part, ["f_part"], ["p_key"], right_unique=True).collect()
    done = [e for e in events if e.get("event") == "stage_done"
            and "join_kernel" in e]
    assert {e["join_kernel"] for e in done} == {"checked"}
    want = fact.join(part, ["f_part"], ["p_key"]).collect()
    assert_same_rows(got, want)


# -- lookup and hash agree ------------------------------------------------------

@pytest.mark.parametrize("text", [Q_STAR, Q_DIM_FIRST, Q_ROWS],
                         ids=["star", "dimension-first", "rows"])
@pytest.mark.parametrize("seed", [4, 2**31 + 9])
def test_lookup_and_hash_agree_row_for_row(devices8, text, seed):
    t = _star(seed=seed)
    ctx = Context()
    lookup = sql.query(ctx, _catalog(t), text).collect()
    general = sql.query(ctx, _catalog(t, ()), text).collect()
    oracle = sql.query(Context(local_debug=True), _catalog(t, ()),
                       text).collect()
    assert len(next(iter(lookup.values()))) > 10
    ordered = "order by" in text
    assert_same_rows(lookup, general, ordered=ordered)
    assert_same_rows(lookup, {k: ([int(x) for x in v] if k == "rev" else v)
                              for k, v in oracle.items()}, ordered=ordered)


@pytest.mark.parametrize("how", ["inner", "left"])
def test_the_kernels_agree_with_unmatched_and_filtered_rows(how):
    """kernels.hash_join, the three forms, one partition: fact keys that
    resolve nowhere, a build side whose own filter left some of its rows
    out (count below capacity), a string payload and a string key."""
    rng = np.random.default_rng(11)
    n, m = 500, 64
    right = batch_from_numpy(
        {"k": [b"key%03d" % i for i in rng.permutation(m)],
         "pay": [b"p%d" % (i * 7) for i in range(m)],
         "w": np.arange(m, dtype=np.int32) * 1000}, capacity=96,
        str_max_len=8)
    left = batch_from_numpy(
        {"k": [b"key%03d" % i for i in rng.integers(0, m + 30, n)],
         "v": rng.integers(-5, 5, n).astype(np.int32)}, capacity=512,
        str_max_len=8)
    right = right.with_count(m - 10)      # ten of its rows filtered away
    outs = {}
    for ru in (False, True, "verified"):
        out, need = jax.jit(lambda a, b: kernels.hash_join(
            a, b, ["k"], ["k"], 512, how=how, right_unique=ru))(left, right)
        assert int(need) == 0
        from dryad_tpu.data.columnar import batch_to_numpy
        outs[ru] = batch_to_numpy(out)
    assert_same_rows(outs["verified"], outs[False])
    assert_same_rows(outs[True], outs[False])
    if how == "inner":
        assert 0 < len(outs[False]["v"]) < n
    else:
        assert len(outs[False]["v"]) == n
