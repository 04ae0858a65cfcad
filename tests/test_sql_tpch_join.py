"""TPC-H Q3 and Q6 through ``sql/`` as published: a FROM list joined by
WHERE's equalities, single-table conjuncts lowered below the joins, only
the named columns into them, the join's sides from the catalog's row
counts.  Seeded tables of a few thousand rows from the small generator
below (dbgen's rules that Q3 leans on: every line's order exists, a line
ships 1 to 121 days after its order, sparse order keys), checked against
numpy in float64 and against the sequential oracle."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from dryad_tpu import sql  # noqa: E402
from dryad_tpu.analysis.canon import semantic_fingerprint  # noqa: E402
from dryad_tpu.api.dataset import Context  # noqa: E402
from dryad_tpu.plan import expr as E  # noqa: E402
from dryad_tpu.plan.planner import plan_query  # noqa: E402
from dryad_tpu.sql.errors import SqlError  # noqa: E402
from utils import assert_same_rows  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SEGMENTS = (b"AUTOMOBILE", b"BUILDING", b"FURNITURE", b"MACHINERY",
             b"HOUSEHOLD")
_DATE = 9204                      # 1995-03-15


def _text(name):
    with open(os.path.join(_REPO, "perfbench", "queries", name)) as f:
        return f.read()


Q3 = _text("tpch_q3.sql")
Q3_ALL = Q3.replace("limit 10", "")
Q3_ON = """
select l_orderkey, sum(l_extendedprice * (1 - l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer join orders on c_custkey = o_custkey
     join lineitem on l_orderkey = o_orderkey
where c_mktsegment = 'BUILDING' and o_orderdate < 9204
      and l_shipdate > 9204
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10"""
Q6 = _text("tpch_q6.sql")
Q1 = _text("tpch_q1.sql")


def _tables(n_cust=150, n_orders=1500, n_lines=6000, seed=3):
    rng = np.random.default_rng(seed)
    cust = {
        "c_custkey": np.arange(1, n_cust + 1, dtype=np.int32),
        "c_name": [b"Customer#%09d" % k for k in range(1, n_cust + 1)],
        "c_acctbal": rng.uniform(-999, 9999, n_cust).astype(np.float32),
        "c_mktsegment": [_SEGMENTS[i]
                         for i in rng.integers(0, 5, n_cust)]}
    i = np.arange(n_orders)
    okey = (((i >> 3) << 5) | (i & 7)).astype(np.int32) + 1
    odate = rng.integers(_DATE - 300, _DATE + 300, n_orders)
    orders = {
        "o_orderkey": okey,
        "o_custkey": rng.integers(1, n_cust + 1, n_orders).astype(np.int32),
        "o_totalprice": rng.uniform(900, 5e5, n_orders).astype(np.float32),
        "o_orderdate": odate.astype(np.int32),
        "o_shippriority": np.zeros(n_orders, np.int32),
        "o_comment": [b"c%d" % k for k in range(n_orders)]}
    of = rng.integers(0, n_orders, n_lines)
    lines = {
        "l_orderkey": okey[of],
        "l_quantity": rng.integers(1, 51, n_lines).astype(np.float32),
        "l_extendedprice": rng.uniform(900, 1e5, n_lines)
        .astype(np.float32),
        "l_discount": (rng.integers(0, 11, n_lines) / 100.0)
        .astype(np.float32),
        "l_tax": (rng.integers(0, 9, n_lines) / 100.0).astype(np.float32),
        "l_shipdate": (odate[of] + rng.integers(1, 122, n_lines))
        .astype(np.int32)}
    return {"customer": cust, "orders": orders, "lineitem": lines}


def _tables_q1(**kw):
    """``_tables`` with the two 1-byte string columns Q1 groups by (a
    generator of their own, so the other columns stay what they were)."""
    t = _tables(**kw)
    n = len(t["lineitem"]["l_quantity"])
    rng = np.random.default_rng(17)
    t["lineitem"]["l_returnflag"] = [
        (b"A", b"N", b"R")[i] for i in rng.integers(0, 3, n)]
    t["lineitem"]["l_linestatus"] = [
        (b"F", b"O")[i] for i in rng.integers(0, 2, n)]
    return t


def _catalog(tables=None):
    cat = sql.Catalog()
    for name, cols in (tables or _tables()).items():
        cat.register_columns(name, cols)
    return cat


def _q3_numpy(t):
    """Q3 in float64: [(l_orderkey, revenue, o_orderdate, 0)] ordered by
    revenue descending, then o_orderdate."""
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    building = {int(k) for k, s in zip(c["c_custkey"], c["c_mktsegment"])
                if s == b"BUILDING"}
    date_of = {int(k): int(d) for k, ck, d in zip(
        o["o_orderkey"], o["o_custkey"], o["o_orderdate"])
        if int(ck) in building and d < _DATE}
    rev = {}
    for k, p, d, s in zip(li["l_orderkey"], li["l_extendedprice"],
                          li["l_discount"], li["l_shipdate"]):
        if s > _DATE and int(k) in date_of:
            rev[int(k)] = rev.get(int(k), 0.0) + \
                float(p) * (1.0 - float(d))
    rows = [(k, r, date_of[k], 0) for k, r in rev.items()]
    return sorted(rows, key=lambda r: (-r[1], r[2]))


def _rows(got):
    return list(zip(np.asarray(got["l_orderkey"]).tolist(),
                    np.asarray(got["revenue"]).tolist(),
                    np.asarray(got["o_orderdate"]).tolist(),
                    np.asarray(got["o_shippriority"]).tolist()))


# -- answers -----------------------------------------------------------------

@pytest.mark.parametrize("text,limit", [(Q3, 10), (Q3_ALL, None),
                                        (Q3_ON, 10)],
                         ids=["published", "no-limit", "join-on"])
def test_q3_agrees_with_numpy_and_the_oracle(devices8, text, limit):
    t = _tables()
    want = _q3_numpy(t)
    assert len(want) > 20
    got = _rows(sql.query(Context(), _catalog(t), text).collect())
    want = want[:limit]
    assert [(k, d, p) for k, _, d, p in got] == \
        [(k, d, p) for k, _, d, p in want]
    assert max(abs(g[1] - w[1]) / w[1] for g, w in zip(got, want)) < 1e-5
    oracle = sql.query(Context(local_debug=True), _catalog(t),
                       text).collect()
    assert [r[0] for r in _rows(oracle)] == [r[0] for r in got]


def test_q6_agrees_with_numpy(devices8):
    li = _tables()["lineitem"]
    keep = ((li["l_shipdate"] >= 8766 + 400) & (li["l_shipdate"] < 9131 + 400)
            & (li["l_discount"] > 0.045) & (li["l_discount"] < 0.075)
            & (li["l_quantity"] < 24))
    assert 20 < keep.sum() < len(keep) / 4
    want = float((li["l_extendedprice"][keep].astype(np.float64)
                  * li["l_discount"][keep].astype(np.float64)).sum())
    # the published text, its dates moved into this table's range
    text = Q6.replace("8766", str(8766 + 400)).replace("9131",
                                                        str(9131 + 400))
    got = sql.query(Context(), _catalog(), text).collect()
    assert list(got) == ["revenue"] and len(got["revenue"]) == 1
    assert abs(float(got["revenue"][0]) - want) / want < 1e-5


@pytest.mark.parametrize("where,want", [
    ("", lambda li: len(li["l_quantity"])),
    ("where l_quantity < 3", lambda li: int((li["l_quantity"] < 3).sum())),
    ("where l_quantity < 3 and l_tax > 0.03",
     lambda li: int(((li["l_quantity"] < 3) & (li["l_tax"] > 0.03)).sum())),
], ids=["no-filter", "one-filter-column", "two-filter-columns"])
def test_count_star_keeps_a_column_to_count(devices8, where, want):
    """COUNT(*) names no column: the scan keeps one (a column its filter
    reads anyway, if it has a filter) so that the batch has rows."""
    got = sql.query(Context(), _catalog(),
                    f"select count(*) as n from lineitem {where}").collect()
    assert int(got["n"][0]) == want(_tables()["lineitem"])


def test_a_join_key_of_the_right_input_can_be_selected(devices8):
    """hash_join drops its right input's key columns; the lowering reads
    the left input's equal key for them (o_orderkey here lives on as
    l_orderkey, whichever side the planner made the left)."""
    q = ("select o_orderkey, c_custkey, l_quantity from customer, orders, "
         "lineitem where c_custkey = o_custkey and l_orderkey = o_orderkey "
         "and l_quantity < 3 and c_custkey < 20")
    got = sql.query(Context(), _catalog(), q).collect()
    oracle = sql.query(Context(local_debug=True), _catalog(), q).collect()
    assert len(got["o_orderkey"]) > 5
    assert_same_rows(got, oracle)


# -- the FROM list is the JOIN ... ON form -----------------------------------

def _graph(ctx, cat, text):
    return plan_query(sql.query(ctx, cat, text).node, ctx.nparts,
                      config=ctx.config)


def test_from_list_and_join_on_make_one_plan(devices8):
    cat, ctx = _catalog(), Context()
    a, b = _graph(ctx, cat, Q3), _graph(ctx, cat, Q3_ON)
    assert [s.fingerprint() for s in a.stages] == \
        [s.fingerprint() for s in b.stages]
    ba = sql.compile_query(cat, Q3)[1]
    bb = sql.compile_query(cat, Q3_ON)[1]
    assert semantic_fingerprint(cat, ba) == semantic_fingerprint(cat, bb)
    assert [(j.table, j.left_keys, j.right_keys) for j in ba.joins] == [
        ("orders", ["customer.c_custkey"], ["orders.o_custkey"]),
        ("lineitem", ["orders.o_orderkey"], ["lineitem.l_orderkey"])]


def test_from_list_order_follows_the_equalities():
    """Each step takes the next listed table that an equality connects
    to what is joined so far: lineitem waits for orders."""
    q = ("select count(*) as n from customer, lineitem, orders "
         "where l_orderkey = o_orderkey and c_custkey = o_custkey")
    bound = sql.compile_query(_catalog(), q)[1]
    assert [j.table for j in bound.joins] == ["orders", "lineitem"]


# -- where each conjunct lands -----------------------------------------------

def _chain(node):
    """Labels of the Map / Filter nodes from ``node`` down to its source
    or its join, nearest first."""
    out = []
    while isinstance(node, (E.Map, E.Filter)):
        out.append(node.label)
        node = node.parents[0]
    return out, node


RESIDUAL = Q3_ALL.replace("and l_shipdate > 9204",
                          "and l_shipdate > 9204\n\tand "
                          "o_totalprice > l_extendedprice")


def test_each_conjunct_lands_where_the_split_says():
    cat = _catalog()
    bound = sql.compile_query(cat, RESIDUAL)[1]
    assert set(bound.scan_filters) == {"customer", "orders", "lineitem"}
    assert bound.residual == ["bin", ">", ["col", "orders.o_totalprice"],
                              ["col", "lineitem.l_extendedprice"]]
    ds, _ = sql.lower(sql.SchemaContext(nparts=8), cat, bound)
    joins = [n for n in E.walk(ds.node) if isinstance(n, E.Join)]
    assert len(joins) == 2
    # the residual is the first thing above the last join ...
    above = [n for n in E.walk(ds.node)
             if isinstance(n, E.Filter) and n.parents[0] is joins[-1]]
    assert [n.label for n in above] == ["sql-where"]
    # ... and each table's own conjunct sits on its scan, below its join
    seen = {}
    for j in joins:
        for p in j.parents:
            labels, bottom = _chain(p)
            if isinstance(bottom, E.Source):
                seen[labels[-1]] = labels
    assert seen == {
        "sql-scan customer": ["sql-prune customer", "sql-where customer",
                              "sql-scan customer"],
        "sql-scan orders": ["sql-where orders", "sql-scan orders"],
        "sql-scan lineitem": ["sql-prune lineitem", "sql-where lineitem",
                              "sql-scan lineitem"]}


def test_only_named_columns_enter_a_join():
    cat = _catalog()
    ds, _ = sql.lower(sql.SchemaContext(nparts=8), cat,
                      sql.compile_query(cat, Q3)[1])
    into = {}
    for j in (n for n in E.walk(ds.node) if isinstance(n, E.Join)):
        for p in j.parents:
            if isinstance(p, (E.Map, E.Filter)):
                while isinstance(p, E.Filter):
                    p = p.parents[0]
                into[p.label.split()[-1]] = sorted(p.fn.outputs)
    assert into == {
        "customer": ["customer.c_custkey"],
        "orders": ["orders.o_custkey", "orders.o_orderdate",
                   "orders.o_orderkey", "orders.o_shippriority"],
        "lineitem": ["lineitem.l_discount", "lineitem.l_extendedprice",
                     "lineitem.l_orderkey"]}


def test_outer_join_predicates_stay_off_the_null_supplying_side(devices8):
    """LEFT JOIN fills lineitem's columns with zeros where an order has
    no line: a filter on lineitem has to see those rows, so it stays
    above the join; orders' own filter may go below."""
    q = ("select o_orderkey, l_quantity from orders left join lineitem "
         "on o_orderkey = l_orderkey "
         "where l_quantity < 2 and o_orderdate < 9000")
    cat = _catalog()
    bound = sql.compile_query(cat, q)[1]
    assert set(bound.scan_filters) == {"orders"}
    assert bound.residual == ["bin", "<", ["col", "lineitem.l_quantity"],
                              ["lit", 2, "int"]]
    got = sql.query(Context(), cat, q).collect()
    oracle = sql.query(Context(local_debug=True), cat, q).collect()
    assert (np.asarray(got["l_quantity"]) == 0).any()   # unmatched orders
    assert_same_rows(got, oracle)


@pytest.mark.parametrize("how,pushed", [
    ("join", {"a", "b"}), ("left join", {"a"}), ("right join", {"b"}),
    ("full join", set())])
def test_which_side_of_a_join_takes_its_filter(how, pushed):
    cat = sql.Catalog()
    cat.register_schema("a", {"k": {"kind": "num", "dtype": "int32"},
                              "x": {"kind": "num", "dtype": "int32"}})
    cat.register_schema("b", {"k": {"kind": "num", "dtype": "int32"},
                              "y": {"kind": "num", "dtype": "int32"}})
    bound = sql.compile_query(
        cat, f"select x, y from a {how} b on a.k = b.k "
             f"where x > 1 and y > 2")[1]
    assert set(bound.scan_filters) == pushed
    assert len(sql.conjuncts(bound.residual)) == 2 - len(pushed)


# -- the join's sides --------------------------------------------------------

def test_the_larger_input_probes_and_the_smaller_is_built():
    """out_capacity is the left capacity, and a key / foreign-key join
    returns at most its larger side's rows: orders (1,500) goes left of
    customer (150), lineitem (6,000) left of both."""
    cat = _catalog()
    bound = sql.compile_query(cat, Q3)[1]
    assert [j.swap for j in bound.joins] == [True, True]
    graph = plan_query(
        sql.lower(sql.SchemaContext(nparts=1), cat, bound)[0].node, 1,
        hosts=1)
    caps = [op.params["out_capacity"] for st in graph.stages
            for op in st.body if op.kind == "join"]
    assert caps == [1500, 6000]
    # written the other way round, nothing is swapped
    rev = sql.compile_query(
        cat, "select count(*) as n from lineitem, orders, customer "
             "where l_orderkey = o_orderkey and c_custkey = o_custkey")[1]
    assert [j.swap for j in rev.joins] == [False, False]


# -- errors ------------------------------------------------------------------

@pytest.mark.parametrize("q,code,span", [
    ("select c_custkey\nfrom customer,\n     orders\n"
     "where c_acctbal > 0", "DTA306", "<sql>:3:6"),
    ("select c_custkey from customer, orders, lineitem\n"
     "where c_custkey = o_custkey and l_quantity < o_totalprice",
     "DTA306", "<sql>:1:41"),
    ("select c_custkey from customer, orders join lineitem "
     "on l_orderkey = o_orderkey", "DTA306", "<sql>:1:40"),
    ("select c_custkey from customer, orders where c_custkey = o_nope",
     "DTA303", "<sql>:1:58"),
], ids=["no-equality", "inequality-only", "mixed-with-join",
        "unknown-column"])
def test_from_list_errors_carry_spans(q, code, span):
    with pytest.raises(SqlError) as ei:
        sql.compile_query(_catalog(), q)
    assert [str(d.span) for d in ei.value.report.by_code(code)] == [span]


# -- spans and counters ------------------------------------------------------

def test_spans_say_what_was_split_pruned_and_joined(devices8):
    events = []
    ctx = Context(event_log=events.append)
    sql.query(ctx, _catalog(), Q3).collect()
    spans = {e["name"]: e.get("attrs") or {} for e in events
             if e.get("event") == "span"}
    assert spans["sql.bind"] == {"joins": 2, "pushed_conjuncts": 3,
                                 "residual_conjuncts": 0}
    assert spans["sql.lower"]["columns_kept"] == {
        "customer": 1, "orders": 4, "lineitem": 3}
    assert spans["sql.lower"]["columns_stored"] == {
        "customer": 4, "orders": 6, "lineitem": 6}
    # 8 partitions x capacity x row bytes, as the join is handed them:
    # orders 4 int32 lanes against customer 1; lineitem 3 lanes against
    # the first join's 4
    caps = {"customer": -(-150 // 8), "orders": -(-1500 // 8),
            "lineitem": 6000 // 8}
    want = [8 * 4 * (4 * caps["orders"] + caps["customer"]),
            8 * 4 * (3 * caps["lineitem"] + 4 * caps["orders"])]
    stage_spans = [a for n, a in spans.items() if n.endswith(":join")]
    done = [e for e in events if e.get("event") == "stage_done"
            and e["label"] == "join"]
    for got in (stage_spans, done):
        assert [g["join_in_bytes"] for g in got] == want
        assert [g["out_capacity"] for g in got] == [caps["orders"],
                                                     caps["lineitem"]]
        assert all(g["right_unique"] is False for g in got)
    assert all("join" in a["program"] for a in stage_spans)


# -- the store is read at the columns the statement names ---------------------

def _filters(events):
    """(filters_masked, filters_compacted) over a query's stage programs,
    off the ``stage_done`` events and off the ``stage`` spans."""
    done = [e for e in events if e.get("event") == "stage_done"]
    spans = [e.get("attrs") or {} for e in events
             if e.get("event") == "span"
             and str(e.get("name", "")).startswith("stage ")]
    got = {(sum(x.get("filters_masked", 0) for x in src),
            sum(x.get("filters_compacted", 0) for x in src))
           for src in (done, spans)}
    assert len(got) == 1, got
    return got.pop()


# Q1 and Q6 as published, their dates moved into this table's range
_Q1_HALF = Q1.replace("10471", str(_DATE + 60))
_Q6_HERE = Q6.replace("8766", str(8766 + 400)).replace("9131",
                                                        str(9131 + 400))


@pytest.mark.parametrize("nparts", [1, 8])
@pytest.mark.parametrize("text,counts", [
    (_Q1_HALF, (1, 0)), (_Q6_HERE, (1, 0)), (Q3, (0, 3))],
    ids=["q1", "q6", "q3"])
def test_where_in_front_of_group_by_is_a_mask(devices8, text, counts,
                                              nparts):
    """WHERE -> GROUP BY groups under the predicate's mask (Q1, Q6; on
    several partitions the local partial group-by shares the filter's
    leg); a filter that feeds a join still compacts (Q3's three).  The
    answers are the oracle's."""
    from dryad_tpu.parallel.mesh import make_mesh
    t = _tables_q1()
    events = []
    ctx = Context(mesh=make_mesh(devices8[:nparts]),
                  event_log=events.append)
    got = sql.query(ctx, _catalog(t), text).collect()
    assert _filters(events) == counts
    want = sql.query(Context(local_debug=True), _catalog(t),
                     text).collect()
    assert sorted(got) == sorted(want)
    n = len(next(iter(want.values())))
    assert n > 0 and (text is not _Q1_HALF or n == 6)
    for name, w in want.items():
        g = got[name]
        assert len(g) == n, name
        if isinstance(w[0], bytes) or np.asarray(w).dtype.kind in "iu":
            assert list(g) == list(w), name           # keys, counts, order
        else:
            np.testing.assert_allclose(np.asarray(g, np.float64),
                                       np.asarray(w, np.float64),
                                       rtol=2e-5, err_msg=name)


@pytest.mark.parametrize("nparts", [1, 8])
@pytest.mark.parametrize("text,columns", [
    (Q1.replace("10471", "-5"),
     ["avg_disc", "avg_price", "avg_qty", "count_order", "l_linestatus",
      "l_returnflag", "sum_base_price", "sum_charge", "sum_disc_price",
      "sum_qty"]),
    (Q6.replace("8766", "99999"), ["revenue"])], ids=["q1", "q6"])
def test_a_where_that_keeps_no_row(devices8, text, columns, nparts):
    """Pinned from the parent commit: no row kept is no group, and a
    global aggregate over no row returns no row either (not SQL's one
    NULL row: the engine has no NULL), masked or compacted."""
    from dryad_tpu.parallel.mesh import make_mesh
    events = []
    ctx = Context(mesh=make_mesh(devices8[:nparts]),
                  event_log=events.append)
    got = sql.query(ctx, _catalog(_tables_q1()), text).collect()
    assert _filters(events) == (1, 0)
    assert sorted(got) == columns
    assert all(len(v) == 0 for v in got.values())
    assert np.asarray(got[columns[-1]]).dtype == np.float32


def _store_catalog(tmp_path, tables):
    cat, paths = sql.Catalog(), {}
    for name, cols in tables.items():
        paths[name] = str(tmp_path / name)
        Context().from_columns(cols).to_store(paths[name])
        cat.register_store(name, paths[name])
    return cat, paths


def _store_reads(events, paths):
    """table -> its ``store.read`` span's attrs, by the ``from_store`` span
    the read nests in."""
    spans = [e for e in events if e.get("event") == "span"]
    source = {e["span"]: e["attrs"]["source"] for e in spans
              if e["name"] == "from_store"}
    table = {p: t for t, p in paths.items()}
    return {table[source[e["parent"]]]: e["attrs"] for e in spans
            if e["name"] == "store.read"}


_Q6_HERE = Q6.replace("8766", str(8766 + 400)).replace("9131",
                                                        str(9131 + 400))


@pytest.mark.parametrize("text,names,reads", [
    (_Q6_HERE, ["revenue"], {"lineitem": (4, 6, 16 / 24)}),
    (Q3, ["l_orderkey", "revenue", "o_orderdate", "o_shippriority"],
     {"customer": (2, 4), "orders": (4, 6), "lineitem": (4, 6, 16 / 24)})],
    ids=["q6", "q3"])
def test_a_store_is_read_at_the_columns_the_statement_names(
        devices8, tmp_path, text, names, reads):
    t = _tables()
    cat, paths = _store_catalog(tmp_path, t)
    events = []
    got = sql.query(Context(event_log=events.append), cat, text).collect()
    # the answers of the whole tables (an inline catalog holds every column)
    want = sql.query(Context(), _catalog(t), text).collect()
    assert sorted(got) == sorted(names)
    for k in names:
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k
    seen = _store_reads(events, paths)
    assert sorted(seen) == sorted(reads)
    for table, (cols, stored, *share) in reads.items():
        a = seen[table]
        assert (a["columns"], a["columns_stored"]) == (cols, stored), table
        assert a["bytes"] < a["bytes_stored"]
        if share:           # four 4-byte columns of lineitem's six
            assert a["bytes"] / a["bytes_stored"] == pytest.approx(share[0])
    # the plan is the plan of the whole tables: the scans' labels, and so
    # the stage programs' names, do not say what the store handed over
    labels = [n.label for n in E.walk(sql.query(Context(), cat, text).node)
              if getattr(n, "label", "")]
    assert labels == [n.label for n in E.walk(
        sql.query(Context(), _catalog(t), text).node)
        if getattr(n, "label", "")]


def test_a_loader_still_gets_and_prunes_a_whole_table(devices8, tmp_path):
    """The service's scan-share hook hands one whole table to queries that
    name different columns: ``columns`` stops at it, and the ``sql-scan``
    projector prunes on the device as before."""
    from dryad_tpu.io.store import read_store
    t = _tables()
    cat, paths = _store_catalog(tmp_path, t)
    ctx = Context()
    loaded = {}

    def loader(name):
        loaded[name] = read_store(paths[name], ctx.mesh)
        return loaded[name]
    _mode, bound = sql.compile_query(cat, Q3)
    ds, _handles = sql.lower(ctx, cat, bound, loader=loader)
    assert {k: len(pd.batch.columns) for k, pd in loaded.items()} == {
        "customer": 4, "orders": 6, "lineitem": 6}
    got = ds.collect()
    want = sql.query(Context(), cat, Q3).collect()
    for k in want:
        assert np.array_equal(np.asarray(got[k]), np.asarray(want[k])), k
    # the sources differ, what leaves the scans does not
    def scans(node):
        return {n.label: n.fn.outputs for n in E.walk(node)
                if getattr(n, "label", "").startswith("sql-scan")}
    assert len(scans(ds.node)) == 3
    assert scans(ds.node) == scans(sql.query(Context(), cat, Q3).node)
