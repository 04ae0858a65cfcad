"""The program's own spans along a query (obs/trace.py call sites in
io/store.py, exec/data.py, api/dataset.py, exec/recovery.py,
exec/executor.py and sql/), and the stable names of stage programs.

Small sizes on the suite's virtual CPU devices; every case is its own
parametrised test so that each counts."""

import os
import re

import jax
import numpy as np
import pytest

from dryad_tpu import Context, make_mesh, sql
from dryad_tpu.exec.executor import stage_program_name
from dryad_tpu.obs import trace
from dryad_tpu.plan import expr as E
from dryad_tpu.plan.planner import plan_query

N = 4096


@pytest.fixture(autouse=True)
def _detach_sink():
    yield
    trace.install(None)


def _columns():
    return {"k": np.arange(N, dtype=np.int32)[::-1].copy(),
            "v": np.arange(N, dtype=np.float32),
            "g": (np.arange(N) % 3).astype(np.int32)}


def _ctx(ndev, events):
    return Context(mesh=make_mesh(jax.devices()[:ndev]),
                   event_log=events.append if events is not None else None)


def _input_store(tmp_path, ndev):
    path = str(tmp_path / "in")
    _ctx(ndev, None).from_columns(_columns()).to_store(path)
    return path


def _sort_query(ctx, src, dst):
    ctx.from_store(src).order_by([("k", False)]).to_store(dst)


def _sql_query(ctx, src):
    cat = sql.Catalog().register_store("t", src)
    return sql.query(ctx, cat,
                     "SELECT g, SUM(v) AS s FROM t GROUP BY g").collect()


def _spans(events):
    return [e for e in events if e.get("event") == "span"]


@pytest.fixture(scope="module")
def sort_spans(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("sort")
    src = _input_store(tmp, 1)
    events = []
    _sort_query(_ctx(1, events), src, str(tmp / "out"))
    trace.install(None)
    return _spans(events), str(tmp / "out")


@pytest.fixture(scope="module")
def sql_spans(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("sql")
    src = _input_store(tmp, 1)
    events = []
    out = _sql_query(_ctx(1, events), src)
    trace.install(None)
    assert sorted(out["g"].tolist()) == [0, 1, 2]
    return _spans(events)


def _one(spans, name):
    hit = [s for s in spans if s["name"] == name]
    assert len(hit) == 1, (name, [s["name"] for s in spans])
    return hit[0]


def _parent_name(spans, span):
    by_id = {s["span"]: s for s in spans}
    return by_id[span["parent"]]["name"] if span.get("parent") else None


# name, kind, the name of its parent (stage spans are matched by kind)
SORT_TREE = [
    ("from_store", "query", None),
    ("store.read", "io", "from_store"),
    ("store.file_read", "io", "store.read"),
    ("store.verify", "io", "store.read"),
    ("store.stack", "io", "store.read"),
    ("store.put", "io", "store.read"),
    ("to_store", "query", None),
    ("plan", "plan", "to_store"),
    ("lint", "plan", "to_store"),
    ("run", "job", "to_store"),
    ("settle", "wait", "run"),
    ("store.write", "io", "to_store"),
    ("store.fetch", "io", "store.write"),
    ("store.file_write", "io", "store.write"),
    ("store.checksum", "io", "store.write"),
    ("store.commit", "io", "store.write"),
]

SQL_TREE = [
    ("sql.query", "query", None),
    ("sql.parse", "front", "sql.query"),
    ("sql.bind", "front", "sql.query"),
    ("sql.lower", "front", "sql.query"),
    ("from_store", "query", "sql.lower"),
    ("store.read", "io", "from_store"),
    ("collect", "query", None),
    ("plan", "plan", "collect"),
    ("lint", "plan", "collect"),
    ("run", "job", "collect"),
    ("settle", "wait", "run"),
    ("collect.fetch", "io", "collect"),
]


@pytest.mark.parametrize("name,kind,parent", SORT_TREE,
                         ids=[t[0] for t in SORT_TREE])
def test_sort_query_span(sort_spans, name, kind, parent):
    spans, _out = sort_spans
    s = _one(spans, name)
    assert s["kind"] == kind
    assert _parent_name(spans, s) == parent


@pytest.mark.parametrize("name,kind,parent", SQL_TREE,
                         ids=[t[0] for t in SQL_TREE])
def test_sql_query_span(sql_spans, name, kind, parent):
    s = _one(sql_spans, name)
    assert s["kind"] == kind
    assert _parent_name(sql_spans, s) == parent


@pytest.mark.parametrize("which", ["sort", "sql"])
def test_stage_span_names_its_program(sort_spans, sql_spans, which):
    spans = sort_spans[0] if which == "sort" else sql_spans
    stages = [s for s in spans if s["kind"] == "stage"]
    assert stages
    for s in stages:
        assert _parent_name(spans, s) == "run"
        assert re.fullmatch(r"jit_stage_[a-z0-9_]+", s["attrs"]["program"])
        assert s["attrs"]["cache_hit"] is False
        (c,) = [x for x in spans if x.get("parent") == s["span"]]
        assert (c["name"], c["kind"]) == ("stage.compile", "compile")
        assert c["attrs"]["compile_s"] > 0


@pytest.mark.parametrize("which,roots", [
    ("sort", ["from_store", "to_store"]),
    ("sql", ["sql.query", "collect"])])
def test_one_trace_id_per_terminal_call(sort_spans, sql_spans, which, roots):
    spans = sort_spans[0] if which == "sort" else sql_spans
    assert [s["name"] for s in spans if not s.get("parent")] == roots
    by_id = {s["span"]: s for s in spans}
    traces = {}
    for s in spans:
        root = s
        while root.get("parent"):
            root = by_id[root["parent"]]
        traces.setdefault(root["name"], set()).add(s["trace"])
    assert all(len(ids) == 1 for ids in traces.values()), traces
    assert len({next(iter(ids)) for ids in traces.values()}) == len(roots)


def test_bytes_are_the_bytes_on_disk(sort_spans):
    spans, out = sort_spans
    on_disk = sum(os.path.getsize(os.path.join(out, f))
                  for f in os.listdir(out) if f.startswith("part-"))
    assert _one(spans, "store.file_write")["attrs"]["bytes"] == on_disk
    assert _one(spans, "store.write")["attrs"]["bytes"] == on_disk
    assert _one(spans, "store.checksum")["attrs"]["bytes"] == on_disk
    assert _one(spans, "store.read")["attrs"]["bytes"] == on_disk
    assert _one(spans, "to_store")["attrs"]["rows"] == N
    assert _one(spans, "to_store")["attrs"]["sink"] == out


def test_settle_is_inside_run_and_span_times_nest(sort_spans):
    spans, _out = sort_spans
    by_id = {s["span"]: s for s in spans}
    for s in spans:
        assert s["t0"] == round(s["t0"], 6)
        if s.get("parent"):
            p = by_id[s["parent"]]
            assert p["t0"] - 1e-3 <= s["t0"]
            assert s["t0"] + s["dur_s"] <= p["t0"] + p["dur_s"] + 1e-3


class _Counting(trace.Span):
    built = 0

    def __init__(self, *a, **kw):
        _Counting.built += 1
        super().__init__(*a, **kw)


@pytest.mark.parametrize("how", ["level_1", "no_event_log"])
def test_spans_off_build_nothing(tmp_path, monkeypatch, how):
    src = _input_store(tmp_path, 1)
    monkeypatch.setattr(trace, "Span", _Counting)
    monkeypatch.setattr(_Counting, "built", 0)
    events = []
    if how == "level_1":
        monkeypatch.setenv("DRYAD_LOGGING_LEVEL", "1")
        ctx = _ctx(1, events)
    else:
        ctx = _ctx(1, None)
    _sort_query(ctx, src, str(tmp_path / "out"))
    _sql_query(ctx, src)
    assert _spans(events) == []
    assert _Counting.built == 0
    if how == "level_1":        # the other events still flow
        assert any(e.get("event") == "stage_done" for e in events)


def _program_names(ndev, build):
    ctx = _ctx(ndev, None)
    ds = build(ctx.from_columns(_columns()))
    graph = plan_query(ds.node, ctx.nparts, hosts=ctx.hosts,
                       levels=ctx.levels, config=ctx.config)
    return [stage_program_name(st) for st in graph.stages]


def _sorted(ds):
    return ds.order_by([("k", False)])


def _grouped(ds):
    return ds.group_by(["g"], {"s": ("sum", "v")})


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("build", [_sorted, _grouped],
                         ids=["sort", "group_by"])
def test_program_name_is_a_function_of_the_plan(ndev, build):
    a, b = _program_names(ndev, build), _program_names(ndev, build)
    assert a == b
    for name in a:
        assert re.fullmatch(r"[a-z0-9_]{1,64}", "jit_" + name)
        assert name.startswith("stage_")


@pytest.mark.parametrize("ndev", [1, 4])
def test_program_name_tells_a_sort_from_a_group_by(ndev):
    assert set(_program_names(ndev, _sorted)).isdisjoint(
        _program_names(ndev, _grouped))


def test_long_labels_are_cut_to_64():
    class _Op:
        kind = "Some-Very Long.Kind"

    class _Stage:
        label = "x" * 100
        legs = ()
        body = (_Op(), _Op())
    name = "jit_" + stage_program_name(_Stage())
    assert re.fullmatch(r"[a-z0-9_]{1,64}", name)


def test_compiled_module_carries_the_name():
    """What the profiler's ``XLA Modules`` line will say."""
    ctx = _ctx(1, None)
    ds = _sorted(ctx.from_columns(_columns()))
    graph = plan_query(ds.node, ctx.nparts, hosts=ctx.hosts,
                       levels=ctx.levels, config=ctx.config)
    (stage,) = graph.stages
    (source,) = [n for n in E.walk(ds.node) if isinstance(n, E.Source)]
    fn = ctx.executor._build_stage_fn(stage, 1, 1, 1, False)
    text = fn.lower(source.data.batch).as_text()
    assert "jit_" + stage_program_name(stage) in text
    assert "jit_per_shard" not in text


# -- the kernel scopes a device profile reads (ISSUE 38) ----------------------

_HEAVY_OP = re.compile(r"\s(sort|gather|scatter)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def _heavy_op_scopes(text):
    """Every scope of the vocabulary on the op_name of the sort, gather
    and scatter instructions of a compiled program; and those of the
    instructions that lie under none."""
    from perfbench.kernel_scopes import scopes_of
    seen, bare = set(), []
    for line in text.splitlines():
        if not _HEAVY_OP.search(line):
            continue
        m = _OP_NAME.search(line)
        sc = scopes_of(m.group(1) if m else None)
        seen.update(sc)
        if not sc:
            bare.append(line.strip()[:120])
    return seen, bare


def _stage_texts(ctx, ds):
    """Each stage program of the query compiled, its inputs' shapes from
    the stages before it run abstractly."""
    graph = plan_query(ds.node, ctx.nparts, hosts=ctx.hosts,
                       levels=ctx.levels, config=ctx.config)
    results, texts = {}, []
    for st in graph.stages:
        args = [results[leg.src] if isinstance(leg.src, int)
                else leg.src[1].batch for leg in st.legs]
        fn = ctx.executor._build_stage_fn(
            st, 1, ctx.config.initial_send_slack, len(args), False)
        results[st.id] = jax.eval_shape(fn, *args)[0]
        texts.append(fn.lower(*args).compile().as_text())
    return "\n".join(texts)


def _q3_shaped(ctx):
    rng = np.random.default_rng(5)
    n = 256
    cat = sql.Catalog()
    cat.register_columns("c", {
        "c_key": np.arange(n // 8, dtype=np.int32),
        "c_seg": rng.integers(0, 3, n // 8).astype(np.int32)})
    cat.register_columns("o", {
        "o_key": np.arange(n // 2, dtype=np.int32),
        "o_cust": rng.integers(0, n // 8, n // 2).astype(np.int32),
        "o_date": rng.integers(0, 100, n // 2).astype(np.int32)})
    cat.register_columns("l", {
        "l_order": rng.integers(0, n // 2, n).astype(np.int32),
        "l_price": rng.uniform(1, 9, n).astype(np.float32),
        "l_ship": rng.integers(0, 100, n).astype(np.int32)})
    return sql.query(ctx, cat, """
        select l_order, sum(l_price) as rev, o_date from c, o, l
        where c_seg = 1 and c_key = o_cust and l_order = o_key
              and o_date < 50 and l_ship > 50
        group by l_order, o_date order by rev desc limit 10""")


@pytest.mark.parametrize("query,want", [
    ("sort", {"index_sort", "row_gather"}),
    ("group_by", {"group_aggregate", "index_sort"}),
    ("two_joins", {"hash_join", "search", "index_sort", "row_gather",
                   "compact", "group_aggregate"}),
])
def test_stage_programs_name_their_kernels(monkeypatch, query, want):
    """A one-partition sort (index sort + packed gather), a group-by and a
    Q3-shaped query of two hash joins: the scopes of the kernels they run
    are path components of their sorts', gathers' and scatters' op names,
    and no sort, gather or scatter lies under none."""
    from dryad_tpu.ops import kernels
    monkeypatch.setattr(kernels, "_VALOPS_MAX_ELEMS", 0)
    monkeypatch.setattr(kernels, "_GATHER_CHUNK", 64)
    ctx = _ctx(1, None)
    if query == "two_joins":
        ds = _q3_shaped(ctx)
    else:
        ds = (_sorted if query == "sort" else _grouped)(
            ctx.from_columns(_columns()))
    seen, bare = _heavy_op_scopes(_stage_texts(ctx, ds))
    assert want <= seen, (want - seen, seen)
    assert not bare, bare


def test_the_exchange_names_its_pack_and_unpack():
    """A range exchange over four devices on the pack path: the dest sort
    and ``slot_expand`` under ``exchange_pack``, ``slot_compact`` under
    ``exchange_unpack``, each with its kernel scope inside; the
    ``all-to-all`` under neither."""
    from jax.sharding import Mesh, PartitionSpec as P
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops.pallas_kernels import force_interpret
    from dryad_tpu.parallel import shuffle
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("dp",))
    cap = 256
    rng = np.random.default_rng(0)
    batch = Batch({"k": rng.integers(0, 1 << 20, (4, cap)).astype(np.int32),
                   "v": rng.uniform(size=(4, cap)).astype(np.float32)},
                  np.full((4,), cap, np.int32))
    bounds = np.sort(rng.integers(0, 1 << 20, 3)).astype(np.uint32)
    bounds = np.stack([bounds ^ np.uint32(1 << 31),
                       np.zeros(3, np.uint32)], axis=1)

    def per_shard(b, bnd):
        b = jax.tree.map(lambda x: x[0], b)
        out, *_ = shuffle.range_exchange(b, [("k", False)], bnd, cap)
        return jax.tree.map(lambda x: x[None], out)

    fn = jax.jit(jax.shard_map(per_shard, mesh=mesh,
                               in_specs=(P("dp"), P()), out_specs=P("dp"),
                               check_vma=False))
    with force_interpret():
        text = fn.lower(batch, bounds).compile().as_text()
    pack = [ln for ln in text.splitlines() if "/exchange_pack/" in ln]
    unpack = [ln for ln in text.splitlines() if "/exchange_unpack/" in ln]
    assert any(_HEAVY_OP.search(ln) and "/index_sort/" in ln for ln in pack)
    assert any(_HEAVY_OP.search(ln) and "/row_gather/" in ln for ln in pack)
    assert any(_HEAVY_OP.search(ln) and "/index_sort/" in ln
               for ln in unpack)
    assert any(_HEAVY_OP.search(ln) and "/row_gather/" in ln
               for ln in unpack)
    a2a = [ln for ln in text.splitlines() if " all-to-all(" in ln]
    assert a2a and not any("/exchange_" in ln for ln in a2a)


def test_one_store_fetch_a_partition_on_four_devices(tmp_path):
    src = _input_store(tmp_path, 4)
    events = []
    _sort_query(_ctx(4, events), src, str(tmp_path / "out"))
    spans = _spans(events)
    fetches = [s for s in spans if s["name"] == "store.fetch"]
    assert sorted(s["attrs"]["partition"] for s in fetches) == [0, 1, 2, 3]
    write = _one(spans, "store.write")
    assert {s["parent"] for s in fetches} == {write["span"]}
    assert write["attrs"]["partitions"] == 4
    assert sum(s["attrs"]["bytes"] for s in fetches) \
        == write["attrs"]["bytes"]
    assert _one(spans, "store.file_write")["attrs"]["files"] == 4
    # two planned stages, and a span a stage attempt, each with its name
    programs = {s["attrs"]["program"] for s in spans
                if s["kind"] == "stage"}
    assert "jit_stage_orderby_range_sort" in programs


# -- what the bounded gathers fetched rides the stage info vector (ISSUE 37)


def _gather_counts(events):
    done = [e for e in events if e.get("event") == "stage_done"]
    assert done and all("gather_rows" in e and "gather_rows_cap" in e
                        for e in done)
    return (sum(e["gather_rows"] for e in done),
            sum(e["gather_rows_cap"] for e in done))


@pytest.mark.parametrize("query,share", [
    ("sort", "full"), ("filter", "part"), ("carried", "none")])
def test_stage_done_says_what_the_bounded_gathers_fetched(monkeypatch,
                                                          query, share):
    """``gather_rows`` / ``gather_rows_cap`` on ``stage_done``: a sort of
    a full batch fetches its capacity, a filter that keeps 10 of 4,096
    rows one chunk of 64, and a program whose sorts carry their values
    (no gather) reports 0 of 0."""
    from dryad_tpu.ops import kernels
    if share != "none":
        monkeypatch.setattr(kernels, "_VALOPS_MAX_ELEMS", 0)
        monkeypatch.setattr(kernels, "_GATHER_CHUNK", 64)
    events = []
    ds = _ctx(1, events).from_columns(_columns())
    if query == "filter":
        out = ds.where(lambda c: c["k"] < 10).collect()
        assert sorted(out["k"].tolist()) == list(range(10))
    else:
        out = ds.order_by([("k", False)]).collect()
        assert out["k"].tolist() == list(range(N))
    fetched, unbounded = _gather_counts(events)
    # and the benchmark's reader of the two, over the same events
    from perfbench.layers import gather_live_share
    read = gather_live_share.read({"queries": [{"i": 0, "events": events}]})
    if share == "full":
        assert fetched == unbounded > 0 and read == 1.0
    elif share == "part":
        assert (fetched, unbounded) == (64, N) and read == 64 / N
    else:
        assert (fetched, unbounded) == (0, 0) and read is None


def _done(stage, rows, cap, **more):
    return {"event": "stage_done", "stage": stage, "gather_rows": rows,
            "gather_rows_cap": cap, **more}


@pytest.mark.parametrize("queries,want", [
    ([[{"event": "stage_done", "stage": 0}]], None),     # an older program
    ([[_done(0, 0, 0)]], None),                          # no bounded gather
    ([[_done(0, 10, 100), _done(1, 40, 100)]], 0.25),    # summed over stages
    ([[_done(0, 100, 100, overflow=True), _done(0, 50, 200)]], 0.25),
    ([[_done(0, 10, 100)], [_done(0, 30, 100)], [_done(0, 20, 100)]], 0.2),
], ids=["no-lanes", "no-gather", "stages", "settled-attempt", "median"])
def test_gather_live_share_reader(queries, want):
    from perfbench.layers import gather_live_share
    run = {"queries": [{"i": i, "events": ev}
                       for i, ev in enumerate(queries)]}
    assert gather_live_share.read(run) == want
