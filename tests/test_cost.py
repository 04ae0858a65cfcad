"""Static cost & resource analyzer (analysis/cost.py + domain.py).

Soundness is the contract: predicted per-stage byte intervals must
CONTAIN the executor's measured ``out_bytes`` (the runtime cross-check
emits ``cost_model_miss`` otherwise), and the upper bound must be tight
(within 4x of measured) or the OOM gate is useless.  The sweep below
asserts both across all five bench apps; the rest covers the DTA2xx
diagnostic family (provable OOM rejected pre-submit with ZERO work
started), the adapt/ priors surface, the offline CLI, the viewer
section, and the ``--selfcheck`` gate (satellite: tier-1 catches
analyzer rot).
"""

import json
import pathlib

import numpy as np
import pytest

from dryad_tpu import Context
from dryad_tpu.analysis import LintError
from dryad_tpu.analysis.cost import (CostReport, StageCostEstimate,
                                     check_stage_measurement,
                                     cost_diagnostics, estimate_graph,
                                     estimate_plan_json)
from dryad_tpu.analysis.domain import ColSpec, Interval, out_bytes
from dryad_tpu.plan import expr as E
from dryad_tpu.plan.planner import plan_query
from dryad_tpu.utils.config import JobConfig
from dryad_tpu.utils.events import EventLog

REPO = pathlib.Path(__file__).resolve().parent.parent

# acceptance bound: the predicted byte upper bound may not exceed 4x the
# measured value on the bench apps (a sound but useless bound fails too)
TIGHTNESS = 4.0


def _ctx(log=None, **cfg):
    cfg.setdefault("lint", "warn")
    return Context(config=JobConfig(**cfg), event_log=log)


def _kv(ctx, n=512, seed=0):
    rng = np.random.RandomState(seed)
    return ctx.from_columns(
        {"k": rng.randint(0, 32, n).astype(np.int32),
         "v": rng.rand(n).astype(np.float32)})


# ---------------------------------------------------------------------------
# domain


def test_interval_algebra():
    assert Interval.exact(5).contains(5)
    assert not Interval.exact(5).contains(4)
    assert Interval.upto(None).contains(10 ** 12)
    assert (Interval(2, 6) + Interval(1, None)).as_tuple() == (3, None)
    assert Interval(2, 6).scale(3).as_tuple() == (6, 18)
    assert Interval(2, None).clamp_hi(10).as_tuple() == (2, 10)
    assert Interval(8, 9).clamp_hi(4).as_tuple() == (4, 4)
    assert Interval(3, 7).relax_lo().as_tuple() == (0, 7)
    assert Interval(1, 4).union(Interval(2, None)).as_tuple() == (1, None)


def test_out_bytes_matches_executor_formula():
    # [P, cap] f32 + count vector: nparts * (cap*4 + 4)
    schema = {"v": ColSpec("dense", "float32")}
    assert out_bytes(schema, 100, 8) == 8 * (100 * 4 + 4)
    # str column: repeat * (max_len + 4) per row
    schema = {"s": ColSpec("str", max_len=16)}
    assert out_bytes(schema, 10, 2) == 2 * (10 * 20 + 4)


# ---------------------------------------------------------------------------
# the soundness sweep: all five bench apps


def _wordcount(ctx):
    from dryad_tpu.apps.wordcount import wordcount_query
    rng = np.random.RandomState(0)
    vocab = ["alpha", "beta", "gamma", "delta", "epsilon"]
    lines = [" ".join(rng.choice(vocab, rng.randint(1, 8)))
             for _ in range(200)]
    ds = ctx.from_columns({"line": [l.encode() for l in lines]},
                          str_max_len=64)
    return wordcount_query(ds, tokens_per_partition=2048)


def _terasort(ctx):
    from dryad_tpu.apps.terasort import gen_records, terasort_query
    return terasort_query(
        ctx.from_columns(gen_records(512), str_max_len=10))


def _groupbyreduce(ctx):
    from dryad_tpu.apps.groupbyreduce import gen_pairs, groupbyreduce_query
    return groupbyreduce_query(ctx.from_columns(gen_pairs(1024, 16)))


def _kmeans_step(ctx):
    from dryad_tpu.apps.kmeans import _assign_fn, _assign_host, gen_points
    pts_cols, _ = gen_points(256, 4, 3)
    pts = ctx.from_columns(pts_cols)
    cents = ctx.from_columns(
        {"cid": np.arange(3, dtype=np.int32),
         "cx": np.zeros((3, 4), np.float32)})
    return (pts.cross_apply(cents, _assign_fn, host_fn=_assign_host)
               .group_by(["cid"], {"cx": ("mean", "x")})
               .with_capacity(3))


def _pagerank_join(ctx):
    from dryad_tpu.apps.pagerank import gen_graph
    edges = ctx.from_columns(gen_graph(32, 64))
    deg = edges.group_by(["src"], {"deg": ("count", None)})
    edges_deg = edges.join(deg, ["src"], ["src"], expansion=2.0,
                           right_unique=True)
    ranks = ctx.from_columns(
        {"node": np.arange(32, dtype=np.int32),
         "rank": np.full(32, 1 / 32, np.float32)})
    contribs = edges_deg.join(ranks, ["src"], ["node"], expansion=2.0,
                              right_unique=True)
    return (contribs
            .select(lambda c: {"node": c["dst"],
                               "c": c["rank"] / c["deg"]})
            .group_by(["node"], {"s": ("sum", "c")})
            .with_capacity(64))


def _where_groupby(ctx):
    """WHERE -> GROUP BY: the executor groups under the filter's mask
    (one fused ``where_group`` op), and the model follows it."""
    return (_kv(ctx).where(lambda c: c["v"] < 0.5)
            .group_by(["k"], {"s": ("sum", "v"), "n": ("count", None)}))


APPS = {"wordcount": _wordcount, "terasort": _terasort,
        "groupbyreduce": _groupbyreduce, "kmeans": _kmeans_step,
        "pagerank-join": _pagerank_join, "where-groupby": _where_groupby}


@pytest.mark.parametrize("app", sorted(APPS))
def test_soundness_sweep(app):
    """Predicted per-stage byte intervals are upper bounds on measured
    ``out_bytes`` (within 4x) and the runtime cross-check stays silent:
    zero ``cost_model_miss`` events across the five bench apps and the
    fused filter -> group-by."""
    log = EventLog(level=2)
    ctx = _ctx(log)
    APPS[app](ctx).collect()

    misses = [e for e in log.events if e["event"] == "cost_model_miss"]
    assert misses == [], f"{app}: cost model missed: {misses}"

    # walk events in order, pairing each stage_done with the cost_report
    # of ITS run (a query may materialize several graphs)
    report = None
    checked = 0
    for e in log.events:
        if e["event"] == "cost_report":
            report = {s["stage"]: s for s in e["report"]["stages"]}
        if e["event"] != "stage_done" or report is None:
            continue
        est = report.get(e["stage"])
        if est is None or est["approx"]:
            continue
        # bytes are predicted for the PLANNED shapes: overflow retries
        # (scale > 1) right-size capacities and validate nothing
        if e["scale"] != 1:
            continue
        lo, hi = est["out_bytes"]
        measured = e["out_bytes"]
        assert hi is not None and lo <= measured <= hi, \
            f"{app} stage {e['stage']}: measured {measured} outside " \
            f"predicted [{lo}, {hi}]"
        assert hi <= TIGHTNESS * measured, \
            f"{app} stage {e['stage']}: bound {hi} looser than " \
            f"{TIGHTNESS}x measured {measured}"
        rlo, rhi = est["rows"]
        rows = int(sum(e["rows"]))
        assert rlo <= rows and (rhi is None or rows <= rhi)
        checked += 1
    assert checked >= 1, f"{app}: no stage was cross-checked"


def test_overflow_retry_is_not_a_miss():
    """An undersized flat_tokens capacity settles at scale > 1 — the
    executor's own adaptation, not a model miss: the bytes check is
    scale-1-only by contract."""
    from dryad_tpu.apps.wordcount import wordcount_query
    log = EventLog(level=2)
    ctx = _ctx(log)
    lines = [b"a b c d e f g h"] * 64
    ds = ctx.from_columns({"line": lines}, str_max_len=32)
    wordcount_query(ds, tokens_per_partition=16).collect()
    assert any(e["event"] == "stage_done" and e["scale"] > 1
               for e in log.events)
    assert not any(e["event"] == "cost_model_miss" and
                   e["what"] == "out_bytes" for e in log.events)


# ---------------------------------------------------------------------------
# DTA2xx gate


def test_dta201_provable_oom_rejected_pre_submit(monkeypatch):
    """A plan sized past device_hbm_bytes fails the lint=error gate with
    DTA201 naming the offending stage and its footprint — and ZERO
    executor work starts."""
    from dryad_tpu.exec.executor import Executor
    runs = []
    orig = Executor.run

    def counting(self, *a, **k):
        runs.append(1)
        return orig(self, *a, **k)

    monkeypatch.setattr(Executor, "run", counting)
    ctx = _ctx(lint="error", device_hbm_bytes=1 << 20)
    big = (ctx.from_columns({"x": np.zeros(8, np.float32)})
              .with_capacity(1 << 22))
    with pytest.raises(LintError) as ei:
        big.order_by([("x", True)]).collect()
    errs = ei.value.report.by_code("DTA201")
    assert errs and all(d.severity == "error" for d in errs)
    # the finding names the stage and quotes the predicted footprint
    assert any(d.node and d.node.startswith("stage") for d in errs)
    assert any("device_hbm_bytes" in d.message for d in errs)
    assert runs == [], "executor ran despite the pre-submit rejection"


def test_dta202_predicted_spill_warn():
    """hbm between the certain floor and the working-set ceiling: not a
    provable OOM (no error) but a predicted spill (warn)."""
    ctx0 = _ctx()
    q0 = _kv(ctx0, n=1024).group_by(["k"], {"s": ("sum", "v")})
    rep0 = q0.cost()
    lo = max(s.work_bytes.lo for s in rep0.stages)
    hi = max(s.work_bytes.hi for s in rep0.stages)
    assert lo < hi
    ctx = _ctx(device_hbm_bytes=(lo + hi) // 2)
    rep = _kv(ctx, n=1024).group_by(
        ["k"], {"s": ("sum", "v")}).check(cost=True)
    assert "DTA202" in rep.codes()
    assert "DTA201" not in rep.codes()
    assert all(d.severity == "warn" for d in rep.by_code("DTA202"))


def test_dta203_unbounded_fanout_at_exchange():
    """A row-unbounded input (loop placeholder) feeding an exchange sizes
    the buffer blind — warn.  Plans with real source statistics stay
    silent."""
    ctx = _ctx()
    ph = E.Placeholder(parents=(), name="__loop", _npartitions=8)
    node = E.GroupByAgg(parents=(ph,), keys=("k",),
                        aggs={"s": ("sum", "v")})
    graph = plan_query(node, 8, config=ctx.config)
    rep = estimate_graph(graph, 8, config=ctx.config)
    ds = cost_diagnostics(rep, ctx.config)
    assert any(d.code == "DTA203" and d.severity == "warn" for d in ds)
    # a statistically seeded source through the same shape: no DTA203
    clean = _kv(ctx).group_by(["k"], {"s": ("sum", "v")}).check(cost=True)
    assert "DTA203" not in clean.codes()


def test_dta204_edge_scale_cache_warn():
    """cache() of edge-scale data: with the re-streaming cache tier ON
    (default) the finding is INFO and the cache LOWERS to a local
    chunked store (the cached dataset streams); with the tier OFF it
    WARNS and the result pins device memory (legacy).  Never a gate
    failure: cache() works either way."""
    log = EventLog(level=2)
    ctx = _ctx(log, device_hbm_bytes=1 << 20)
    big = ctx.from_columns({"x": np.zeros((64, 4096), np.float32)})
    cached = big.cache()
    found = [e for e in log.events
             if e["event"] == "lint_finding" and e["code"] == "DTA204"]
    assert found and all(e["severity"] == "info" for e in found)
    assert "re-streaming cache tier" in found[0]["message"]
    # the lowering really happened: the cached dataset is streamed, a
    # cold cache write was recorded, and the rows survive intact
    assert cached._streaming()
    assert any(e["event"] == "ooc_cache_write" for e in log.events)
    out = cached.collect()
    assert np.asarray(out["x"]).shape == (64, 4096)
    # tier off (the A/B lever): legacy warn + device-resident cache
    log_off = EventLog(level=2)
    ctx_off = _ctx(log_off, device_hbm_bytes=1 << 20,
                   ooc_restream_cache=False)
    big_off = ctx_off.from_columns({"x": np.zeros((64, 4096),
                                                  np.float32)})
    cached_off = big_off.cache()
    found_off = [e for e in log_off.events
                 if e["event"] == "lint_finding"
                 and e["code"] == "DTA204"]
    assert found_off and all(e["severity"] == "warn" for e in found_off)
    assert not cached_off._streaming()
    # a small cache stays silent
    log2 = EventLog(level=2)
    ctx2 = _ctx(log2, device_hbm_bytes=1 << 30)
    _kv(ctx2, n=64).cache()
    assert not any(e["event"] == "lint_finding" and e["code"] == "DTA204"
                   for e in log2.events)


def test_dta205_cost_summary_info():
    ctx = _ctx()
    rep = _kv(ctx).group_by(["k"], {"s": ("sum", "v")}).check(cost=True)
    info = rep.by_code("DTA205")
    assert info and all(d.severity == "info" for d in info)
    assert rep.clean      # info never dirties a plan


# ---------------------------------------------------------------------------
# runtime cross-check contract


def test_check_stage_measurement_contract():
    est = StageCostEstimate(0, "s", Interval(10, 20), 32,
                            Interval.exact(1000), Interval(0, 4000))
    # inside both intervals: silent
    assert check_stage_measurement(est, 1, 15, 1000, 8) == []
    # rows outside: always a miss, any scale
    m = check_stage_measurement(est, 2, 25, 1000, 8)
    assert [x["what"] for x in m] == ["rows"]
    # bytes outside at scale 1: a miss
    m = check_stage_measurement(est, 1, 15, 999, 8)
    assert [x["what"] for x in m] == ["out_bytes"]
    assert all(x["event"] == "cost_model_miss" for x in m)
    # bytes outside at scale > 1: executor adaptation, not a model miss
    assert check_stage_measurement(est, 2, 15, 4000, 8) == []
    # approximate estimates were widened on purpose: skipped entirely
    approx = StageCostEstimate(0, "s", Interval(10, 20), 32,
                               Interval.upto(None), Interval(0, None),
                               approx=True)
    assert check_stage_measurement(approx, 1, 999, 999, 8) == []


def test_cost_report_payload_roundtrip():
    rep = CostReport(8, [StageCostEstimate(
        0, "groupby", Interval(1, 64), 16, Interval.exact(528),
        Interval(528, 2000), notes=("n1",))], device_hbm_bytes=123)
    back = CostReport.from_payload(
        json.loads(json.dumps(rep.to_payload())))
    assert back.nparts == 8 and back.device_hbm_bytes == 123
    assert back.bounds(0) == (Interval(1, 64), Interval.exact(528))
    assert back.capacity_of(0) == 16
    assert back.stage(0).notes == ("n1",)
    assert "groupby" in back.render()


# ---------------------------------------------------------------------------
# adapt/ consumes the static bounds as priors


def test_adapt_rows_bounds_prior():
    from dryad_tpu.adapt.rules import RuleContext, rows_bounds
    from dryad_tpu.adapt.stats import StageStats
    rep = CostReport(8, [StageCostEstimate(
        3, "s", Interval(2, 40), 8, Interval.exact(100),
        Interval(0, 100))])
    ctx = RuleContext(rw=None, stats={}, config=JobConfig(),
                      nparts=8, levels=(), cost=rep)
    # unmaterialized stage: the static interval is the prior
    assert rows_bounds(ctx, 3) == (2, 40)
    # unknown stage: no prior
    assert rows_bounds(ctx, 9) is None
    # measured stats win over the prior (exact)
    ctx.stats[3] = StageStats(3, (5, 5), capacity=8, out_bytes=100,
                              wall_s=0.0)
    assert rows_bounds(ctx, 3) == (10, 10)


# ---------------------------------------------------------------------------
# surfaces: CLI, explain, viewer, selfcheck


def test_offline_plan_cost_cli(tmp_path, capsys):
    from dryad_tpu.analysis.__main__ import main
    from dryad_tpu.plan.serialize import graph_to_json
    ctx = _ctx()
    graph = plan_query(
        _kv(ctx).group_by(["k"], {"s": ("sum", "v")}).node, ctx.nparts,
        config=ctx.config)
    p = tmp_path / "plan.json"
    p.write_text(graph_to_json(graph))
    assert main([str(p), "--cost", "--nparts", "8"]) == 0
    out = capsys.readouterr().out
    assert "peak per-device working set" in out
    # serialized plans carry no schemas: capacities compute, bytes don't
    rep = estimate_plan_json(p.read_text(), nparts=8)
    assert rep.stages and all(s.approx for s in rep.stages)
    assert any(s.capacity for s in rep.stages)


def test_explain_and_check_cost_surface():
    ctx = _ctx()
    q = _kv(ctx).group_by(["k"], {"s": ("sum", "v")})
    text = q.explain(cost=True)
    assert "predicted cost:" in text
    assert "work/dev" in text
    # Dataset.cost() is the machine-readable surface
    rep = q.cost()
    assert rep.stages and rep.nparts == ctx.nparts
    assert all(s.out_bytes.hi is not None for s in rep.stages)


def test_viewer_predicted_cost_section():
    from dryad_tpu.utils.viewer import job_report_html
    log = EventLog(level=2)
    ctx = _ctx(log)
    _kv(ctx).group_by(["k"], {"s": ("sum", "v")}).collect()
    html = job_report_html(log.events)
    assert "Predicted cost" in html
    assert "no cost-model misses" in html
    # a miss renders the warning list
    events = list(log.events) + [
        {"event": "cost_model_miss", "stage": 0, "label": "x",
         "what": "rows", "measured": 9, "predicted": [1, 2]}]
    assert "cost-model miss" in job_report_html(events)


def test_streamed_plan_out_of_scope(tmp_path):
    """Chunk-streamed sources take the >HBM path by construction — the
    report says so instead of predicting garbage."""
    ctx = _ctx()
    pd = _kv(ctx, n=64)
    store = tmp_path / "st"
    pd.to_store(str(store))
    q = ctx.read_store_stream(str(store)).group_by(
        ["k"], {"s": ("sum", "v")})
    rep = q.cost()
    assert rep.streamed and not rep.stages
    assert "streamed plan" in rep.render()
    assert cost_diagnostics(rep, ctx.config) == []


def test_selfcheck_gate():
    """Satellite: `python -m dryad_tpu.analysis --selfcheck` (ruff/
    selflint + docs drift + committed-plan smoke) runs clean — wired
    here so tier-1 catches analyzer rot."""
    from dryad_tpu.analysis.__main__ import main
    assert main(["--selfcheck"]) == 0


def test_docs_table_drift():
    """docs/diagnostics.md is GENERATED from diagnostics.CODES — a code
    added without regenerating the table fails here, not in review."""
    from dryad_tpu.analysis.diagnostics import render_code_table
    docs = REPO / "docs" / "diagnostics.md"
    assert docs.exists(), "docs/diagnostics.md missing — regenerate " \
        "with `python -m dryad_tpu.analysis --selfcheck --write-docs`"
    assert docs.read_text() == render_code_table(), \
        "docs/diagnostics.md stale vs diagnostics.CODES — regenerate " \
        "with `python -m dryad_tpu.analysis --selfcheck --write-docs`"
