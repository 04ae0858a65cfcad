"""CPU smoke drives of the framework's subsystems.

Usage:  python bench.py --smoke[-adapt|-sql|-kernels|-service|-analyze|
                                 -ooc|-inc|-reuse|-latency|-durable] [OUT]

Each ``--smoke-*`` sub-command runs one subsystem end to end at a small
size, checks its result against the oracle, and writes a
``BENCH_<name>.json`` record (walls in those records are whatever backend
ran them — XLA's CPU backend in this repo's committed copies, never a
device number).  ``--smoke`` runs all of them.  There is no benchmark
here yet: ``chip_smoke.py`` proves the main path on the chip, and the
benchmark proper is future work (ROADMAP S0).
"""

import json
import os
import sys
import tempfile
import time

import numpy as np


def smoke(out_path="BENCH_obs.json", n_lines=None, reps=None):
    """Perf-smoke mode (``python bench.py --smoke``): a small traced
    wordcount, wall/compile/io split + telemetry overhead vs an untraced
    (DRYAD_LOGGING_LEVEL=0) run, written as ``BENCH_obs.json``.  Fast
    enough to ride the normal pytest tier (tests/test_obs.py), so the
    perf-trajectory file is refreshed on every run instead of staying
    empty between full bench captures.

    Both sides run ``reps`` (>= 3) measured repetitions, INTERLEAVED
    (untraced, traced, untraced, traced, ...), and report the MEDIAN: a
    single-shot comparison on a shared box reads scheduler noise as
    overhead (an earlier capture reported -3.4% "overhead", i.e. the
    traced run got the luckier slice), and back-to-back phases read
    load DRIFT as overhead — interleaving gives both sides the same
    weather.  Every capture also appends one record to
    ``BENCH_trend.jsonl`` next to ``out_path`` — the seed trajectory
    the job history server (``python -m dryad_tpu.obs history``) folds
    into its index."""
    import statistics
    import tempfile

    import jax

    from dryad_tpu import Context
    from dryad_tpu.apps import wordcount
    from dryad_tpu.obs.critical_path import critical_path
    from dryad_tpu.obs.metrics import metrics_from_events
    from dryad_tpu.parallel.mesh import make_mesh
    from dryad_tpu.utils.events import EventLog

    n_lines = n_lines or int(os.environ.get("BENCH_SMOKE_LINES", "20000"))
    reps = max(3, reps or int(os.environ.get("BENCH_SMOKE_REPS", "3")))
    rng = np.random.RandomState(0)
    vocab = np.array(["alpha", "beta", "gamma", "delta", "epsilon",
                      "zeta", "eta", "theta"])
    words_per_line = 6
    idx = rng.randint(0, len(vocab), (n_lines, words_per_line))
    lines = [" ".join(vocab[i]) for i in idx]
    mesh = make_mesh(jax.devices())
    nchips = mesh.devices.size
    per_part = -(-n_lines // nchips)
    cap = per_part * (words_per_line + 2)

    def make_query(log):
        ctx = Context(mesh=mesh, event_log=log)
        return wordcount.wordcount_query(
            ctx.from_columns({"line": lines}, str_max_len=64),
            tokens_per_partition=cap)

    jsonl = os.path.join(tempfile.mkdtemp(prefix="bench-obs-"),
                         "events.jsonl")
    # EventLog.close (the with-exit) detaches itself from the tracer.
    # The untraced reference runs at level 0 (errors only): span AND
    # sampler creation are no-ops; the explicit per-log level gates
    # them, so both queries coexist and alternate.
    with EventLog(level=0) as log0, EventLog(jsonl, level=2) as log:
        q0 = make_query(log0)     # untraced reference
        q1 = make_query(log)      # traced + sampled
        q0.collect()              # warmups: compiles (shared cache)
        q1.collect()
        untraced_walls, traced_walls, rep_events = [], [], []
        for _ in range(reps):
            t0 = time.time()
            q0.collect()
            untraced_walls.append(time.time() - t0)
            mark = len(log.events)
            t0 = time.time()
            q1.collect()
            traced_walls.append(time.time() - t0)
            rep_events.append(log.events[mark:])
        spans_untraced = len([e for e in log0.events
                              if e.get("event") == "span"])
    traced_s = statistics.median(traced_walls)
    untraced_s = statistics.median(untraced_walls)
    # the split / critical-path / span figures must describe the SAME
    # run as the reported wall: use the rep closest to the median (a
    # last-rep snapshot could pair a hiccup's split with a median wall)
    ev = rep_events[min(range(reps),
                        key=lambda i: abs(traced_walls[i] - traced_s))]

    comp = sum(e.get("compile_s", 0) for e in ev
               if e.get("event") == "stage_done")
    # the measured run usually hits the compile cache; the warmup's
    # compile wall (same log, earlier events) is the honest compile cost
    comp_warm = sum(e.get("compile_s", 0) for e in log.events
                    if e.get("event") == "stage_done")
    runw = sum(e.get("wall_s", 0) for e in ev
               if e.get("event") == "stage_done")
    io_s = sum(e.get("dur_s", 0) for e in ev
               if e.get("event") == "span" and e.get("kind") == "io")
    cp = critical_path(ev)
    snap = metrics_from_events(ev).snapshot()
    overhead = (round(100.0 * (traced_s - untraced_s) / untraced_s, 1)
                if untraced_s > 0 else None)
    out = {
        "metric": "obs smoke (traced wordcount)",
        "lines": n_lines,
        "n_chips": nchips,
        "reps": reps,
        "wall_s_traced": round(traced_s, 4),
        "wall_s_untraced": round(untraced_s, 4),
        "wall_s_traced_all": [round(w, 4) for w in traced_walls],
        "wall_s_untraced_all": [round(w, 4) for w in untraced_walls],
        "tracing_overhead_pct": overhead,
        "span_events_traced": len([e for e in ev
                                   if e.get("event") == "span"]),
        "span_events_untraced": spans_untraced,
        "resource_samples": sum(
            1 for r in rep_events for e in r
            if e.get("event") == "resource_sample"),
        "split": {"compile_s": round(comp, 4),
                  "compile_s_incl_warmup": round(comp_warm, 4),
                  "run_s": round(runw, 4), "io_s": round(io_s, 4)},
        "critical_path": {
            "total_s": cp["total_s"],
            "top": [{"name": s["name"], "kind": s["kind"],
                     "self_s": s["self_s"]} for s in cp["top"][:5]]},
        "metrics": snap,
        "events_jsonl": jsonl,
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    # bench-over-bench trajectory: one line per capture, read back by
    # the job history index (obs/history._trend_entries)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-smoke",
            "wall_s": round(traced_s, 4),
            "untraced_wall_s": round(untraced_s, 4),
            "overhead_pct": overhead,
            "compile_s": round(comp_warm, 4), "run_s": round(runw, 4),
            "io_s": round(io_s, 4), "lines": n_lines, "reps": reps,
            "n_chips": nchips}) + "\n")
    print(json.dumps(out))
    return out


def smoke_adapt(out_path="BENCH_adapt.json", n_rows=None, reps=None,
                quiet=False):
    """Adaptive-execution smoke (``python bench.py --smoke`` /
    ``--smoke-adapt``): a SKEWED SHUFFLE — a 90%-hot-key group_by whose
    ~1k-row output carries a conservative static capacity bound
    (``with_capacity``, the DTA010-recommended pattern for unknown
    fan-outs) into a global sort, so the downstream range exchange +
    sort run over the full padded envelope unless adaptation right-sizes
    them from the MEASURED rows — run adapt-on vs adapt-off,
    INTERLEAVED >=3 reps, median walls (the PR-4 protocol: both sides
    get the same scheduler weather).  The adaptive run must record a
    ``graph_rewrite`` and produce identical output rows; the wall delta
    is the value of right-sizing the downstream exchange from observed
    stats (adapt/rules.SkewRepartition).  Written to
    ``BENCH_adapt.json`` and appended to ``BENCH_trend.jsonl`` (app
    ``bench-adapt``)."""
    import statistics

    from dryad_tpu import Context
    from dryad_tpu.utils.config import JobConfig

    n_rows = n_rows or int(os.environ.get("BENCH_ADAPT_ROWS", "50000"))
    reps = max(3, reps or int(os.environ.get("BENCH_ADAPT_REPS", "5")))
    rng = np.random.RandomState(0)
    # 90% of rows on one key, the rest over 1k cold keys: the group
    # output is ~1k rows; the declared downstream bound is 131072
    k = np.where(rng.rand(n_rows) < 0.9, 0,
                 rng.randint(1, 1000, n_rows)).astype(np.int32)
    v = rng.randint(0, 10, n_rows).astype(np.int32)

    def make(adaptive, events):
        ctx = Context(event_log=events.append,
                      config=JobConfig(adaptive=adaptive))
        return (ctx.from_columns({"k": k, "v": v})
                .group_by(["k"], {"s": ("sum", "v")})
                .with_capacity(1 << 17)
                .order_by([("s", False)]))

    ev_on, ev_off = [], []
    q_on, q_off = make("on", ev_on), make("off", ev_off)
    out_on, out_off = q_on.collect(), q_off.collect()   # warmup+verify
    # rewrite count for ONE run (the warmup): later reps replan and
    # re-fire the same rewrites, which would inflate the figure reps-fold
    rewrites = [e for e in ev_on if e.get("event") == "graph_rewrite"]
    rows_identical = (
        sorted(zip(out_on["k"].tolist(), out_on["s"].tolist()))
        == sorted(zip(out_off["k"].tolist(), out_off["s"].tolist())))
    walls_on, walls_off = [], []
    for _ in range(reps):
        t0 = time.time()
        q_off.collect()
        walls_off.append(time.time() - t0)
        t0 = time.time()
        q_on.collect()
        walls_on.append(time.time() - t0)
    on_s = statistics.median(walls_on)
    off_s = statistics.median(walls_off)
    out = {
        "metric": "adapt smoke (skewed shuffle, adapt-on vs adapt-off)",
        "rows": n_rows,
        "reps": reps,
        "wall_s_adapt_on": round(on_s, 4),
        "wall_s_adapt_off": round(off_s, 4),
        "wall_s_adapt_on_all": [round(w, 4) for w in walls_on],
        "wall_s_adapt_off_all": [round(w, 4) for w in walls_off],
        "speedup_pct": (round(100.0 * (off_s - on_s) / off_s, 1)
                        if off_s > 0 else None),
        "graph_rewrites": len(rewrites),
        "rewrite_kinds": sorted({e.get("kind", "?") for e in rewrites}),
        "rows_identical": rows_identical,
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-adapt",
            "wall_s": round(on_s, 4),
            "adapt_off_wall_s": round(off_s, 4),
            "speedup_pct": out["speedup_pct"],
            "graph_rewrites": len(rewrites), "rows": n_rows,
            "reps": reps}) + "\n")
    if not quiet:
        print(json.dumps(out))
    return out


def smoke_sql(out_path="BENCH_sql.json", n_rows=None, reps=None,
              quiet=False):
    """SQL front-end smoke (``python bench.py --smoke`` /
    ``--smoke-sql``): a TPC-H-style SKEWED join+group query — lineitem
    with a 90%-hot order key joined to orders, filtered, grouped to
    per-order revenue, globally sorted, LIMIT 10 — compiled by
    dryad_tpu/sql and run adaptive-on vs adaptive-off, INTERLEAVED >=3
    reps, median walls (the PR-4 protocol).  The adaptive run must
    record at least one ``graph_rewrite`` with IDENTICAL result rows:
    the declarative front end exercising the optimizer stack on a real
    query shape is the point (ROADMAP item 5).  Written to
    ``BENCH_sql.json`` + appended to ``BENCH_trend.jsonl`` (app
    ``bench-sql``)."""
    import statistics

    from dryad_tpu import sql
    from dryad_tpu.api.dataset import Context
    from dryad_tpu.utils.config import JobConfig

    n_rows = n_rows or int(os.environ.get("BENCH_SQL_ROWS", "50000"))
    reps = max(3, reps or int(os.environ.get("BENCH_SQL_REPS", "5")))
    n_orders = 1000
    rng = np.random.RandomState(0)
    okey = np.where(rng.rand(n_rows) < 0.9, 0,
                    rng.randint(1, n_orders, n_rows)).astype(np.int32)
    cat = sql.Catalog()
    cat.register_columns("lineitem", {
        "okey": okey,
        "price": rng.randint(1, 100, n_rows).astype(np.int32),
        "qty": rng.randint(1, 10, n_rows).astype(np.int32)})
    cat.register_columns("orders", {
        "okey": np.arange(n_orders, dtype=np.int32),
        "flag": (np.arange(n_orders) % 2).astype(np.int32)})
    query = ("SELECT l.okey, SUM(l.price * l.qty) AS revenue, "
             "COUNT(*) AS n "
             "FROM lineitem l JOIN orders o ON l.okey = o.okey "
             "WHERE o.flag = 0 "
             "GROUP BY l.okey ORDER BY revenue DESC LIMIT 10")

    def make(adaptive, events):
        ctx = Context(event_log=events.append,
                      config=JobConfig(adaptive=adaptive))
        return sql.query(ctx, cat, query)

    ev_on, ev_off = [], []
    q_on, q_off = make("on", ev_on), make("off", ev_off)
    out_on, out_off = q_on.collect(), q_off.collect()  # warmup+verify
    rewrites = [e for e in ev_on if e.get("event") == "graph_rewrite"]

    def rows(t):
        return sorted(zip(np.asarray(t["okey"]).tolist(),
                          np.asarray(t["revenue"]).tolist(),
                          np.asarray(t["n"]).tolist()))

    rows_identical = rows(out_on) == rows(out_off)
    walls_on, walls_off = [], []
    for _ in range(reps):
        t0 = time.time()
        q_off.collect()
        walls_off.append(time.time() - t0)
        t0 = time.time()
        q_on.collect()
        walls_on.append(time.time() - t0)
    on_s = statistics.median(walls_on)
    off_s = statistics.median(walls_off)
    out = {
        "metric": "sql smoke (TPC-H-style skewed join+group via the "
                  "SQL front end, adapt-on vs adapt-off)",
        "rows": n_rows,
        "reps": reps,
        "query": sql.normalize_query(query),
        "wall_s_adapt_on": round(on_s, 4),
        "wall_s_adapt_off": round(off_s, 4),
        "wall_s_adapt_on_all": [round(w, 4) for w in walls_on],
        "wall_s_adapt_off_all": [round(w, 4) for w in walls_off],
        "speedup_pct": (round(100.0 * (off_s - on_s) / off_s, 1)
                        if off_s > 0 else None),
        "graph_rewrites": len(rewrites),
        "rewrite_kinds": sorted({e.get("kind", "?") for e in rewrites}),
        "rows_identical": rows_identical,
        "sql_events": sum(1 for e in ev_on
                          if e.get("event") == "sql_query"),
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-sql",
            "wall_s": round(on_s, 4),
            "adapt_off_wall_s": round(off_s, 4),
            "speedup_pct": out["speedup_pct"],
            "graph_rewrites": len(rewrites), "rows": n_rows,
            "reps": reps}) + "\n")
    if not quiet:
        print(json.dumps(out))
    return out


def smoke_inc(out_path="BENCH_inc.json", n_rows=None, rounds=None,
              reps=None, quiet=False):
    """Continuous-query smoke (``python bench.py --smoke`` /
    ``--smoke-inc``): a standing group-sum query over a store growing
    5% per round — each round measures one INCREMENTAL refresh
    (watermark-scoped delta scan + host merge into persisted state,
    dryad_tpu/inc) against a FULL re-run of the same statement over the
    whole store, INTERLEAVED >=3 reps, median walls (the PR-4
    protocol).  The rows must be BIT-IDENTICAL every round — the
    decomposable-merge correctness claim is the point, the wall-clock
    ratio is the payoff (ISSUE-16 bar: warm refresh >= 2x faster than
    the full re-run at 5% growth).  Written to ``BENCH_inc.json`` +
    appended to ``BENCH_trend.jsonl`` (app ``bench-inc``)."""
    import shutil
    import statistics
    import tempfile

    from dryad_tpu import sql
    from dryad_tpu.api.dataset import Context
    from dryad_tpu.inc import state as inc_state
    from dryad_tpu.inc.refresh import run_refresh
    from dryad_tpu.io.store import append_store, store_meta

    n_rows = n_rows or int(os.environ.get("BENCH_INC_ROWS", "200000"))
    rounds = rounds or int(os.environ.get("BENCH_INC_ROUNDS", "3"))
    reps = max(3, reps or int(os.environ.get("BENCH_INC_REPS", "3")))
    growth = 0.05
    n_keys = 64

    tmp = tempfile.mkdtemp(prefix="dryad-bench-inc-")
    store = os.path.join(tmp, "store")
    state_dir = os.path.join(tmp, "state")
    ctx = Context(install_trace=False)

    def batch(n, seed):
        r = np.random.RandomState(seed)
        return {"k": r.randint(0, n_keys, n).astype(np.int32),
                "v": r.randint(0, 1000, n).astype(np.int32)}

    ctx.from_columns(batch(n_rows, 1)).to_store(store)
    cat = sql.Catalog().register_store("t", store)
    query = ("SELECT k, SUM(v) AS s, COUNT(*) AS c FROM t "
             "GROUP BY k EMIT EVERY 1")
    norm = sql.normalize_query(query)
    _mode, bound = sql.compile_query(cat, query)
    full_bound = sql.compile_query(
        cat, "SELECT k, SUM(v) AS s, COUNT(*) AS c FROM t GROUP BY k")[1]
    sp = inc_state.state_path(
        state_dir, inc_state.state_key(norm, "t", store,
                                       store_meta(store)["schema"]))

    def full_run():
        ds, _ = sql.lower(ctx, cat, full_bound)
        return ds.collect()

    def rows_of(table):
        return sorted(zip(np.asarray(table["k"]).tolist(),
                          np.asarray(table["s"]).tolist(),
                          np.asarray(table["c"]).tolist()))

    # round 0 builds the initial state (the one full-priced refresh);
    # warmup for both sides' compile caches too
    run_refresh(ctx, cat, bound, norm, state_dir)
    full_run()

    identical = True
    per_round = []
    inc_medians, full_medians = [], []
    for rnd in range(rounds):
        n_new = max(1, int(n_rows * growth))
        append_store(store, ctx.from_columns(
            batch(n_new, 100 + rnd)).node.data)
        snap = sp + ".snap"
        shutil.copyfile(sp, snap)      # pre-append committed state
        wi, wf = [], []
        res = None
        full_table = None
        for _ in range(reps):
            # interleaved: each rep restores the pre-append state so
            # every incremental run merges the SAME 5% delta
            shutil.copyfile(snap, sp)
            t0 = time.time()
            res = run_refresh(ctx, cat, bound, norm, state_dir)
            wi.append(time.time() - t0)
            t0 = time.time()
            full_table = full_run()
            wf.append(time.time() - t0)
        os.unlink(snap)
        same = rows_of(res.table) == rows_of(full_table)
        identical = identical and same
        mi, mf = statistics.median(wi), statistics.median(wf)
        inc_medians.append(mi)
        full_medians.append(mf)
        per_round.append({
            "round": rnd + 1, "appended_rows": n_new,
            "mode": res.mode, "delta_parts": len(res.delta_parts),
            "delta_rows": res.delta_rows,
            "wall_s_incremental": round(mi, 4),
            "wall_s_full": round(mf, 4),
            "rows_identical": same})
    inc_s = statistics.median(inc_medians)
    full_s = statistics.median(full_medians)
    out = {
        "metric": "inc smoke (standing group-sum: incremental refresh "
                  "vs full rescan, store growing 5%/round)",
        "rows": n_rows, "rounds": rounds, "reps": reps,
        "growth_pct": 5.0, "query": norm,
        "wall_s_incremental": round(inc_s, 4),
        "wall_s_full": round(full_s, 4),
        "speedup_x": (round(full_s / inc_s, 2) if inc_s > 0 else None),
        "rows_identical": identical,
        "per_round": per_round,
    }
    shutil.rmtree(tmp, ignore_errors=True)
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-inc",
            "wall_s": round(inc_s, 4),
            "full_wall_s": round(full_s, 4),
            "speedup_x": out["speedup_x"], "rows": n_rows,
            "rounds": rounds, "reps": reps}) + "\n")
    if not quiet:
        print(json.dumps(out))
    return out


def smoke_reuse(out_path="BENCH_reuse.json", n_rows=None, reps=None,
                quiet=False):
    """Semantic cross-job reuse smoke (``python bench.py --smoke`` /
    ``--smoke-reuse``): tenant A submits a SQL aggregate cold
    (parse -> bind -> lower -> plan -> compile), then tenant B submits
    a SYNTACTICALLY DIFFERENT but semantically equal query — different
    alias, reordered predicates and SELECT list, flipped comparison.
    The daemon's plan cache keys on the canonical semantic fingerprint
    (analysis/canon.py), so B must hit (DTA501 reuse_verdict), spend
    ~zero compile, and return bit-identical rows; the headline is B's
    submit->result wall vs the cold one.  Each rep builds a FRESH
    daemon (own FileCache dir), so every rep pays its own cold start.
    Written to ``BENCH_reuse.json`` + appended to ``BENCH_trend.jsonl``
    (app ``bench-reuse``)."""
    import statistics
    import tempfile

    from dryad_tpu import sql
    from dryad_tpu.parallel.mesh import make_mesh
    from dryad_tpu.service.daemon import JobService
    from dryad_tpu.service.tenancy import ServiceConfig
    from dryad_tpu.utils.config import JobConfig

    n_rows = n_rows or int(os.environ.get("BENCH_REUSE_ROWS", "20000"))
    reps = max(3, reps or int(os.environ.get("BENCH_REUSE_REPS", "3")))
    rng = np.random.RandomState(0)
    cat = sql.Catalog()
    cat.register_columns("lineitem", {
        "okey": rng.randint(0, 50, n_rows).astype(np.int32),
        "price": rng.randint(1, 100, n_rows).astype(np.int32),
        "qty": rng.randint(1, 10, n_rows).astype(np.int32)})
    q_cold = ("SELECT l.okey AS okey, SUM(l.price * l.qty) AS revenue "
              "FROM lineitem AS l WHERE l.qty > 2 AND l.price < 90 "
              "GROUP BY l.okey ORDER BY revenue DESC LIMIT 8")
    q_warm = ("SELECT x.okey AS okey, SUM(x.qty * x.price) AS revenue "
              "FROM lineitem AS x WHERE 90 > x.price AND 2 < x.qty "
              "GROUP BY x.okey ORDER BY revenue DESC LIMIT 8")
    mesh = make_mesh()
    cold_walls, warm_walls, warm_compiles = [], [], []
    identical = True
    hits = 0
    for _ in range(reps):
        with tempfile.TemporaryDirectory(prefix="bench-reuse-") as d:
            # pin the exchange strategy so the warm job's stage
            # programs key identically to the cold job's (the probe
            # would otherwise re-decide — and recompile — per run)
            svc = JobService(
                ServiceConfig(service_dir=d, slots=2,
                              job_config=JobConfig(
                                  exchange_probe_min_mb=-1.0)),
                mesh=mesh, catalog=cat)
            try:
                t0 = time.time()
                j1 = svc.submit_sql(q_cold, tenant="alice")
                r1 = svc.wait(j1, timeout=600)
                cold_walls.append(time.time() - t0)
                t0 = time.time()
                j2 = svc.submit_sql(q_warm, tenant="bob")
                r2 = svc.wait(j2, timeout=600)
                warm_walls.append(time.time() - t0)
                assert r1["state"] == "done", r1
                assert r2["state"] == "done", r2
                identical &= (r1["result"] == r2["result"])
                hits += sum(1 for e in svc.log.events
                            if e.get("event") == "reuse_verdict"
                            and e.get("code") == "DTA501")
                warm_compiles.append(sum(
                    e.get("compile_s", 0)
                    for e in svc.jobs[j2].log.events
                    if e.get("event") == "stage_done"))
            finally:
                svc.close()
    cold_s = statistics.median(cold_walls)
    warm_s = statistics.median(warm_walls)
    out = {
        "metric": "semantic reuse smoke (2nd tenant's reordered query "
                  "submit->result vs cold, fingerprint-keyed cache)",
        "rows": n_rows,
        "reps": reps,
        "wall_s_cold": round(cold_s, 4),
        "wall_s_warm": round(warm_s, 4),
        "wall_s_cold_all": [round(w, 4) for w in cold_walls],
        "wall_s_warm_all": [round(w, 4) for w in warm_walls],
        "speedup_pct": (round(100.0 * (cold_s - warm_s) / cold_s, 1)
                        if cold_s > 0 else None),
        "warm_compile_s": round(statistics.median(warm_compiles), 4),
        "semantic_hits": hits,
        "rows_identical": identical,
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-reuse",
            "wall_s": round(warm_s, 4),
            "cold_wall_s": round(cold_s, 4),
            "speedup_pct": out["speedup_pct"],
            "warm_compile_s": out["warm_compile_s"],
            "semantic_hits": hits, "rows": n_rows,
            "reps": reps}) + "\n")
    if not quiet:
        print(json.dumps(out))
    return out


def smoke_analyze(out_path="BENCH_analyze.json", n_lines=None,
                  reps=None, quiet=False):
    """EXPLAIN ANALYZE smoke (``python bench.py --smoke-analyze``, also
    rides ``--smoke``): the traced wordcount run through plain
    ``collect()`` and through ``Dataset.analyze()`` (execute + annotate
    the executed stages against the static cost model), INTERLEAVED
    >= 3 reps each, median walls — the delta is the ANNOTATION
    overhead (event capture + cost pass + report build).

    Correctness gate, not just timing: the analyze report's totals must
    EXACTLY equal the event-derived metrics of the same capture (both
    accumulate in event order — bit-identical float sums), every
    settled stage must carry actuals, the static predictions must
    contain them, and the runtime cross-check must stay silent (zero
    ``cost_model_miss``).  Written to ``BENCH_analyze.json`` +
    appended to ``BENCH_trend.jsonl`` (app ``bench-analyze``)."""
    import statistics

    import jax

    from dryad_tpu import Context
    from dryad_tpu.apps import wordcount
    from dryad_tpu.obs.metrics import metrics_from_events
    from dryad_tpu.parallel.mesh import make_mesh

    n_lines = n_lines or int(os.environ.get("BENCH_ANALYZE_LINES",
                                            "8000"))
    reps = max(3, reps or int(os.environ.get("BENCH_ANALYZE_REPS", "3")))
    rng = np.random.RandomState(0)
    vocab = np.array(["alpha", "beta", "gamma", "delta", "epsilon",
                      "zeta", "eta", "theta"])
    words_per_line = 6
    idx = rng.randint(0, len(vocab), (n_lines, words_per_line))
    lines = [" ".join(vocab[i]) for i in idx]
    mesh = make_mesh(jax.devices())
    per_part = -(-n_lines // mesh.devices.size)
    cap = per_part * (words_per_line + 2)
    ctx = Context(mesh=mesh)
    q = wordcount.wordcount_query(
        ctx.from_columns({"line": lines}, str_max_len=64),
        tokens_per_partition=cap)

    q.collect()                       # warmup: compiles (shared cache)
    rep0 = q.analyze()                # warmup + the verified capture

    # -- correctness: the ANALYZE actuals ARE the event-derived metrics
    derived = metrics_from_events(rep0._events).snapshot()
    checks = {
        "stage_runs": (rep0.stage_runs,
                       derived.get("dryad_stage_runs_total", 0)),
        "run_s": (rep0.run_s,
                  derived.get("dryad_run_seconds_total", 0.0)),
        "compile_s": (rep0.compile_s,
                      derived.get("dryad_compile_seconds_total", 0.0)),
        "out_bytes": (rep0.out_bytes_total,
                      derived.get("dryad_shuffle_bytes_total", 0)),
    }
    for what, (ours, theirs) in checks.items():
        # snapshot() rounds to 6 places; match it for the comparison
        assert round(float(ours), 6) == round(float(theirs), 6), \
            f"analyze {what} {ours} != event-derived {theirs}"
    settled = rep0.settled
    assert settled and all(s.runs >= 1 for s in settled)
    compared = [s for s in settled if s.bytes_in_bounds is not None]
    assert compared, "no stage carried a prediction comparison"
    assert all(s.bytes_in_bounds and s.rows_in_bounds
               for s in compared), "prediction excluded a measured value"
    assert rep0.misses == 0, f"{rep0.misses} cost_model_miss event(s)"

    walls_plain, walls_analyze = [], []
    for _ in range(reps):
        t0 = time.time()
        q.collect()
        walls_plain.append(time.time() - t0)
        t0 = time.time()
        q.analyze()
        walls_analyze.append(time.time() - t0)
    plain_s = statistics.median(walls_plain)
    analyze_s = statistics.median(walls_analyze)
    overhead = (round(100.0 * (analyze_s - plain_s) / plain_s, 1)
                if plain_s > 0 else None)
    out = {
        "metric": "analyze smoke (EXPLAIN ANALYZE vs plain collect)",
        "lines": n_lines,
        "reps": reps,
        "wall_s_plain": round(plain_s, 4),
        "wall_s_analyze": round(analyze_s, 4),
        "wall_s_plain_all": [round(w, 4) for w in walls_plain],
        "wall_s_analyze_all": [round(w, 4) for w in walls_analyze],
        "annotation_overhead_pct": overhead,
        "stages": len(rep0.stages),
        "stages_settled": len(settled),
        "stages_prediction_compared": len(compared),
        "predictions_contained": True,
        "actuals_match_metrics": True,
        "cost_model_misses": rep0.misses,
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-analyze",
            "wall_s": round(analyze_s, 4),
            "plain_wall_s": round(plain_s, 4),
            "overhead_pct": overhead, "lines": n_lines,
            "reps": reps}) + "\n")
    if not quiet:
        print(json.dumps(out))
    return out


def smoke_ooc(out_path="BENCH_ooc.json", n_edges=None, reps=None,
              quiet=False):
    """Out-of-core re-streaming smoke (``python bench.py --smoke-ooc``,
    also rides ``--smoke``): ONE streamed PageRank superstep over an
    hdfs:// store served by the in-process fake WebHDFS with a
    simulated per-request RTT + response-bandwidth cap — a loopback
    that behaves like a REMOTE namenode/datanode — measured
    INTERLEAVED >= 3 reps each in two configs:

    * **cold** — the pre-PR out-of-core posture and the committed A/B
      lever (``ooc_restream_cache=False``, ``ooc_prefetch_depth=0``,
      no ``cache()``): every superstep re-streams the edges from
      remote and recomputes the loop-invariant per-edge weight table
      (edges ⋈ out-degree) before the rank join.
    * **warm** — the ISSUE-14 tier (``cache()`` on the invariant
      weight table — the DryadLINQ materialized-intermediate pattern
      ``pagerank_stream`` hoists — with default prefetch): the warmup
      pass pays one cold write, every timed pass re-streams the local
      fingerprinted chunk cache with the prefetcher overlapping host
      IO and device compute.

    Correctness gate, not just timing: both configs must produce
    IDENTICAL rows (bit-equal node ids and float32 ranks after a host
    sort by node — same chunk boundaries, same reduction order), the
    warm run must show exactly one ``ooc_cache_write`` and >= one
    ``ooc_cache_hit`` per timed pass, and the speedup is asserted
    positive here / >= 30% by the committed-number regression guard.
    Written to ``BENCH_ooc.json`` + appended to ``BENCH_trend.jsonl``
    (app ``bench-ooc``)."""
    import statistics

    from dryad_tpu import Context
    from dryad_tpu.apps import pagerank
    from dryad_tpu.utils.config import JobConfig
    from dryad_tpu.utils.events import EventLog

    # the fake namenode/datanode lives with the tests on purpose — it is
    # a protocol double, not product code
    tests_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tests")
    if tests_dir not in sys.path:
        sys.path.insert(0, tests_dir)
    from webhdfs_fake import FakeWebHdfs

    n_nodes = int(os.environ.get("BENCH_OOC_NODES", "2000"))
    n_edges = n_edges or int(os.environ.get("BENCH_OOC_EDGES", "300000"))
    reps = max(3, reps or int(os.environ.get("BENCH_OOC_REPS", "3")))
    latency_s = float(os.environ.get("BENCH_OOC_LATENCY_S", "0.002"))
    # a busy shared / cross-region link, not RAM-to-loopback
    bandwidth_bps = float(os.environ.get("BENCH_OOC_BANDWIDTH_BPS",
                                         str(8 << 20)))
    chunk_rows = 1 << 13

    edges = pagerank.gen_graph(n_nodes, n_edges, seed=0)
    srv = FakeWebHdfs()
    url = srv.url + "/graphs/edges"
    Context().from_columns(edges).to_store(url)
    # upload free; every READ pays RTT + transfer at the capped rate
    srv.latency_s = latency_s
    srv.throttle_bps = bandwidth_bps

    damping = 0.85

    def inv_weight(c):
        return {"src": c["src"], "dst": c["dst"], "w": 1.0 / c["deg"]}

    def contrib(c):
        return {"node": c["dst"], "c": c["rank"] * c["w"]}

    def damp(c):
        return {"node": c["node"],
                "rank": (1.0 - damping) / n_nodes + damping * c["s"]}

    def build_step(ctx, cached):
        """One pagerank_stream body evaluation as a collectable query:
        the loop-invariant per-edge weight table (edges ⋈ out-degree,
        the part ``cache()`` hoists out of iteration 2..N) feeding the
        per-superstep rank join + contribution group-sum."""
        e = ctx.read_store_stream(url, chunk_rows=chunk_rows)
        links = (e.join(e.group_by(["src"], {"deg": ("count", None)}),
                        ["src"], ["src"], expansion=2.0)
                 .select(inv_weight))
        if cached:
            links = links.cache()
        ranks = ctx.from_columns(
            {"node": np.arange(n_nodes, dtype=np.int32),
             "rank": np.full(n_nodes, 1.0 / n_nodes, np.float32)})
        # exactly one rank row matches each link row: capacity 1.0
        return (links.join(ranks, ["src"], ["node"], expansion=1.0)
                .select(contrib)
                .group_by(["node"], {"s": ("sum", "c")})
                .select(damp))

    import shutil
    cache_dir = tempfile.mkdtemp(prefix="bench-ooc-cache-")
    try:
        cold_ctx = Context(config=JobConfig(
            ooc_chunk_rows=chunk_rows, ooc_restream_cache=False,
            ooc_prefetch_depth=0))
        warm_log = EventLog(level=2)
        warm_ctx = Context(config=JobConfig(
            ooc_chunk_rows=chunk_rows, ooc_cache_dir=cache_dir),
            event_log=warm_log)
        cold_q = build_step(cold_ctx, cached=False)
        warm_q = build_step(warm_ctx, cached=True)

        out_cold = cold_q.collect()         # warmup: compile
        out_warm = warm_q.collect()         # warmup: compile + cold write

        def by_node(t):
            o = np.argsort(np.asarray(t["node"]), kind="stable")
            return (np.asarray(t["node"])[o], np.asarray(t["rank"])[o])

        nc, rc = by_node(out_cold)
        nw, rw = by_node(out_warm)
        rows_identical = (np.array_equal(nc, nw)
                          and np.array_equal(rc, rw))
        assert rows_identical, "warm rows diverged from cold rows"

        walls_cold, walls_warm = [], []
        for _ in range(reps):
            t0 = time.time()
            cold_q.collect()
            walls_cold.append(time.time() - t0)
            t0 = time.time()
            warm_q.collect()
            walls_warm.append(time.time() - t0)
        cold_s = statistics.median(walls_cold)
        warm_s = statistics.median(walls_warm)

        writes = sum(1 for e in warm_log.events
                     if e["event"] == "ooc_cache_write")
        hits = sum(1 for e in warm_log.events
                   if e["event"] == "ooc_cache_hit")
        stall_evs = [e for e in warm_log.events
                     if e["event"] == "prefetch_stall"]
        assert writes == 1, f"expected ONE cold write (links): {writes}"
        assert hits >= reps, f"warm passes must hit the cache: {hits}"
    finally:
        srv.close()
        shutil.rmtree(cache_dir, ignore_errors=True)

    speedup = (round(100.0 * (cold_s - warm_s) / cold_s, 1)
               if cold_s > 0 else None)
    assert speedup is not None and speedup > 0, \
        f"warm must beat cold remote re-streaming: {speedup}"
    out = {
        "metric": "ooc smoke (streamed PageRank step: warm re-streaming "
                  "cache + prefetch vs cold remote)",
        "nodes": n_nodes,
        "edges": n_edges,
        "reps": reps,
        "remote_latency_s": latency_s,
        "remote_bandwidth_mbps": round(bandwidth_bps / (1 << 20), 1),
        "wall_s_cold": round(cold_s, 4),
        "wall_s_warm": round(warm_s, 4),
        "wall_s_cold_all": [round(w, 4) for w in walls_cold],
        "wall_s_warm_all": [round(w, 4) for w in walls_warm],
        "warm_speedup_pct": speedup,
        "rows_identical": rows_identical,
        "warm_cache_writes": writes,
        "warm_cache_hits": hits,
        "prefetch_stalls": sum(int(e.get("stalls", 1))
                               for e in stall_evs),
        # the committed A/B levers the regression guard keeps
        "cold_config": {"ooc_restream_cache": False,
                        "ooc_prefetch_depth": 0, "cache_calls": False},
        "warm_config": {"ooc_restream_cache": True,
                        "ooc_prefetch_depth":
                            JobConfig().ooc_prefetch_depth,
                        "cache_calls": True},
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-ooc",
            "wall_s": round(warm_s, 4),
            "cold_wall_s": round(cold_s, 4),
            "speedup_pct": speedup, "edges": n_edges,
            "reps": reps}) + "\n")
    if not quiet:
        print(json.dumps(out))
    return out


def smoke_kernels(out_path="BENCH_kernels.json", n=None, quiet=False):
    """Data-plane kernel micro-bench smoke (``python bench.py
    --smoke-kernels``, also rides ``--smoke``): DEVICE-TRUTH rows for the
    round-6 data-plane kernels, each an A/B of the shipped lowering vs
    the pre-kernel one it replaced (kept live behind
    ``DRYAD_NO_SORT_OPT`` / reconstructed verbatim here), slope-measured
    (benchmarks.micro.slope_time: in-program repetition, fetch-fenced,
    dispatch floor cancels) with the two sides' slope calls INTERLEAVED
    (A, B, A, B; best-of per side) so both read the same box weather.

    Rows:
      * multikey_sort   — sort_by_columns, 2 i32 keys: runtime key-lane
                          fusion (_sort_fused2) vs the general 3-lane
                          carry sort; roofline_pct against this
                          backend's measured copy rate.
      * exchange_pack   — send-side slot build: tile-histogram +
                          unstable (dest, idx) carry sort + slot
                          expansion vs stable argsort + bincount +
                          composed random gather.
      * exchange_unpack — receive-side: slot compaction vs stable
                          valid-first sort + gather.
      * join_gather     — the join's output materialization: ONE packed
                          word-matrix gather (_packed_gather) vs one
                          random gather per column; plus the full
                          hash_join's absolute device-truth rows/s.
      * wire_utilization_inmem — NOT a timing: the measured-slot wire
                          arithmetic of a real multi-exchange in-memory
                          stage (both join legs carry ops, so only the
                          round-6 slot FEEDBACK can size them): slots
                          needed / slots shipped on the discovery wave
                          (structural slack) vs the steady state
                          (measured exact slots).

    Backend honesty: the slot kernels compile on TPU only — on other
    backends slot_expand/slot_compact take their XLA fallback (exercised
    bit-exactly by tests/test_pallas_kernels.py force_interpret rows),
    so a CPU capture's pack/unpack delta reflects the sort-path changes
    only; the ``backend`` field says which chip the row describes."""

    import jax
    import jax.numpy as jnp

    from benchmarks.micro import bench_hbm_copy, slope_time
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.ops import kernels as K
    from dryad_tpu.ops.pallas_kernels import (pallas_active, slot_compact,
                                              slot_expand)

    n = n or int(os.environ.get("BENCH_KERNEL_ROWS", str(1 << 17)))
    k_lo = int(os.environ.get("BENCH_KERNEL_KLO", "2"))
    k_hi = int(os.environ.get("BENCH_KERNEL_KHI", "10"))
    rng = np.random.RandomState(6)
    backend = jax.default_backend()

    def ab(body_new, body_old, make_carry, rounds=2, khi=None):
        """Interleaved slope pairs: A,B,A,B — best-of per side.
        ``khi`` widens the repetition spread for cheap bodies whose
        per-pass device time would drown in call-wall jitter."""
        ts_new, ts_old = [], []
        for _ in range(rounds):
            ts_new.append(slope_time(body_new, make_carry,
                                     k_lo=k_lo, k_hi=khi or k_hi,
                                     iters=2))
            ts_old.append(slope_time(body_old, make_carry,
                                     k_lo=k_lo, k_hi=khi or k_hi,
                                     iters=2))
        return min(ts_new), min(ts_old)

    def fold(tree):
        """Reduce EVERY output element into one i32 — the timed body's
        carry must consume the whole result or XLA dead-code-eliminates
        the work down to the slice the carry actually reads (measured:
        an unconsumed unpack body 'ran' in 0.0 s)."""
        tot = jnp.zeros((), jnp.int32)
        for leaf in jax.tree.leaves(tree):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                leaf = jax.lax.bitcast_convert_type(
                    leaf.astype(jnp.float32), jnp.int32)
            tot = tot + leaf.astype(jnp.int32).sum()
        return tot

    rows = {}

    # -- multikey sort: runtime key-lane fusion vs general 3-lane ------
    k1 = jnp.asarray(rng.randint(0, 1000, n).astype(np.int32))
    k2 = jnp.asarray(rng.randint(0, 1000, n).astype(np.int32))
    pv = jnp.asarray(rng.rand(n).astype(np.float32))
    pw = jnp.asarray(rng.randint(0, 1 << 30, n).astype(np.int32))
    cnt = jnp.asarray(n, jnp.int32)
    keys = [("k1", False), ("k2", False)]

    # the fused path ships on the TPU tier (sort_by_columns gates it by
    # pallas_active); the A/B here measures the two DESIGNS directly on
    # identical lanes, whatever tier this backend rides in production
    inv0 = jnp.zeros((n,), jnp.uint32)
    la0 = k1.astype(jnp.uint32)
    lb0 = k2.astype(jnp.uint32)
    packed0 = [jax.lax.bitcast_convert_type(pv, jnp.uint32),
               pw.astype(jnp.uint32)]

    def sort_fused_body(i, carry):
        a, b = carry
        lanes = [inv0, la0 ^ (a[0] & 1), lb0]
        sk, sv = K._sort_fused2(lanes, [x ^ a[0] for x in packed0], n)
        return (a ^ fold(sk).astype(jnp.uint32)
                ^ fold(sv).astype(jnp.uint32), b)

    def sort_general_body(i, carry):
        a, b = carry
        lanes = [inv0, la0 ^ (a[0] & 1), lb0]
        sk, sv = K._sort_carrying(lanes, [x ^ a[0] for x in packed0], n)
        return (a ^ fold(list(sk)).astype(jnp.uint32)
                ^ fold(list(sv)).astype(jnp.uint32), b)

    def mk_carry(j):
        seed = jnp.asarray(
            rng.randint(0, 1 << 30, n).astype(np.uint32))
        return (seed, jnp.zeros((), jnp.uint32))

    t_new, t_old = ab(sort_fused_body, sort_general_body, mk_carry)
    hbm = bench_hbm_copy(mb=int(os.environ.get("BENCH_KERNEL_COPY_MB",
                                               "64")))
    copy_gbps = hbm["hbm_copy_gbps"]
    row_bytes = 16       # k1+k2+v+w, 4 B each
    t_copy = 2 * n * row_bytes / (copy_gbps * 1e9)
    rows["multikey_sort"] = {
        "rows": n, "new_s": round(t_new, 6), "old_s": round(t_old, 6),
        "speedup_pct": round(100 * (t_old - t_new) / t_old, 1),
        "rows_per_s": round(n / t_new),
        "roofline_pct": round(100 * t_copy / t_new, 2),
        "copy_gbps_basis": round(copy_gbps, 2),
        "prod_lowering": ("fused" if pallas_active() == "compiled"
                          else "general"),
    }

    # -- exchange pack/unpack: slot build + compaction A/B -------------
    D, W = 8, 4
    C = -(-2 * n // D)           # the structural slack-2 slot width
    dest0 = jnp.asarray(rng.randint(0, D, n).astype(np.int32))
    lanes0 = [jnp.asarray(rng.randint(0, 1 << 30, n)
                          .astype(np.uint32)) for _ in range(W)]

    from dryad_tpu.ops.pallas_kernels import hist_buckets

    def pack_new(i, carry):
        d, acc = carry
        counts = hist_buckets(d, D)
        offsets = jnp.cumsum(counts) - counts
        iota = jnp.arange(n, dtype=jnp.uint32)
        _, sl = K._sort_carrying([d.astype(jnp.uint32), iota],
                                 [x ^ acc[0] for x in lanes0], n,
                                 stable=False)
        words = jnp.stack(sl, axis=1)
        send = slot_expand(words, offsets.astype(jnp.int32), C)
        return (d ^ (fold(send) & 1), acc)

    def pack_old(i, carry):
        d, acc = carry
        order = jnp.argsort(d, stable=True)
        counts = jnp.bincount(jnp.minimum(jnp.take(d, order), D),
                              length=D + 1)[:D]
        offsets = jnp.cumsum(counts) - counts
        d_idx = jnp.repeat(jnp.arange(D, dtype=jnp.int32), C)
        j_idx = jnp.tile(jnp.arange(C, dtype=jnp.int32), D)
        src = jnp.clip(jnp.take(offsets, d_idx) + j_idx, 0, n - 1)
        comp = jnp.take(order, src)
        send = jnp.stack([jnp.take(x ^ acc[0], comp)
                          for x in lanes0], axis=1)
        return (d ^ (fold(send) & 1), acc)

    def mk_pack_carry(j):
        return (dest0, (jnp.asarray(
            rng.randint(0, 1 << 30, n).astype(np.uint32)),))

    t_new, t_old = ab(pack_new, pack_old, mk_pack_carry)
    rows["exchange_pack"] = {
        "rows": n, "dests": D, "slot_rows": C,
        "new_s": round(t_new, 6), "old_s": round(t_old, 6),
        "speedup_pct": round(100 * (t_old - t_new) / t_old, 1),
        "rows_per_s": round(n / t_new),
        "slot_kernels_engaged": pallas_active() == "compiled",
        # the pack lowering ships ONLY where the slot kernels engage
        # (TPU); elsewhere _exchange_one_axis keeps the gather form —
        # a negative delta here on cpu is the PROVENANCE for that gate
        "prod_lowering": ("pack" if pallas_active() == "compiled"
                          else "gather"),
    }

    recv0 = jnp.asarray(rng.randint(0, 1 << 30, (D * C, W))
                        .astype(np.uint32))
    counts0 = jnp.asarray(
        rng.randint(0, max(n // D, 1), D).astype(np.int32))

    def unpack_new(i, carry):
        r, acc = carry
        out = slot_compact(r ^ acc[0], counts0, C, n)
        return (r ^ (fold(out) & 1).astype(jnp.uint32), acc)

    def unpack_old(i, carry):
        r, acc = carry
        rr = r ^ acc[0]
        idx = jnp.arange(D * C, dtype=jnp.int32)
        rvalid = (idx % C) < jnp.take(counts0, idx // C)
        perm = jnp.argsort(~rvalid, stable=True)
        g = jnp.take(rr, perm[:n], axis=0)
        total = rvalid.sum(dtype=jnp.int32)
        gmask = jnp.arange(n, dtype=jnp.int32) < total
        out = jnp.where(gmask[:, None], g, 0)
        return (r ^ (fold(out) & 1).astype(jnp.uint32), acc)

    def mk_unpack_carry(j):
        return (recv0, (jnp.asarray(
            rng.randint(0, 1 << 30, (1,)).astype(np.uint32)),))

    t_new, t_old = ab(unpack_new, unpack_old, mk_unpack_carry,
                      khi=max(k_hi, 64))
    rows["exchange_unpack"] = {
        "rows": n, "dests": D,
        "new_s": round(t_new, 6), "old_s": round(t_old, 6),
        "speedup_pct": round(100 * (t_old - t_new) / t_old, 1),
        "rows_per_s": round(n / t_new),
        "slot_kernels_engaged": pallas_active() == "compiled",
        "prod_lowering": ("pack" if pallas_active() == "compiled"
                          else "gather"),
    }

    # -- join gather: packed single-gather vs per-column gathers -------
    nl, nright = n, max(n // 8, 1024)
    jcols = {"a": jnp.asarray(rng.rand(nl).astype(np.float32)),
             "b": jnp.asarray(rng.randint(0, 1 << 30, nl)
                              .astype(np.int32)),
             "c": jnp.asarray(rng.randint(0, 1 << 30, nl)
                              .astype(np.int64)),
             "d": jnp.asarray(rng.rand(nl).astype(np.float32))}
    idx0 = jnp.asarray(rng.randint(0, nl, nl).astype(np.int32))

    def jg_new(i, carry):
        ix, acc = carry
        # the packed design, measured raw (its prod entry point
        # _packed_gather gates to per-column off-TPU)
        lanes, spec = K._pack_columns_u32(jcols)
        w = jnp.stack(lanes, axis=1)
        g = jnp.take(w, ix, axis=0)
        out = K._unpack_columns_u32(
            [g[:, j] for j in range(len(lanes))], spec)
        return (ix ^ (fold(out) & 1), acc)

    def jg_old(i, carry):
        ix, acc = carry
        out = {k: jnp.take(v, ix, axis=0) for k, v in jcols.items()}
        return (ix ^ (fold(out) & 1), acc)

    def mk_jg_carry(j):
        return (idx0, ())

    t_new, t_old = ab(jg_new, jg_old, mk_jg_carry, khi=max(k_hi, 32))
    lk = jnp.asarray(rng.randint(0, nright, nl).astype(np.int32))
    rk = jnp.arange(nright, dtype=jnp.int32)
    rv = jnp.asarray(rng.rand(nright).astype(np.float32))
    lb = Batch({"k": lk, "a": jcols["a"], "b": jcols["b"]},
               jnp.asarray(nl, jnp.int32))
    right_b = Batch({"k": rk, "rv": rv}, jnp.asarray(nright, jnp.int32))

    def join_body(i, carry):
        kk, acc = carry
        out, _need = K.hash_join(
            Batch({"k": kk, "a": jcols["a"], "b": jcols["b"]},
                  jnp.asarray(nl, jnp.int32)),
            right_b, ["k"], ["k"], nl)
        return (kk ^ (fold(dict(out.columns)) & 1), acc)

    t_join = slope_time(join_body, lambda j: (lk, ()),
                        k_lo=k_lo, k_hi=k_hi, iters=2)
    rows["join_gather"] = {
        "rows": nl, "right_rows": nright,
        "new_s": round(t_new, 6), "old_s": round(t_old, 6),
        "speedup_pct": round(100 * (t_old - t_new) / t_old, 1),
        "join_rows_per_s_chip": round(nl / t_join),
        "join_s": round(t_join, 6),
        "prod_lowering": ("packed" if pallas_active() == "compiled"
                          else "per_column"),
    }

    # -- wire utilization: measured slots on a multi-exchange stage ----
    from dryad_tpu import Context
    from dryad_tpu.exec.executor import _quantize_slot_rows
    from dryad_tpu.utils.config import JobConfig

    un = 20_000
    uk1 = rng.randint(0, 500, un).astype(np.int32)
    uv1 = rng.randint(0, 1 << 20, un).astype(np.int32)
    uk2 = np.arange(500, dtype=np.int32)
    uv2 = rng.randint(0, 1 << 20, 500).astype(np.int32)
    from dryad_tpu.exec.executor import Executor

    ctx = Context(config=JobConfig(exchange_probe_min_mb=1e9))
    leg_caps = {}                     # (fingerprint, leg) -> input cap
    orig_hints = Executor._slot_hints

    def spy(self, stage, inputs, slack, salted):
        fp = stage.fingerprint()
        for li, inp in enumerate(inputs):
            if stage.legs[li].exchange is not None:
                leg_caps[(fp, li)] = inp.capacity   # per-partition rows
        return orig_hints(self, stage, inputs, slack, salted)

    Executor._slot_hints = spy
    try:
        qleft = (ctx.from_columns({"k": uk1, "v": uv1})
                 .where(lambda c: c["v"] >= 0))
        qright = (ctx.from_columns({"k": uk2, "w": uv2})
                  .where(lambda c: c["w"] >= 0))
        qj = qleft.join(qright, ["k"])
        qj.collect()                   # wave 1: structural slack
        qj.collect()                   # wave 2: measured exact slots
    finally:
        Executor._slot_hints = orig_hints
    ex = ctx.executor
    slack = ctx.config.initial_send_slack
    Dm = ex.nparts
    needed = shipped_struct = shipped_meas = 0
    for key, slot in ex._slot_feedback.items():
        cap = leg_caps.get(key)
        if cap is None:
            continue
        needed += slot
        # the structural discovery slot (_exchange_one_axis formula)
        shipped_struct += max(1, min(cap, -(-slack * cap // Dm)))
        shipped_meas += _quantize_slot_rows(slot)
    util_struct = (round(100.0 * needed / shipped_struct, 1)
                   if shipped_struct else None)
    util_meas = (round(100.0 * needed / shipped_meas, 1)
                 if shipped_meas else None)
    rows["wire_utilization_inmem"] = {
        "rows": un, "exchange_legs": len(ex._slot_feedback),
        "wave1_structural_pct": util_struct,
        "wave2_measured_pct": util_meas,
    }

    out = {
        "metric": "kernel smoke (data-plane A/B device-truth rows)",
        "backend": backend,
        "n_devices": jax.device_count(),
        "slope_k": [k_lo, k_hi],
        "rows": rows,
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-kernels",
            "backend": backend,
            "multikey_sort_speedup_pct":
                rows["multikey_sort"]["speedup_pct"],
            "multikey_sort_roofline_pct":
                rows["multikey_sort"]["roofline_pct"],
            "exchange_pack_speedup_pct":
                rows["exchange_pack"]["speedup_pct"],
            "exchange_unpack_speedup_pct":
                rows["exchange_unpack"]["speedup_pct"],
            "join_gather_speedup_pct":
                rows["join_gather"]["speedup_pct"],
            "join_rows_per_s_chip":
                rows["join_gather"]["join_rows_per_s_chip"],
            "wire_util_inmem_wave1_pct":
                rows["wire_utilization_inmem"]["wave1_structural_pct"],
            "wire_util_inmem_wave2_pct":
                rows["wire_utilization_inmem"]["wave2_measured_pct"],
            "kernel_rows": n}) + "\n")
    if not quiet:
        print(json.dumps(out))
    return out


def smoke_service(out_path="BENCH_service.json", n_lines=None,
                  k_jobs=None, reps=None, quiet=False):
    """Multi-tenant job-service smoke (``python bench.py
    --smoke-service``): K wordcount jobs CONCURRENTLY through one
    persistent daemon (shared in-process fleet + shared compiled-stage
    caches, dryad_tpu/service) vs the SAME K jobs run sequentially as
    standalone drivers (fresh Executor each — the reference's
    one-Graph-Manager-per-job model, nothing amortized).  Both sides run
    ``reps`` repetitions INTERLEAVED (standalone, service, standalone,
    ...) and report MEDIAN aggregate walls (the PR-4 protocol: both
    sides get the same box weather; each rep builds a fresh daemon /
    fresh executors so every rep pays its own cold start).

    The second headline is the amortization story the ROADMAP names
    (BENCH_obs: compile is ~0.75s of a ~1.0s job): after the K
    concurrent jobs, a WARM-CACHE second-user submission of the same
    app — its compile segment must be near zero because the daemon's
    shared executor keeps the compiled stages hot.  Written to
    ``BENCH_service.json`` and appended to ``BENCH_trend.jsonl`` (app
    ``bench-smoke-service``)."""
    import statistics
    import tempfile

    from dryad_tpu.api.dataset import Context
    from dryad_tpu.exec.data import maybe_shrink_for_collect, pdata_to_host
    from dryad_tpu.exec.executor import Executor
    from dryad_tpu.parallel.mesh import make_mesh
    from dryad_tpu.plan.planner import plan_query
    from dryad_tpu.service.apps import APPS
    from dryad_tpu.service.daemon import JobService
    from dryad_tpu.service.tenancy import ServiceConfig

    n_lines = n_lines or int(os.environ.get("BENCH_SERVICE_LINES", "4000"))
    k_jobs = k_jobs or int(os.environ.get("BENCH_SERVICE_JOBS", "3"))
    reps = max(1, reps or int(os.environ.get("BENCH_SERVICE_REPS", "3")))
    app = APPS["wordcount"]
    job_params = [{"n_lines": n_lines, "seed": i} for i in range(k_jobs)]
    mesh = make_mesh()

    def standalone(params, ex):
        """One job the one-GM-per-job way: its own executor (cold
        compile), its own driver run."""
        tasks = app.make_tasks(dict(params), mesh.devices.size)
        cols = {k: [x for t in tasks for x in t[k]] for k in tasks[0]}
        ctx = Context(mesh=mesh)
        q = app.build_query(ctx, cols, params)
        graph = plan_query(q.node, ctx.nparts, hosts=ctx.hosts,
                           levels=ctx.levels)
        pd = ex.run(graph)
        return app.combine([pdata_to_host(maybe_shrink_for_collect(pd))])

    seq_walls, conc_walls = [], []
    warm = cold = None
    seq_results = conc_results = None
    for _ in range(reps):
        # -- sequential standalone baseline (fresh executor per job)
        t0 = time.time()
        seq_results = []
        for params in job_params:
            seq_results.append(standalone(params, Executor(mesh)))
        seq_walls.append(time.time() - t0)
        # -- K jobs concurrently through one fresh daemon
        with tempfile.TemporaryDirectory(prefix="bench-svc-") as d:
            svc = JobService(ServiceConfig(service_dir=d, slots=2),
                             mesh=mesh)
            try:
                t0 = time.time()
                jids = [svc.submit("wordcount", p,
                                   tenant=f"tenant{i % 2}")
                        for i, p in enumerate(job_params)]
                rows = [svc.wait(j, timeout=600) for j in jids]
                conc_walls.append(time.time() - t0)
                assert all(r["state"] == "done" for r in rows), rows
                conc_results = [r["result"] for r in rows]

                def compile_of(jid):
                    return sum(e.get("compile_s", 0)
                               for e in svc.jobs[jid].log.events
                               if e.get("event") == "stage_done")

                cold = compile_of(jids[0])
                # warm-cache second user: same app+params as job 0,
                # new tenant — the Nth-user-pays-zero-compile check
                t0 = time.time()
                jw = svc.submit("wordcount", job_params[0],
                                tenant="warm-user")
                rw = svc.wait(jw, timeout=600)
                warm = {"wall_s": round(time.time() - t0, 4),
                        "compile_s": round(compile_of(jw), 4)}
                assert rw["state"] == "done", rw
            finally:
                svc.close()
    seq_s = statistics.median(seq_walls)
    conc_s = statistics.median(conc_walls)
    results_match = conc_results == seq_results
    out = {
        "metric": "service smoke (K concurrent jobs through one daemon "
                  "vs K sequential standalone runs)",
        "k_jobs": k_jobs,
        "lines_per_job": n_lines,
        "reps": reps,
        "wall_s_sequential": round(seq_s, 4),
        "wall_s_concurrent": round(conc_s, 4),
        "wall_s_sequential_all": [round(w, 4) for w in seq_walls],
        "wall_s_concurrent_all": [round(w, 4) for w in conc_walls],
        "speedup_pct": (round(100.0 * (seq_s - conc_s) / seq_s, 1)
                        if seq_s > 0 else None),
        "cold": {"compile_s": round(cold, 4)},
        "warm": warm,
        "results_match": results_match,
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-smoke-service",
            "wall_s": round(conc_s, 4),
            "sequential_wall_s": round(seq_s, 4),
            "speedup_pct": out["speedup_pct"],
            "warm_user_compile_s": warm["compile_s"],
            "warm_user_wall_s": warm["wall_s"],
            "cold_compile_s": round(cold, 4),
            "k_jobs": k_jobs, "lines": n_lines, "reps": reps}) + "\n")
    if not quiet:
        print(json.dumps(out))
    return out


def smoke_latency(out_path="BENCH_latency.json", n_lines=None,
                  k_tenants=None, jobs_per_tenant=None, reps=None,
                  quiet=False):
    """Tail-latency percentile smoke (``python bench.py
    --smoke-latency``, the ROADMAP item-4 deliverable): K concurrent
    tenants submit wordcount jobs through ONE persistent daemon whose
    fleet was WARMED first (a throwaway submission pays the cold
    compile), and every request's settled phase waterfall
    (obs/latency.py) supplies its submit→result wall.  ``reps``
    repetitions run interleaved and each percentile reports the MEDIAN
    across reps (the PR-4 protocol: one anomalous rep cannot own the
    headline); the committed number is p50/p95/p99 over the per-request
    walls plus the dominant-phase attribution and the p99 exemplar —
    whose trace id must resolve to a real recorded trace
    (``python -m dryad_tpu.obs trace --job ...``)."""
    import statistics
    import tempfile

    from dryad_tpu.parallel.mesh import make_mesh
    from dryad_tpu.service.daemon import JobService
    from dryad_tpu.service.tenancy import ServiceConfig

    n_lines = n_lines or int(os.environ.get("BENCH_LATENCY_LINES",
                                            "2000"))
    k_tenants = k_tenants or int(os.environ.get("BENCH_LATENCY_TENANTS",
                                                "3"))
    jobs_per_tenant = jobs_per_tenant or int(
        os.environ.get("BENCH_LATENCY_JOBS", "2"))
    reps = max(1, reps or int(os.environ.get("BENCH_LATENCY_REPS", "3")))
    mesh = make_mesh()

    def pctl(vals, q):
        """Exact percentile over the measured walls (sorted oracle —
        the sketch's error bound is tested against this in
        tests/test_latency.py)."""
        s = sorted(vals)
        if not s:
            return 0.0
        i = min(len(s) - 1, max(0, int(round(q * (len(s) - 1)))))
        return s[i]

    per_rep = {"p50": [], "p95": [], "p99": []}
    all_walls = []
    snap = None
    exemplar = None
    exemplar_resolves = False
    with tempfile.TemporaryDirectory(prefix="bench-lat-") as d:
        svc = JobService(ServiceConfig(service_dir=d,
                                       slots=max(2, k_tenants)),
                         mesh=mesh)
        try:
            # warm the fleet: the cold XLA compile is the amortized
            # story (BENCH_service.json); this smoke measures the
            # INTERACTIVE tail on a warm service
            jw = svc.submit("wordcount", {"n_lines": n_lines, "seed": 0},
                            tenant="warmup")
            assert svc.wait(jw, timeout=600)["state"] == "done"
            for _ in range(reps):
                jids = [svc.submit("wordcount",
                                   {"n_lines": n_lines, "seed": 0},
                                   tenant=f"tenant{i % k_tenants}")
                        for i in range(k_tenants * jobs_per_tenant)]
                rows = [svc.wait(j, timeout=600) for j in jids]
                assert all(r["state"] == "done" for r in rows), rows
                walls = [svc.jobs[j].waterfall["wall_s"] for j in jids]
                all_walls.extend(walls)
                for q, key in ((0.50, "p50"), (0.95, "p95"),
                               (0.99, "p99")):
                    per_rep[key].append(pctl(walls, q))
            snap = svc.latency_snapshot()
            # the slowest request across tenants: its job id + trace id
            # is the one-click p99 attribution — verify the trace id
            # resolves to a real recorded span in that job's archive
            exes = [r["exemplar"] for t, r in snap.items()
                    if r.get("exemplar") and t != "warmup"]
            if exes:
                exemplar = max(exes, key=lambda e: e["wall_s"])
                ej = svc.jobs.get(exemplar["job"])
                exemplar_resolves = bool(
                    exemplar.get("trace") and ej is not None
                    and any(e.get("trace") == exemplar["trace"]
                            for e in ej.log.events
                            if e.get("event") == "span"))
        finally:
            svc.close()
    dom_us = {}
    for r in snap.values():
        if r["tenant"] == "warmup":
            continue
        for ph in r["phases"]:
            dom_us[ph["phase"]] = (dom_us.get(ph["phase"], 0.0)
                                   + ph["total_s"])
    out = {
        "metric": "tail latency: K concurrent tenants on a warm fleet "
                  "(submit->result walls from per-request phase "
                  "waterfalls)",
        "k_tenants": k_tenants,
        "jobs_per_tenant": jobs_per_tenant,
        "lines_per_job": n_lines,
        "reps": reps,
        "requests": len(all_walls),
        "p50_s": round(statistics.median(per_rep["p50"]), 4),
        "p95_s": round(statistics.median(per_rep["p95"]), 4),
        "p99_s": round(statistics.median(per_rep["p99"]), 4),
        "p50_s_all": [round(w, 4) for w in per_rep["p50"]],
        "p99_s_all": [round(w, 4) for w in per_rep["p99"]],
        "dominant_phase": (max(dom_us, key=dom_us.get)
                           if dom_us else None),
        "phase_totals_s": {k: round(v, 4)
                           for k, v in sorted(dom_us.items())},
        "per_tenant": {t: {"count": r["count"], "p50_s": r["p50_s"],
                           "p95_s": r["p95_s"], "p99_s": r["p99_s"],
                           "dominant": r["dominant"]}
                       for t, r in snap.items() if t != "warmup"},
        "exemplar": exemplar,
        "exemplar_trace_resolves": exemplar_resolves,
    }
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-smoke-latency",
            "wall_s": out["p99_s"], "p50_s": out["p50_s"],
            "p95_s": out["p95_s"], "p99_s": out["p99_s"],
            "dominant_phase": out["dominant_phase"],
            "k_tenants": k_tenants, "lines": n_lines,
            "reps": reps}) + "\n")
    if not quiet:
        print(json.dumps(out))
    return out


def smoke_durable(out_path="BENCH_durable.json", n_lines=None,
                  k_jobs=None, reps=None, quiet=False):
    """Durability smoke (``python bench.py --smoke-durable``, also
    rides ``--smoke``): K wordcount jobs submitted to a durable daemon
    that is CRASHED mid-fleet (the test/bench kill hook — journal cut
    first, exactly what SIGKILL leaves) and restarted; vs the SAME K
    jobs run uninterrupted.  ``reps`` repetitions run INTERLEAVED
    (uninterrupted, crashed, uninterrupted, ...) and both headline
    walls are MEDIANS (the PR-4 protocol).  Reports the journal-replay
    recovery wall, how many jobs came back resumed/readmitted, and the
    end-to-end submit→complete overhead a crash+restart costs — with
    oracle-identical results required (a recovered job's output must
    equal its uninterrupted twin's).  Written to ``BENCH_durable.json``
    + appended to ``BENCH_trend.jsonl`` (app ``bench-smoke-durable``)."""
    import statistics
    import tempfile

    from dryad_tpu.service.daemon import JobService
    from dryad_tpu.service.tenancy import ServiceConfig

    n_lines = n_lines or int(os.environ.get("BENCH_DURABLE_LINES",
                                            "2000"))
    k_jobs = k_jobs or int(os.environ.get("BENCH_DURABLE_JOBS", "3"))
    reps = max(1, reps or int(os.environ.get("BENCH_DURABLE_REPS",
                                             "3")))
    job_params = [{"n_lines": n_lines, "seed": i} for i in range(k_jobs)]

    def run_fleet(svc, jids=None):
        jids = jids or [svc.submit("wordcount", p,
                                   tenant=f"tenant{i % 2}")
                        for i, p in enumerate(job_params)]
        rows = [svc.wait(j, timeout=600) for j in jids]
        assert all(r["state"] == "done" for r in rows), rows
        return jids, [r.get("result") for r in rows]

    plain_walls, crash_walls, recovery_walls = [], [], []
    plain_results = crashed_results = None
    recovered = 0
    rec = None
    for _ in range(reps):
        # -- uninterrupted twin
        with tempfile.TemporaryDirectory(prefix="bench-dur-") as d:
            svc = JobService(ServiceConfig(service_dir=d, slots=2))
            try:
                t0 = time.time()
                _, plain_results = run_fleet(svc)
                plain_walls.append(time.time() - t0)
            finally:
                svc.close()
        # -- crashed + recovered
        with tempfile.TemporaryDirectory(prefix="bench-dur-") as d:
            cfg = lambda: ServiceConfig(service_dir=d,  # noqa: E731
                                        slots=2, durable_spill=True)
            t0 = time.time()
            svc = JobService(cfg())
            jids = [svc.submit("wordcount", p, tenant=f"tenant{i % 2}")
                    for i, p in enumerate(job_params[:-1])]
            svc.wait(jids[0], timeout=600)   # some work settles...
            # ...one more lands just before the lights go out (so the
            # recovered fleet is never empty, however fast the box)...
            jids.append(svc.submit("wordcount", job_params[-1],
                                   tenant=f"tenant{(len(jids)) % 2}"))
            svc.crash()                      # ...then the daemon dies
            svc2 = JobService(cfg())         # successor adopts
            try:
                rec = svc2.recovery
                recovery_walls.append(rec["wall_s"])
                recovered += rec["resumed"] + rec["readmitted"]
                _, crashed_results = run_fleet(svc2, jids)
                crash_walls.append(time.time() - t0)
            finally:
                svc2.close()
    # jobs terminal before the crash serve an archived row (no result
    # payload retained) — compare wherever both sides have one
    results_match = all(
        c == p for c, p in zip(crashed_results, plain_results)
        if c is not None)
    plain_s = statistics.median(plain_walls)
    crash_s = statistics.median(crash_walls)
    out = {
        "metric": "durable smoke (K jobs through a crashed+recovered "
                  "daemon vs uninterrupted)",
        "k_jobs": k_jobs,
        "lines_per_job": n_lines,
        "reps": reps,
        "wall_s_uninterrupted": round(plain_s, 4),
        "wall_s_crashed": round(crash_s, 4),
        "wall_s_uninterrupted_all": [round(w, 4) for w in plain_walls],
        "wall_s_crashed_all": [round(w, 4) for w in crash_walls],
        "crash_overhead_pct": (round(100.0 * (crash_s - plain_s)
                                     / plain_s, 1)
                               if plain_s > 0 else None),
        "recovery_wall_s": round(statistics.median(recovery_walls), 4),
        "jobs_recovered": recovered,
        "last_recovery": {k: rec[k] for k in
                          ("records", "resumed", "readmitted",
                           "failed", "terminal_indexed")},
        "results_match": results_match,
    }
    assert results_match, out
    with open(out_path, "w") as f:
        json.dump(out, f, indent=1)
    trend_path = os.environ.get("BENCH_TREND_PATH") or os.path.join(
        os.path.dirname(os.path.abspath(out_path)), "BENCH_trend.jsonl")
    with open(trend_path, "a") as f:
        f.write(json.dumps({
            "ts": round(time.time(), 3), "app": "bench-smoke-durable",
            "wall_s": round(crash_s, 4),
            "uninterrupted_wall_s": round(plain_s, 4),
            "crash_overhead_pct": out["crash_overhead_pct"],
            "recovery_wall_s": out["recovery_wall_s"],
            "jobs_recovered": recovered,
            "k_jobs": k_jobs, "lines": n_lines, "reps": reps}) + "\n")
    if not quiet:
        print(json.dumps(out))
    return out

if __name__ == "__main__":
    if "--smoke-adapt" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke-adapt"]
        smoke_adapt(out_path=args[0] if args else "BENCH_adapt.json")
    elif "--smoke-sql" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke-sql"]
        smoke_sql(out_path=args[0] if args else "BENCH_sql.json")
    elif "--smoke-kernels" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke-kernels"]
        smoke_kernels(out_path=args[0] if args else "BENCH_kernels.json")
    elif "--smoke-service" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke-service"]
        smoke_service(out_path=args[0] if args else "BENCH_service.json")
    elif "--smoke-analyze" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke-analyze"]
        smoke_analyze(out_path=args[0] if args else "BENCH_analyze.json")
    elif "--smoke-ooc" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke-ooc"]
        smoke_ooc(out_path=args[0] if args else "BENCH_ooc.json")
    elif "--smoke-inc" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke-inc"]
        smoke_inc(out_path=args[0] if args else "BENCH_inc.json")
    elif "--smoke-reuse" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke-reuse"]
        smoke_reuse(out_path=args[0] if args else "BENCH_reuse.json")
    elif "--smoke-latency" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke-latency"]
        smoke_latency(out_path=args[0] if args else "BENCH_latency.json")
    elif "--smoke-durable" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke-durable"]
        smoke_durable(out_path=args[0] if args else "BENCH_durable.json")
    elif "--smoke" in sys.argv:
        args = [a for a in sys.argv[1:] if a != "--smoke"]
        obs_out = args[0] if args else "BENCH_obs.json"
        smoke(out_path=obs_out)
        # the adapt + kernel cases ride --smoke: outputs land NEXT TO
        # the requested obs path (an explicit path keeps the cwd clean)
        # and stdout stays ONE JSON document — existing
        # json.loads(stdout) consumers of --smoke keep working
        base = os.path.dirname(os.path.abspath(obs_out))
        smoke_adapt(out_path=os.path.join(base, "BENCH_adapt.json"),
                    quiet=True)
        smoke_kernels(out_path=os.path.join(base, "BENCH_kernels.json"),
                      quiet=True)
        smoke_service(out_path=os.path.join(base, "BENCH_service.json"),
                      quiet=True)
        smoke_sql(out_path=os.path.join(base, "BENCH_sql.json"),
                  quiet=True)
        smoke_analyze(out_path=os.path.join(base, "BENCH_analyze.json"),
                      quiet=True)
        smoke_ooc(out_path=os.path.join(base, "BENCH_ooc.json"),
                  quiet=True)
        smoke_inc(out_path=os.path.join(base, "BENCH_inc.json"),
                  quiet=True)
        smoke_reuse(out_path=os.path.join(base, "BENCH_reuse.json"),
                    quiet=True)
        smoke_latency(out_path=os.path.join(base, "BENCH_latency.json"),
                      quiet=True)
        smoke_durable(out_path=os.path.join(base, "BENCH_durable.json"),
                      quiet=True)
    else:
        print(__doc__.strip(), file=sys.stderr)
        sys.exit(2)
