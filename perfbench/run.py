#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json``.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
    python3 perfbench/run.py ... --rehearse      # CPU, tiny sizes, recorded nowhere

One process, no child.  A cell is a configuration (``configs/<name>.json``,
whose ``kind`` names ``kinds/<kind>.py``: data from the seed and its
ingest) under a traffic mix (``traffic/<name>.json``, whose ``driver``
names ``drivers/<driver>.py``: one query, and whose ``reference`` names
``ref/<module>.py``: the plain check).  A per-layer metric is
``layers/<name>.py`` with one ``read(run)``.  Nothing here names a cell:
a new one is new files and new entries.

Set-up (counted in ``setup_s``): data from ``--seed`` with numpy, ingest
through ``from_pdata -> to_store``, ``Context``, the warm-up queries.
Then a closed loop of one client for ``--seconds`` seconds, which closes
when the query in flight completes.  Then the peak memory is read, the
program's state freed, and the answers kept from the window compared
with the reference.  The last line of standard output is the result.
Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(workload, root=ROOT):
    """cell -> (cell, its configuration file, its traffic file, and the
    metrics it reports)."""
    bench = _load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"perfbench: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    cfg = _load_json(root, cfg_entry["file"])
    traffic = _load_json(HERE, "traffic", cell["traffic"] + ".json")

    def mine(m):
        return "workloads" not in m or workload in m["workloads"]
    return cell, cfg, traffic, {
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)]}


def _module(group, name):
    return importlib.import_module(f"perfbench.{group}.{name}")


def find_devices(chips, rehearse):
    """The devices to run on, or None where this machine cannot run the
    cell (no TPU, or fewer chips than it asks for)."""
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={chips}").strip()
    import jax
    devices = jax.devices()
    want = "cpu" if rehearse else "tpu"
    if devices[0].platform != want:
        print(f"perfbench: JAX found {devices[0].platform!r} devices, not "
              f"{want!r}", file=sys.stderr)
        return None
    if len(devices) < chips:
        print(f"perfbench: the cell asks for {chips} chip(s), JAX found "
              f"{len(devices)}", file=sys.stderr)
        return None
    return devices[:chips]


def run_cell(workload, seed, seconds, trace, rehearse=False, devices=None,
             root=ROOT, dump_trace=None):
    """Everything of a run after the look for a chip.  Returns the result
    line as a dict."""
    import numpy as np
    import jax
    from perfbench.meter import Meter
    from perfbench.spans import Spans

    cell, cfg, traffic, metrics = resolve(workload, root)
    chips = int(cell["chips"])
    if devices is None:
        devices = jax.devices()[:chips]
    kind = _module("kinds", cfg["kind"])
    driver = _module("drivers", traffic["driver"])
    refspec = traffic["reference"]
    ref = _module("ref", refspec["module"])

    from dryad_tpu import Context, make_mesh
    meter = Meter()
    events = []
    work_root = os.path.join(root, ".perfbench_work")
    os.makedirs(work_root, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix=workload + "-", dir=work_root)
    spans = Spans(traced=bool(trace))
    tracing = False
    try:
        # ---- set-up ------------------------------------------------------
        ctx = Context(mesh=make_mesh(devices), event_log=events.append)
        data = kind.generate(seed, cfg, rehearse=rehearse)
        state = kind.ingest(ctx, data, cfg, workdir)
        dstate = driver.prepare(ctx, state, traffic, workdir)
        warm = int(cfg.get("warm_queries", traffic.get("warm_queries", 1)))
        for w in range(warm):
            spans.query = -1 - w
            driver.release(driver.query(ctx, dstate, -1 - w, spans))
        setup_meter = meter.snapshot()
        del events[:]
        del spans.rows[:]

        # ---- the window --------------------------------------------------
        rng = np.random.default_rng([int(seed), 99])
        n_trace = int(traffic.get("trace_queries", 3)) if trace else 0
        trace_dir = os.path.join(workdir, "trace")
        queries, kept, failed = [], [], 0
        # the answers the check reads: all of them, or as many as the
        # traffic (or the configuration) says, drawn evenly from the seed
        sample = cfg.get("check_sample", traffic.get("check_sample", 2))
        if traffic.get("check_answers", "sample") == "all":
            sample = None
        if n_trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            # the TPU's host tracer writes millions of runtime events; the
            # rehearsal's stand-in for device operations are host events
            opts.host_tracer_level = 2 if rehearse else 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
        setup_s = time.time() - T_START
        t_open = time.perf_counter()
        i = 0
        traced_rows = None
        while True:
            spans.query = i
            ev0 = len(events)
            q0 = time.perf_counter()
            try:
                with spans.span("query"):
                    answer = driver.query(ctx, dstate, i, spans)
            except Exception as e:      # a failed query ends the window
                failed += 1
                print(f"perfbench: query {i} failed: {e!r}",
                      file=sys.stderr)
                break
            q1 = time.perf_counter()
            queries.append({"i": i, "t0": q0, "t1": q1,
                            "events": events[ev0:]})
            if tracing and len(queries) == n_trace:
                jax.profiler.stop_trace()
                tracing = False
                traced_rows = list(spans.rows)
            if sample is None or len(kept) < sample:
                kept.append((i, answer))
            else:                       # reservoir: each answer as likely
                j = int(rng.integers(0, i + 1))
                if j < sample:
                    driver.release(kept[j][1])
                    kept[j] = (i, answer)
                else:
                    driver.release(answer)
            i += 1
            if time.perf_counter() - t_open >= seconds:
                break
        t_close = queries[-1]["t1"] if queries else time.perf_counter()
        if tracing:
            jax.profiler.stop_trace()
            tracing = False
            traced_rows = list(spans.rows)
        window_s = t_close - t_open
        window_meter = meter.snapshot()
        answers = sorted(kept, key=lambda a: a[0])

        # ---- after the window: memory, then free, then the check ---------
        peak = 0
        for d in devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        nparts = ctx.nparts
        device_kind = devices[0].device_kind
        del ctx, dstate
        compared, limits = {}, refspec["limits"]
        t_chk = time.time()
        for idx, ans in answers:
            for k, v in ref.check(ans, data, refspec, nparts).items():
                compared[k] = max(compared.get(k, 0), v)
        if not answers:
            compared["answers_checked"] = 0
            limits = dict(limits, answers_checked=None)
        check_s = time.time() - t_chk
        correct = bool(answers) and not failed and all(
            k in limits and compared[k] <= limits[k] for k in compared)

        done = len(queries)
        rows = state["rows"]
        # what a per-layer reader (layers/<name>.py) is handed
        run = {"cfg": cfg, "traffic": traffic, "chips": chips,
               "device_kind": device_kind, "state": state,
               "queries": queries, "spans": spans,
               "compiles_in_window": window_meter["compiles"]
               - setup_meter["compiles"], "trace": None}
        device = {"platform": devices[0].platform, "kind": device_kind,
                  "count": len(devices), "memory_peak_bytes": peak}
        out = {"correct": correct, "attempted": done + failed,
               "failed": failed, "metrics": {}, "device": device}
        if trace:
            from perfbench import trace_reduce
            t_red = time.time()
            xplane = trace_reduce.find_xplane(trace_dir)
            loaded = trace_reduce.add_host_spans(trace_reduce.load(xplane),
                                                 traced_rows)
            if dump_trace:
                trace_reduce.dump(loaded, xplane, dump_trace)
            summ = trace_reduce.reduce(loaded, chips=chips)
            run["trace"] = summ
            device["busy_s"] = summ["busy_s_mean"]
            device["window_s"] = summ["window_s"]
            for m in metrics["per_layer"]:
                v = _module("layers", m["name"]).read(run)
                if v is not None:
                    out["metrics"][m["name"]] = {"value": v,
                                                 "unit": m["unit"]}
            out["breakdown"] = {"device_ops": summ["device_ops"],
                                "idle_gaps": summ["idle_gaps"]}
            out["trace"] = {"reduce_s": time.time() - t_red,
                            "busiest": summ["busiest"],
                            "busy_per_query_s": summ["busy_per_query_s"],
                            "collective_per_query_s":
                            summ["collective_per_query_s"],
                            "modules": summ["modules"]}
        else:
            e2e = {"setup_s": setup_s}
            if done:
                e2e["rows_per_s"] = rows * done / window_s
                e2e["query_s"] = window_s / done
            for m in metrics["end_to_end"]:
                if m["name"] in e2e:
                    out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                                 "unit": m["unit"]}
        out["run"] = {
            "workload": workload, "seed": seed, "seconds": seconds,
            "rehearse": rehearse, "rows": rows, "queries": done,
            "window_s": window_s, "check_s": check_s,
            "answers_checked": [i for i, _ in answers],
            "setup": {"compiles": setup_meter["compiles"],
                      "compile_s": setup_meter["compile_s"],
                      "persistent_cache_hits": setup_meter["hits"],
                      "persistent_cache_misses": setup_meter["misses"]},
            "compiles_in_window": run["compiles_in_window"],
            "query_s_each": [q["t1"] - q["t0"] for q in queries][:32]}
        out["compared"] = {k: {"value": v, "limit": limits.get(k)}
                           for k, v in compared.items()}
        return out
    finally:
        if tracing:
            jax.profiler.stop_trace()
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1: also write the lines of the "
                         "trace that the reduction reads, as gzipped JSON")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, tiny sizes; says platform cpu; recorded "
                         "nowhere")
    args = ap.parse_args(argv)
    if not os.path.isfile(os.path.join(ROOT, "dryad_tpu", "__init__.py")):
        print("perfbench: the program (dryad_tpu/) is not in this "
              "directory", file=sys.stderr)
        return 2
    cell, _cfg, _traffic, _m = resolve(args.workload)
    seconds = args.seconds
    if seconds is None:
        seconds = _load_json(ROOT, "BENCHMARK.json")["run_seconds"]
    devices = find_devices(int(cell["chips"]), args.rehearse)
    if devices is None:
        return 1
    if not args.rehearse:
        from perfbench import roofline
        roofline.peaks(devices[0].device_kind)     # unknown device: error
    out = run_cell(args.workload, args.seed, seconds, args.trace,
                   rehearse=args.rehearse, devices=devices,
                   dump_trace=args.dump_trace)
    for k, v in out["compared"].items():
        print(f"perfbench: compared {k} = {v['value']} "
              f"(limit {v['limit']})", file=sys.stderr)
    print(f"perfbench: correct = {out['correct']}", file=sys.stderr,
          flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
