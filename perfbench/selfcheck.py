#!/usr/bin/env python3
"""Check the yardstick against itself, with no chip and no profiler:

    python3 perfbench/selfcheck.py          (or python3 -m perfbench.selfcheck)

* ``trace_reduce.reduce`` on a hand-made trace whose busy time, per-query
  time, collective time and gap attribution are worked out by hand below;
* the same on ``selfcheck_trace.json``, lines cut from a trace recorded on
  the chip (PERF.md says of which run), against the numbers kept in it;
* the roofline's byte counts for the three configurations;
* every cell of ``BENCHMARK.json`` resolves to its files, every per-layer
  metric has its reader, and a reader that finds nothing returns nothing.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import roofline, trace_reduce     # noqa: E402
from perfbench.spans import Spans                # noqa: E402


def near(a, b, rel=1e-9):
    return abs(a - b) <= rel * max(abs(a), abs(b), 1e-30)


def same(got, want):
    if isinstance(want, list):
        return (isinstance(got, list) and len(got) == len(want)
                and all(same(g, w) for g, w in zip(got, want)))
    if isinstance(want, float):
        return near(got, want, 1e-6)
    return got == want


def hand_made():
    """Two queries of 1000 ns; device 0 busy 100-400 (two overlapping
    ops), 1200-1500 (an all-to-all) and 1900-2100 (runs past the window's
    end at 2000); device 1 busy 100-200 only."""
    ms = 1.0
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["perfbench:query", 0 * ms, 1000 * ms],
            ["perfbench:store_read", 0 * ms, 100 * ms],
            ["perfbench:execute_and_write", 100 * ms, 900 * ms],
            ["perfbench:query", 1000 * ms, 1000 * ms],
            ["perfbench:store_read", 1000 * ms, 150 * ms],
            ["perfbench:execute_and_write", 1200 * ms, 800 * ms],
            ["SomethingElse", 5.0, 7.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["fusion.1", 100 * ms, 200 * ms],
                ["sort.2", 250 * ms, 150 * ms],
                ["all-to-all.3", 1200 * ms, 300 * ms],
                ["fusion.1", 1900 * ms, 200 * ms]]},
            {"name": "XLA Modules", "events": [
                ["jit_stage(1)", 100 * ms, 300 * ms],
                ["jit_stage(1)", 1200 * ms, 300 * ms]]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [["fusion.1", 100 * ms, 100 * ms]]}]},
    ]}


def check_hand_made():
    s = trace_reduce.reduce(hand_made(), chips=2)
    assert s["real_device"] and s["busiest"] == "/device:TPU:0"
    assert near(s["window_s"], 2000e-9)
    assert near(s["busy_s_busiest"], (300 + 300 + 100) * 1e-9), s
    assert near(s["busy_s_mean"], (700 + 100) / 2 * 1e-9)
    assert [round(x * 1e9) for x in s["busy_per_query_s"]] == [300, 400]
    assert [round(x * 1e9) for x in s["collective_per_query_s"]] == [0, 300]
    ops = dict(s["device_ops"])
    assert near(ops["fusion.1"], 400e-9) and near(ops["sort.2"], 150e-9)
    gaps = dict(s["idle_gaps"])
    # idle of device 0 in the window: 0-100 (store_read), 400-1000
    # (execute_and_write), 1000-1200 (150 store_read, 50 between spans),
    # 1500-1900 (execute_and_write)
    assert near(gaps["store_read"], 250e-9), gaps
    assert near(gaps["execute_and_write"], 1000e-9), gaps
    assert near(gaps["between_spans"], 50e-9), gaps
    assert near(sum(gaps.values()), s["window_s"] - s["busy_s_busiest"])


def check_recorded():
    path = os.path.join(HERE, "selfcheck_trace.json")
    with open(path) as f:
        rec = json.load(f)
    s = trace_reduce.reduce(rec["trace"], chips=rec["chips"])
    for k, want in rec["expect"].items():
        got = s[k]
        if k == "device_ops":           # the file keeps the first few
            got = got[:len(want)]
        assert same(got, want), (k, got, want)
    return rec.get("of", "")


def check_bytes():
    cfgs = {}
    for name in os.listdir(os.path.join(HERE, "configs")):
        with open(os.path.join(HERE, "configs", name)) as f:
            cfgs[name[:-5]] = json.load(f)
    g = cfgs["gensort100_8Mi_1chip"]
    assert roofline.device_row_bytes(g["schema"]) == 108
    assert roofline.sort_bytes(g["records"], 108) == 2 * 8388608 * 108
    assert near(roofline.least_seconds(2 * 8388608 * 108, "TPU v5 lite"),
                1811939328 / 819e9)
    g4 = cfgs["gensort100_16Mi_4chip"]
    assert roofline.sort_bytes(g4["records"] // 4, 108) == 2 * 4194304 * 108
    t = cfgs["tpch_lineitem_sf2_1chip"]
    assert roofline.device_row_bytes(t["schema"]) == 145
    with open(os.path.join(HERE, "traffic", "tpch_q1_collect.json")) as f:
        spec = json.load(f)["reference"]
    from perfbench.ref import relational
    used = sorted(relational.columns_used(spec) & set(t["schema"]))
    assert used == ["l_discount", "l_extendedprice", "l_linestatus",
                    "l_quantity", "l_returnflag", "l_shipdate", "l_tax"]
    assert roofline.scan_bytes(t["rows"], t["schema"], used) \
        == 12000000 * 30
    try:
        roofline.peaks("TPU v9 imaginary")
    except KeyError:
        pass
    else:
        raise AssertionError("an unknown device has to be an error")


def check_wiring():
    from perfbench import run as R
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    empty = {"spans": Spans(False), "queries": [], "trace": None,
             "compiles_in_window": 0, "cfg": {}, "traffic": {},
             "state": {"rows": 0, "stored_bytes": 0}, "chips": 1,
             "device_kind": "TPU v5 lite"}
    for m in bench["per_layer"]:
        mod = importlib.import_module(f"perfbench.layers.{m['name']}")
        v = mod.read(empty)
        assert v is None or m["name"] == "compiles_in_window", (m, v)
    for w in bench["workloads"]:
        cell, cfg, traffic, metrics = R.resolve(w["name"])
        R._module("kinds", cfg["kind"])
        R._module("drivers", traffic["driver"])
        R._module("ref", traffic["reference"]["module"])
        assert cfg["chips"] == cell["chips"]
        assert any(m["name"] == "setup_s" for m in metrics["end_to_end"])
        assert metrics["per_layer"]


def main() -> int:
    check_hand_made()
    of = check_recorded()
    check_bytes()
    check_wiring()
    print(f"perfbench.selfcheck: ok (recorded trace: {of})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
