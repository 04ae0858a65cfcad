"""``perfbench/program_spans.py`` and the per-layer readers built on it:
self time, union and ``untraced`` on a hand-made event list with nested
and overlapping spans; every new reader gives nothing on a run without
its span; the rehearsal prints the new metrics a CPU can give."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from perfbench import program_spans as ps
from perfbench import run as R
from perfbench.spans import Spans

NEW = ["store_file_read_ms", "store_verify_ms", "store_stack_ms",
       "store_put_ms", "fetch_ms", "store_checksum_ms", "store_write_MBps",
       "plan_ms", "dispatch_ms", "stage_device_ms", "sql_front_ms",
       "host_untraced_ms"]
CELLS = ["sort100_1chip", "tpch_q1_sf2", "sort100_4chip"]
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _span(name, kind, t0, t1, span, parent=None, **attrs):
    e = {"event": "span", "name": name, "kind": kind, "trace": "t",
         "span": span, "t0": 1000.0 + t0, "dur_s": t1 - t0}
    if parent:
        e["parent"] = parent
    if attrs:
        e["attrs"] = attrs
    return e


def _reader(name):
    return importlib.import_module(f"perfbench.layers.{name}").read


def hand_made():
    """One query of 10 s (its own clock starts elsewhere: 50 .. 60).

    sql.query 0-3: parse 0-0.5, bind 0.5-1, lower 1-3 holding from_store
    1.2-2.8 > store.read 1.3-2.7 > file_read 1.3-1.7, verify 1.7-2.0,
    stack 2.0-2.4, put 2.4-2.6.  The harness's sql_front ends at 3.5.
    A stray parse + bind outside sql.query (the harness's sql_bind call)
    at 9.0-9.2 and 9.2-9.3.
    collect 4-9: plan 4-4.1, lint 4.1-4.3, run 4.5-7 holding stage a
    4.6-5.6 (compile 4.7-5.2), stage b 5.5-6.0 (overlaps a), settle 6-6.9;
    collect.fetch 7-8.5; two store.fetch 8.5-8.6 and 8.55-8.7 (overlap);
    store.file_write 8.7-8.9 of 40 MB.
    Covered: 0-3, 4-9.3 -> 8.3 s of 10; untraced 1.7 s."""
    ev = [
        _span("sql.parse", "front", 0.0, 0.5, "p", "q"),
        _span("sql.bind", "front", 0.5, 1.0, "b", "q"),
        _span("store.file_read", "io", 1.3, 1.7, "fr", "sr", bytes=100),
        _span("store.verify", "io", 1.7, 2.0, "ve", "sr"),
        _span("store.stack", "io", 2.0, 2.4, "st", "sr"),
        _span("store.put", "io", 2.4, 2.6, "pu", "sr"),
        _span("store.read", "io", 1.3, 2.7, "sr", "fs"),
        _span("from_store", "query", 1.2, 2.8, "fs", "lo"),
        _span("sql.lower", "front", 1.0, 3.0, "lo", "q"),
        {"event": "sql_query"},
        _span("sql.query", "query", 0.0, 3.0, "q"),
        _span("plan", "plan", 4.0, 4.1, "pl", "c"),
        _span("lint", "plan", 4.1, 4.3, "li", "c"),
        _span("stage.compile", "compile", 4.7, 5.2, "co", "sa"),
        _span("stage 0:a", "stage", 4.6, 5.6, "sa", "r"),
        _span("stage 1:b", "stage", 5.5, 6.0, "sb", "r"),
        _span("settle", "wait", 6.0, 6.9, "se", "r"),
        {"event": "stage_done"},
        _span("run", "job", 4.5, 7.0, "r", "c"),
        _span("collect.fetch", "io", 7.0, 8.5, "cf", "c"),
        _span("store.fetch", "io", 8.5, 8.6, "f0", "c", partition=0),
        _span("store.fetch", "io", 8.55, 8.7, "f1", "c", partition=1),
        _span("store.file_write", "io", 8.7, 8.9, "fw", "c",
              bytes=40_000_000),
        _span("collect", "query", 4.0, 9.0, "c"),
        _span("sql.parse", "front", 9.0, 9.2, "p2"),
        _span("sql.bind", "front", 9.2, 9.3, "b2"),
    ]
    spans = Spans(traced=True)
    spans.rows.append((0, "sql_front", 1000.0, 1003.5))
    return {"queries": [{"i": 0, "t0": 50.0, "t1": 60.0, "events": ev}],
            "spans": spans, "trace": None}


def test_rows_keep_span_events_only():
    rows = ps.rows(hand_made())
    assert len(rows) == 24
    q, name, kind, t0, t1, span, parent, attrs = rows[2]
    assert (q, name, kind, span, parent) == (0, "store.file_read", "io",
                                             "fr", "sr")
    assert t0 == pytest.approx(1001.3) and t1 == pytest.approx(1001.7)
    assert attrs == {"bytes": 100}


def test_union_of_nested_and_overlapping_intervals():
    assert ps.union_seconds([]) == 0.0
    assert ps.union_seconds([(0, 2), (1, 3), (5, 6), (5.2, 5.4)]) \
        == pytest.approx(4.0)


def test_self_time_leaves_out_children_once():
    qrows = ps.by_query(hand_made())[0]
    by = {r[5]: r for r in qrows}
    # run 2.5 s; children a 4.6-5.6, b 5.5-6.0, settle 6-6.9 cover 2.3
    assert ps.self_seconds(by["r"], qrows) == pytest.approx(0.2)
    assert ps.self_seconds(by["sa"], qrows) == pytest.approx(0.5)
    assert ps.self_seconds(by["lo"], qrows) == pytest.approx(0.4)
    assert ps.self_seconds(by["sr"], qrows) == pytest.approx(0.1)
    assert ps.self_seconds(by["fr"], qrows) == pytest.approx(0.4)


def test_seconds_sums_a_name_within_a_query():
    run = hand_made()
    assert ps.seconds(run, "store.fetch") == pytest.approx(0.25)
    assert ps.seconds(run, ("plan", "lint")) == pytest.approx(0.3)
    assert ps.seconds(run, "no.such.span") is None


def test_untraced_is_the_wall_less_the_union():
    assert ps.untraced(hand_made()) == pytest.approx(1.7)


def test_median_is_over_queries():
    run = hand_made()
    more = dict(run["queries"][0], i=1, events=[
        _span("store.fetch", "io", 0.0, 1.0, "x")])
    run["queries"].append(more)
    assert ps.seconds(run, "store.fetch") == pytest.approx((0.25 + 1) / 2)
    # a query without the span is left out, not counted as zero
    assert ps.seconds(run, "plan") == pytest.approx(0.1)


@pytest.mark.parametrize("name,want", [
    ("store_file_read_ms", 400.0),
    ("store_verify_ms", 300.0),
    ("store_stack_ms", 400.0),
    ("store_put_ms", 200.0 + 800.0),     # enqueue + (3.5 - 2.7) of wait
    ("fetch_ms", 1500.0 + 250.0),
    ("store_write_MBps", 200.0),
    ("plan_ms", 300.0),
    ("dispatch_ms", 200.0 + 500.0 + 500.0),
    ("sql_front_ms", 500.0 + 500.0 + 400.0),
    ("host_untraced_ms", 1700.0),
])
def test_reader_on_the_hand_made_run(name, want):
    assert _reader(name)(hand_made()) == pytest.approx(want)


def test_stage_device_ms_reads_named_modules():
    run = hand_made()
    run["trace"] = {"real_device": True, "n_queries": 2, "modules": [
        ["jit_stage_orderby_range_sort(123)", 3.0],
        ["jit_dynamic_slice(9)", 0.5],
        ["jit_stage_sort_input(77)", 1.0]]}
    assert _reader("stage_device_ms")(run) == pytest.approx(2000.0)
    run["trace"]["modules"] = [["jit_per_shard(1)", 3.0]]
    assert _reader("stage_device_ms")(run) is None
    run["trace"]["real_device"] = False
    assert _reader("stage_device_ms")(run) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_without_its_span(name):
    """A program of before this PR: ``run`` and ``stage`` spans only,
    and other events; and a run with no query at all."""
    read = _reader(name)
    none = {"queries": [], "spans": Spans(False), "trace": None}
    assert read(none) is None
    old = {"queries": [{"i": 0, "t0": 0.0, "t1": 1.0, "events": [
        {"event": "plan"},
        _span("stage 0:output", "stage", 0.1, 0.2, "s", "r"),
        {"event": "stage_done"},
        _span("run", "job", 0.1, 0.4, "r")]}],
        "spans": Spans(True), "trace": {
            "real_device": True, "n_queries": 1,
            "modules": [["jit_per_shard(1)", 0.3]]}}
    got = read(old)
    if name == "dispatch_ms":       # those two spans are what it reads
        assert got == pytest.approx(300.0)
    elif name == "host_untraced_ms":
        assert got == pytest.approx(700.0)
    else:
        assert got is None


def test_benchmark_json_lists_every_new_reader():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        m = per_layer[name]
        assert m["source"] == ("device_trace" if name == "stage_device_ms"
                               else "program_span")
        assert set(m["workloads"]) <= set(CELLS)


def test_selfcheck_still_passes():
    out = subprocess.run([sys.executable,
                          os.path.join(ROOT, "perfbench", "selfcheck.py")],
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_prints_the_new_metrics(cell):
    """``run.py --workload <cell> --rehearse --trace 1``: every new metric
    of the cell that a CPU can give (all but the device trace's)."""
    out = R.run_cell(cell, 2**31 + 11, 0.3, 1, rehearse=True)
    assert out["correct"] is True, out["compared"]
    _cell, _cfg, _traffic, metrics = R.resolve(cell)
    mine = {m["name"] for m in metrics["per_layer"]} & set(NEW)
    assert mine - set(out["metrics"]) == {"stage_device_ms"}
    for name in mine - {"stage_device_ms"}:
        assert out["metrics"][name]["value"] >= 0
    read_ms = sum(out["metrics"][n]["value"] for n in (
        "store_file_read_ms", "store_verify_ms", "store_stack_ms",
        "store_put_ms"))
    assert read_ms > 0
