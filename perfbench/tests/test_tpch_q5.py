"""The ``tpch_q5_tables`` kind and the cell ``tpch_q5_sf2``: the
generator's own rules (row counts, the specification's nations and
regions, every line's supplier, keys that are keys), the reference in the
program's place and its controls (the ``region`` filter left out: nations
of other regions; the cycle's ``c_nationkey = s_nationkey`` left out: the
same groups, every sum about 25 times too large; bfloat16 arithmetic:
``agg_max_rel_err`` over its limit), the least bytes of the joins with a
join that closes a cycle, the reader, and the rehearsal of the cell."""

import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench import roofline, roofline_join
from perfbench.kinds import tpch_q5_tables
from perfbench.layers import inherited_key_joins
from perfbench.ref import relational_join

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CELL = "tpch_q5_sf2"
ASIA = {b"INDIA", b"INDONESIA", b"JAPAN", b"CHINA", b"VIETNAM"}


def _load(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


CFG = _load("configs", "tpch_q5_tables_sf2_1chip.json")
SPEC = _load("traffic", "tpch_q5_collect.json")["reference"]


def _strs(col):
    data, lens = col
    return [bytes(d[:n]) for d, n in zip(data, lens)]


def _within(compared):
    return all(compared[k] <= SPEC["limits"][k] for k in compared)


def _without_cycle(spec):
    out = copy.deepcopy(spec)
    out["joins"] = [j for j in out["joins"]
                    if j != ["customer", "c_nationkey", "supplier",
                             "s_nationkey"]]
    assert len(out["joins"]) == len(spec["joins"]) - 1
    return out


@pytest.fixture(scope="module")
def data():
    return tpch_q5_tables.generate(2**31 + 5, dict(CFG, rows=480000))


# -- the generator --------------------------------------------------------------

def test_row_counts_columns_and_keys(data):
    assert tpch_q5_tables.sizes(CFG) == CFG["tables"] == {
        "customer": 300000, "orders": 3000000, "lineitem": 12000000,
        "supplier": 20000, "nation": 25, "region": 5}
    assert sum(CFG["tables"].values()) == 15320030
    assert CFG["keys"] == {t: [k] for t, k in tpch_q5_tables.KEYS.items()}
    for t, cols in data["tables"].items():
        assert list(CFG["schemas"][t]) == list(cols), t
        for c, v in cols.items():
            assert len(v[1] if isinstance(v, tuple) else v) == \
                data["n"][t], (t, c)
            spec = CFG["schemas"][t][c]
            if isinstance(v, tuple):
                assert spec == {"kind": "str", "max_len": v[0].shape[1]}
                assert 1 <= v[1].min() and v[1].max() <= v[0].shape[1]
            else:
                assert spec["dtype"] == str(v.dtype)
    for t, key in tpch_q5_tables.KEYS.items():
        k = data["tables"][t][key]
        assert len(np.unique(k)) == len(k), t


def test_nations_regions_and_suppliers(data):
    t = data["tables"]
    nation, region, sup = t["nation"], t["region"], t["supplier"]
    assert _strs(region["r_name"]) == list(tpch_q5_tables.REGIONS)
    names = _strs(nation["n_name"])
    assert len(set(names)) == 25 and names[0] == b"ALGERIA"
    assert names[24] == b"UNITED STATES" and names[18] == b"CHINA"
    asia = list(tpch_q5_tables.REGIONS).index(b"ASIA")
    assert {names[i] for i in np.flatnonzero(
        nation["n_regionkey"] == asia)} == ASIA
    assert np.bincount(nation["n_regionkey"]).tolist() == [5] * 5
    # every line's supplier exists; nations uniform over the 25
    assert np.isin(t["lineitem"]["l_suppkey"], sup["s_suppkey"]).all()
    counts = np.bincount(sup["s_nationkey"], minlength=25)
    assert counts.min() > 0.7 * 800 and counts.max() < 1.3 * 800
    phone = _strs(sup["s_phone"])
    assert all(int(p[:2]) == n + 10
               for p, n in zip(phone[:200], sup["s_nationkey"][:200]))
    assert _strs(sup["s_name"])[0] == b"Supplier#000000001"


# -- the reference and its controls ---------------------------------------------

def test_the_reference_in_the_programs_place_is_correct(data):
    spec = copy.deepcopy(SPEC)
    ref = relational_join.run(spec, data["tables"])
    assert {k[0] for k in ref["keys"]} == ASIA
    got = relational_join.as_collected(spec, ref, None)
    compared = relational_join.check({"collected": got}, data, spec, 1)
    assert compared == {k: 0 for k in spec["limits"]}
    assert spec["join_rows_found"] == ref["join_rows"]
    rows = ref["join_rows"]
    # the cycle's join is a filter: it keeps about one line in 25
    assert 15 < rows[2] / rows[3] < 40


def test_without_the_region_filter_nations_of_other_regions_appear(data):
    ctl = relational_join.check(relational_join.control_dropped_filter(
        data, SPEC, 1), data, SPEC, 1)
    assert ctl["groups_wrong"] >= 20 and not _within(ctl)


def test_without_the_cycle_every_sum_is_about_25_times_too_large(data):
    ref = relational_join.run(SPEC, data["tables"])
    open_ = relational_join.run(_without_cycle(SPEC), data["tables"])
    assert open_["keys"] and set(open_["keys"]) == set(ref["keys"])
    ratio = open_["columns"]["revenue"] / np.asarray(
        [ref["columns"]["revenue"][ref["keys"].index(k)]
         for k in open_["keys"]])
    assert (15 < ratio).all() and (ratio < 40).all()
    ctl = relational_join.compare(SPEC, ref, relational_join.as_collected(
        SPEC, open_, None))
    assert ctl["groups_wrong"] == 0 and ctl["columns_missing"] == 0
    assert ctl["agg_max_rel_err"] > 10 and not _within(ctl)


def test_bfloat16_sums_fail_the_limit(data):
    ctl = relational_join.check(relational_join.control(data, SPEC, 1),
                                data, SPEC, 1)
    assert ctl["groups_wrong"] == 0
    assert ctl["agg_max_rel_err"] > 10 * SPEC["limits"]["agg_max_rel_err"]


def test_the_controls_at_the_cells_size():
    """At 12,000,000 lines (numpy alone, about a minute): the program's
    float32 sums of about 2,900 lines a group must come within 1e-5; the
    reference in bfloat16 must not, nor without the cycle's key."""
    d = tpch_q5_tables.generate(2**31 + 77, CFG)
    ref = relational_join.run(SPEC, d["tables"])
    assert {k[0] for k in ref["keys"]} == ASIA
    rows = ref["join_rows"]
    assert 2500 * 5 < rows[-1] < 3400 * 5
    bf = relational_join.compare(SPEC, ref, relational_join.as_collected(
        SPEC, relational_join.run(SPEC, d["tables"],
                                  precision="bfloat16"), None))
    assert bf["agg_max_rel_err"] > SPEC["limits"]["agg_max_rel_err"]
    f32 = relational_join.compare(SPEC, ref, relational_join.as_collected(
        SPEC, relational_join.run(SPEC, d["tables"], precision="float32"),
        None))
    assert f32["agg_max_rel_err"] < SPEC["limits"]["agg_max_rel_err"] / 10
    print(f"\nbfloat16 {bf['agg_max_rel_err']:.4g}, float32 inputs "
          f"{f32['agg_max_rel_err']:.4g}, join rows {rows}")


# -- the least bytes of joins with a cycle ---------------------------------------

def test_join_bytes_counts_the_cycle_join_as_a_pass_over_the_running_join(
        data):
    spec = copy.deepcopy(SPEC)
    rows = relational_join.run(spec, data["tables"])["join_rows"]
    stored = dict(data["n"])
    schemas = CFG["schemas"]
    total = roofline_join.join_bytes(spec, schemas, stored, rows)
    # the cycle (join 3) names no new table: dropping it takes away just
    # the running join read at join 2's rows and written at its own
    cut = copy.deepcopy(spec)
    del cut["joins"][3]
    without = roofline_join.join_bytes(cut, schemas, stored,
                                       rows[:3] + rows[4:])
    named = roofline_join.named_columns(spec, schemas)
    widest = max(roofline.device_row_bytes({c: schemas[t][c]
                                            for c in named[t]})
                 for t in ("customer", "orders", "lineitem"))
    assert 0 < total - without <= (rows[2] + rows[3]) * 6 * widest
    # every stored table is read once, at the columns the query names
    reads = sum(stored[t] * roofline.device_row_bytes(
        {c: schemas[t][c] for c in named[t]}) for t in spec["tables"])
    assert reads < total < reads + 2 * sum(rows) * 64


def test_join_roofline_stays_far_under_100_percent_at_the_cells_size(data):
    """The least bytes at the cell's size (the reference's join rows of a
    seed, from its shares at 480,000 lines scaled to 12,000,000) over the
    chip's bandwidth, against the least device time five join stages at 12
    M rows of capacity have taken (0.14 s a lookup stage, PERF.md)."""
    rows = relational_join.run(SPEC, data["tables"])["join_rows"]
    rows = [r * 25 for r in rows]
    least = roofline.least_seconds(roofline_join.join_bytes(
        SPEC, CFG["schemas"], CFG["tables"], rows), "TPU v5 lite")
    assert least < 0.05 * (5 * 0.14)


# -- the reader -----------------------------------------------------------------

def _lower(n=None):
    attrs = {"unique_joins": 5}
    if n is not None:
        attrs["inherited_unique_joins"] = n
    return {"event": "span", "name": "sql.lower", "kind": "front",
            "t0": 1000.0, "dur_s": 0.001, "span": "lo", "attrs": attrs}


def _run(*queries):
    return {"queries": [{"i": i, "events": ev}
                        for i, ev in enumerate(queries)]}


def test_the_reader_on_recorded_events():
    assert inherited_key_joins.read(_run([_lower(1)], [_lower(1)],
                                         [_lower(0)])) == 1.0
    assert inherited_key_joins.read(_run([_lower(0)])) == 0.0
    # an older program's span has no such attribute; a query with none
    assert inherited_key_joins.read(_run([_lower()], [_lower()])) is None
    assert inherited_key_joins.read(_run([], [])) is None
    assert inherited_key_joins.read(_run()) is None


def test_benchmark_json_lists_the_cell_and_the_reader():
    bench = _load("..", "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == dict(cells[CELL], config=CFG["name"],
                               traffic="tpch_q5_collect", chips=1)
    per = {m["name"]: m for m in bench["per_layer"]}
    assert per["inherited_key_joins"]["workloads"] == [CELL]
    assert per["inherited_key_joins"]["layer"] == "front end"
    assert CELL not in per["kernel_search_ms"]["workloads"]
    for m in bench["per_layer"]:
        if "tpch_q3_sf2" in m.get("workloads", ()) \
                and m["name"] != "kernel_search_ms":
            assert m["workloads"][-1] == CELL, m["name"]
    assert per["join_hash_stages"]["workloads"][-1] == CELL


def test_rehearsal_of_the_cell_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--rehearse", "--trace", "1", "--seconds", "1", "--seed",
         str(2**31 + 17)], capture_output=True, text=True, cwd=ROOT,
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["join_hash_stages"] == 0.0 and m["inherited_key_joins"] == 1.0
    assert m["stage_attempts"] == 6.0 and m["compiles_in_window"] == 0.0
    assert line["run"]["rows"] == sum(tpch_q5_tables.sizes(CFG,
                                                           True).values())
