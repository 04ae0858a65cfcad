"""The join reference against a nested loop, its two controls against
its limits, and both new cells end to end at the rehearsal's sizes."""

import json
import os

import numpy as np
import pytest

from perfbench import roofline_join
from perfbench.kinds import tpch_q3_tables
from perfbench.ref import relational_join

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def _within(compared, limits):
    return all(compared[k] <= limits[k] for k in compared)


def _data(seed, lines):
    cfg = dict(_json("configs", "tpch_q3_tables_sf2_1chip.json"),
               rows=lines)
    return tpch_q3_tables.generate(seed, cfg)


def _nested_loop(tables, o_before, l_after):
    """Q3 row by row, in float64."""
    c, o, li = tables["customer"], tables["orders"], tables["lineitem"]
    out = {}
    for ci in range(len(c["c_custkey"])):
        seg = bytes(c["c_mktsegment"][0][ci, :c["c_mktsegment"][1][ci]])
        if seg != b"BUILDING":
            continue
        for oi in range(len(o["o_orderkey"])):
            if o["o_custkey"][oi] != c["c_custkey"][ci] \
                    or not o["o_orderdate"][oi] < o_before:
                continue
            for k in range(len(li["l_orderkey"])):
                if li["l_orderkey"][k] == o["o_orderkey"][oi] \
                        and li["l_shipdate"][k] > l_after:
                    key = (int(li["l_orderkey"][k]),
                           int(o["o_orderdate"][oi]),
                           int(o["o_shippriority"][oi]))
                    out[key] = out.get(key, 0.0) + \
                        float(li["l_extendedprice"][k]) * \
                        (1.0 - float(li["l_discount"][k]))
    return out


@pytest.mark.parametrize("seed", [6, 2**31 + 17])
def test_reference_equals_a_nested_loop(seed):
    spec = _json("traffic", "tpch_q3_collect.json")["reference"]
    data = _data(seed, 200)
    # 200 lines over 50 orders of 5 customers, their dates spread over
    # seven years: Q3's own dates would let a line in twenty through
    spec = dict(spec, filters=dict(spec["filters"],
                                   orders=["<", "o_orderdate", 9800],
                                   lineitem=[">", "l_shipdate", 8800]))
    want = _nested_loop(data["tables"], 9800, 8800)
    got = relational_join.run(spec, data["tables"])
    assert dict(zip(got["keys"], got["columns"]["revenue"])) == \
        pytest.approx(want, rel=1e-12)
    rev = got["columns"]["revenue"]
    assert list(rev) == sorted(rev, reverse=True)
    assert got["join_rows"][1] >= len(want) > 0


def test_match_pairs_every_equal_key():
    l = np.array([3, 1, 3, 7, 2])
    r = np.array([3, 3, 2, 9])
    want = sorted((i, j) for i in range(5) for j in range(4)
                  if l[i] == r[j])
    for a, b in ((l, r), (np.tile(l, 3), r)):   # either side the larger
        i, j = relational_join.match(a, b)
        assert (a[i] == b[j]).all()
        assert len(i) == sum(1 for x in a for y in b if x == y)
    i, j = relational_join.match(l, r)
    assert sorted(zip(i.tolist(), j.tolist())) == want


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_both_controls_fail_and_the_reference_passes(seed):
    spec = _json("traffic", "tpch_q3_collect.json")["reference"]
    data = _data(seed, 1 << 17)
    limits = spec["limits"]
    ref = relational_join.reference(data, spec)
    assert len(ref["keys"]) > 100
    good = {"collected": relational_join.as_collected(spec, ref, 10)}
    assert _within(relational_join.check(good, data, spec, 1), limits)
    low = relational_join.check(
        relational_join.control(data, spec, 1), data, spec, 1)
    assert low["agg_max_rel_err"] > 10 * limits["agg_max_rel_err"]
    assert not _within(low, limits)
    dropped = relational_join.check(
        relational_join.control_dropped_filter(data, spec, 1), data, spec,
        1)
    assert dropped["groups_wrong"] + dropped["agg_max_rel_err"] > 0
    assert not _within(dropped, limits)


def test_compare_sees_each_broken_guarantee():
    spec = _json("traffic", "tpch_q3_collect.json")["reference"]
    data = _data(9, 1 << 15)
    ref = relational_join.reference(data, spec)
    good = relational_join.as_collected(spec, ref, 10)

    def numbers(got):
        return relational_join.compare(spec, ref, got)

    assert _within(numbers(good), spec["limits"])
    assert numbers({k: v[:9] for k, v in good.items()})[
        "rows_returned_wrong"] == 1
    swapped = {k: v[[1, 0] + list(range(2, 10))] for k, v in good.items()}
    assert numbers(swapped)["rows_out_of_order"] == 1
    # the eleventh row in the third's place: the third is left out
    eleven = relational_join.as_collected(spec, ref, 11)
    skipped = {k: v[[0, 1, 3, 4, 5, 6, 7, 8, 9, 10]]
               for k, v in eleven.items()}
    assert numbers(skipped)["top_rows_left_out"] == 1
    alien = {k: v.copy() for k, v in good.items()}
    alien["l_orderkey"][4] = 2      # dbgen's sparse keys: never an order's
    assert numbers(alien)["groups_wrong"] == 1
    less = dict(good)
    del less["o_shippriority"]
    assert numbers(less)["columns_missing"] == 1


def test_join_bytes_by_hand():
    cfg = _json("configs", "tpch_q3_tables_sf2_1chip.json")
    spec = _json("traffic", "tpch_q3_collect.json")["reference"]
    # customer 4 + 14 B, orders 16 B, then 12 B carried; lineitem 16 B,
    # 20 B a joined row written
    assert roofline_join.join_bytes(
        spec, cfg["schemas"], cfg["tables"], [292000, 60000]) == \
        300000 * 18 + 3000000 * 16 + 292000 * 12 \
        + 292000 * 12 + 12000000 * 16 + 60000 * 20


@pytest.mark.parametrize("cell", ["tpch_q3_sf2", "tpch_q6_sf2"])
def test_rehearsal_of_the_new_cells_is_correct(cell):
    from perfbench import run as R
    out = R.run_cell(cell, 2**31 + 5, 1.0, 1, rehearse=True)
    assert out["correct"], out["compared"]
    assert out["run"]["compiles_in_window"] == 0
    reported = set(out["metrics"])
    bench = _json("..", "BENCHMARK.json")
    host = {m["name"] for m in bench["per_layer"]
            if m["source"] != "device_trace"
            and cell in m.get("workloads", [cell])}
    assert host <= reported, host - reported
