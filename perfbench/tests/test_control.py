"""The control of every reference comes out as not correct, and the
reference put in the program's place comes out as correct: at a size a
test run can hold (on the chip at the cells' own sizes: PERF.md)."""

import json
import os

import numpy as np
import pytest

from perfbench.kinds import gensort, tpch_lineitem
from perfbench.ref import relational, valsort

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _traffic(name):
    with open(os.path.join(HERE, "traffic", name + ".json")) as f:
        return json.load(f)


def _within(compared, limits):
    return all(compared[k] <= limits[k] for k in compared)


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 77])
@pytest.mark.parametrize("nparts", [1, 4])
def test_valsort_control_fails_and_true_sort_passes(seed, nparts):
    spec = _traffic("sort_store_to_store")["reference"]
    cfg = {"records": 1 << 20, "key_bytes": 10, "payload_bytes": 90}
    data = gensort.generate(seed, cfg)
    n = data["n"]
    ctl = valsort.check(valsort.control(data, spec, nparts), data, spec,
                        nparts)
    assert ctl["rows_out_of_order"] > 0
    assert not _within(ctl, spec["limits"])
    hi, lo = valsort.key_lanes(data["keys"])
    order = np.lexsort((lo, hi))
    good = {"columns": {
        "key": (data["keys"][order], np.full(n, 10, np.int32)),
        "payload": (data["payload"][order], np.full(n, 90, np.int32))},
        "counts": [n // nparts] * nparts}
    assert _within(valsort.check(good, data, spec, nparts), spec["limits"])


def test_valsort_sees_each_broken_guarantee():
    spec = _traffic("sort_store_to_store")["reference"]
    data = gensort.generate(5, {"records": 4096, "key_bytes": 10,
                                "payload_bytes": 90})
    n = data["n"]
    hi, lo = valsort.key_lanes(data["keys"])
    order = np.lexsort((lo, hi))

    def answer(keys, payload, counts=(n,)):
        return {"columns": {"key": (keys, np.full(len(keys), 10, np.int32)),
                            "payload": (payload, np.full(len(keys), 90,
                                                         np.int32))},
                "counts": list(counts)}

    k, p = data["keys"][order], data["payload"][order]
    assert _within(valsort.check(answer(k, p), data, spec, 1),
                   spec["limits"])
    # a payload away from its key
    p2 = p.copy()
    p2[[0, 1]] = p2[[1, 0]]
    assert valsort.check(answer(k, p2), data, spec, 1)["rows_misplaced"] == 2
    # a row lost, another twice
    k3, p3 = k.copy(), p.copy()
    k3[10], p3[10] = k3[11], p3[11]
    r = valsort.check(answer(k3, p3), data, spec, 1)
    assert r["rows_not_input"] == 2
    # half of the rows
    assert valsort.check(answer(k[:n // 2], p[:n // 2], (n // 2,)), data,
                         spec, 1)["rows_missing"] == n // 2
    # the input, unsorted
    assert valsort.check(answer(data["keys"], data["payload"]), data, spec,
                         1)["rows_out_of_order"] > n // 4
    # a partition too few
    assert valsort.check(answer(k, p), data, spec, 4)["partitions_wrong"] == 1


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 77])
def test_relational_control_fails_and_reference_passes(seed):
    spec = _traffic("tpch_q1_collect")["reference"]
    cfg = {"rows": 65536, "scale_factor": 2}
    data = tpch_lineitem.generate(seed, cfg)
    ref = relational.check(
        {"collected": relational.as_collected(
            spec, relational.run(spec, data["tables"]))}, data, spec, 1)
    assert _within(ref, spec["limits"])
    f32 = relational.check(
        {"collected": relational.as_collected(
            spec, relational.run(spec, data["tables"],
                                 precision="float32"))}, data, spec, 1)
    assert _within(f32, spec["limits"])
    ctl = relational.check(relational.control(data, spec, 1), data, spec, 1)
    assert ctl["agg_max_rel_err"] > 3 * spec["limits"]["agg_max_rel_err"]
    assert not _within(ctl, spec["limits"])


def test_relational_sees_a_wrong_group_count_and_order():
    spec = _traffic("tpch_q1_collect")["reference"]
    data = tpch_lineitem.generate(9, {"rows": 8192, "scale_factor": 2})
    good = relational.as_collected(spec, relational.run(spec,
                                                        data["tables"]))
    bad = {k: (list(v) if isinstance(v, list) else v.copy())
           for k, v in good.items()}
    bad["count_order"][0] += 1
    assert relational.check({"collected": bad}, data, spec,
                            1)["counts_wrong"] == 1
    rev = {k: v[::-1] for k, v in good.items()}
    assert relational.check({"collected": rev}, data, spec,
                            1)["rows_out_of_order"] == 1
    short = {k: v[1:] for k, v in good.items()}
    assert relational.check({"collected": short}, data, spec,
                            1)["groups_wrong"] == 1
