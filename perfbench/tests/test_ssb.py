"""The ``ssb`` kind and the two cells built on it: the generator's own
rules (row counts, every foreign key resolves, ``lo_revenue``'s formula,
the hierarchy ``p_mfgr`` < ``p_category`` < ``p_brand1``, a nation's
region), the controls of ``ref/star_join.py`` (sums wrapped to 32 bits:
every Q3.1 sum wrong, no Q2.1 sum wrong; a filter left out: wrong groups),
the two readers on recorded events, and the rehearsal of both cells."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from perfbench.kinds import ssb
from perfbench.layers import int64_sums, join_hash_stages
from perfbench.ref import relational_join, star_join

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
CFG = {"rows": 12000000, "rehearse": {"rows": 8192}}
CELLS = {"ssb_q2.1_sf2": "ssb_q2.1_collect",
         "ssb_q3.1_sf2": "ssb_q3.1_collect"}


def _spec(traffic):
    with open(os.path.join(HERE, "traffic", traffic + ".json")) as f:
        return json.load(f)["reference"]


def _strs(col):
    data, lens = col
    return [bytes(d[:n]) for d, n in zip(data, lens)]


@pytest.fixture(scope="module")
def data():
    return ssb.generate(2**31 + 3, dict(CFG, rows=120000))


# -- the generator --------------------------------------------------------------

def test_row_counts_and_columns(data):
    with open(os.path.join(HERE, "configs", "ssb_sf2_1chip.json")) as f:
        cfg = json.load(f)
    assert ssb.sizes(cfg) == cfg["tables"] == {
        "lineorder": 12000000, "part": 400000, "supplier": 4000,
        "customer": 60000, "date": 2556}
    assert sum(cfg["tables"].values()) == 12466556
    assert {t: len(c) for t, c in data["tables"].items()} == {
        "lineorder": 17, "part": 9, "supplier": 7, "customer": 8,
        "date": 17}
    for t, cols in data["tables"].items():
        # the file's schemas are the generator's columns, in its order
        assert list(cfg["schemas"][t]) == list(cols)
        for c, v in cols.items():
            n = len(v[1] if isinstance(v, tuple) else v)
            assert n == data["n"][t], (t, c)
            spec = cfg["schemas"][t][c]
            if isinstance(v, tuple):
                assert spec == {"kind": "str", "max_len": v[0].shape[1]}
                assert v[1].max() <= v[0].shape[1] and v[1].min() >= 1
            else:
                assert spec["dtype"] == str(v.dtype) == "int32"
    assert cfg["keys"] == {t: [k] for t, k in ssb.KEYS.items()}


def test_the_same_seed_gives_the_same_tables():
    a = ssb.generate(2**31 + 99, CFG, rehearse=True)
    b = ssb.generate(2**31 + 99, CFG, rehearse=True)
    c = ssb.generate(2**31 + 98, CFG, rehearse=True)
    lo = "lineorder"
    assert (a["tables"][lo]["lo_revenue"] ==
            b["tables"][lo]["lo_revenue"]).all()
    assert (a["tables"][lo]["lo_revenue"] !=
            c["tables"][lo]["lo_revenue"]).any()
    assert a["n"][lo] == 8192


def test_every_key_is_a_key_and_every_foreign_key_resolves(data):
    t = data["tables"]
    for table, key in ssb.KEYS.items():
        k = t[table][key]
        assert len(np.unique(k)) == len(k)
    lo = t["lineorder"]
    for fk, (table, key) in {"lo_orderdate": ("date", "d_datekey"),
                             "lo_partkey": ("part", "p_partkey"),
                             "lo_suppkey": ("supplier", "s_suppkey"),
                             "lo_custkey": ("customer",
                                            "c_custkey")}.items():
        assert np.isin(lo[fk], t[table][key]).all(), fk
        # uniform over the dimension: most of it is referenced
        assert len(np.unique(lo[fk])) > 0.9 * min(len(t[table][key]),
                                                  len(lo[fk]) / 4)
    # a line's order decides its customer and its date
    order = lo["lo_orderkey"]
    for per_order in ("lo_custkey", "lo_orderdate", "lo_ordtotalprice"):
        first = {}
        for o, v in zip(order[:5000].tolist(),
                        lo[per_order][:5000].tolist()):
            assert first.setdefault(o, v) == v


def test_the_measures(data):
    lo = data["tables"]["lineorder"]
    ext, disc = lo["lo_extendedprice"].astype(np.int64), lo["lo_discount"]
    assert (lo["lo_revenue"] == ext * (100 - disc) // 100).all()
    retail = 90000 + (lo["lo_partkey"] // 10) % 20001 \
        + 100 * (lo["lo_partkey"] % 1000)
    assert (ext == lo["lo_quantity"] * retail).all()
    assert lo["lo_quantity"].min() == 1 and lo["lo_quantity"].max() == 50
    assert disc.min() == 0 and disc.max() == 10
    assert lo["lo_tax"].min() == 0 and lo["lo_tax"].max() == 8
    assert lo["lo_linenumber"].min() == 1 and lo["lo_linenumber"].max() == 7
    assert (lo["lo_supplycost"] == retail * 6 // 10).all()
    assert (lo["lo_commitdate"] > lo["lo_orderdate"]).all()


def test_the_calendar(data):
    d = data["tables"]["date"]
    assert len(d["d_datekey"]) == 2556
    assert d["d_datekey"][0] == 19920101 and d["d_datekey"][-1] == 19981230
    assert (np.diff(d["d_datekey"]) > 0).all()
    assert (d["d_year"] == d["d_datekey"] // 10000).all()
    assert (d["d_yearmonthnum"] == d["d_datekey"] // 100).all()
    assert _strs(d["d_date"])[59] == b"February 29, 1992"
    assert _strs(d["d_dayofweek"])[0] == b"Wednesday"       # 1992-01-01
    assert _strs(d["d_yearmonth"])[-1] == b"Dec1998"
    assert d["d_daynuminyear"][365] == 366                    # a leap year
    assert d["d_lastdayinmonthfl"][30] == 1 and d["d_lastdayinmonthfl"][31] == 0
    assert set(np.unique(d["d_daynuminweek"])) == set(range(1, 8))


def test_the_hierarchies(data):
    p = data["tables"]["part"]
    mfgr, cat, brand = (_strs(p[c]) for c in ("p_mfgr", "p_category",
                                              "p_brand1"))
    assert set(mfgr) == {b"MFGR#%d" % i for i in range(1, 6)}
    assert len(set(cat)) == 25 and 950 < len(set(brand)) <= 1000
    for m, c, b in zip(mfgr, cat, brand):
        assert c[:6] == m and b[:7] == c
        assert 1 <= int(c[6:]) <= 5 and 1 <= int(b[7:]) <= 40
    region_of = dict(ssb.NATIONS)
    assert len(region_of) == 25 and len(set(region_of.values())) == 5
    for table, pre in (("supplier", "s_"), ("customer", "c_")):
        t = data["tables"][table]
        nation, region = _strs(t[pre + "nation"]), _strs(t[pre + "region"])
        city = _strs(t[pre + "city"])
        for nt, rg, ct in zip(nation, region, city):
            assert region_of[nt] == rg
            assert ct[:9] == nt[:9].ljust(9) and ct[9:].isdigit()
    assert len(set(_strs(data["tables"]["customer"]["c_nation"]))) == 25


# -- the reference and its controls ---------------------------------------------

def _within(compared, limits):
    return all(compared[k] <= limits[k] for k in compared)


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_reference_in_the_programs_place_is_correct(data, cell):
    spec = _spec(CELLS[cell])
    ref = relational_join.run(spec, data["tables"])
    assert len(ref["keys"]) > 20
    got = star_join.as_collected(spec, ref)
    compared = star_join.check({"collected": got}, data, spec, 1)
    assert compared == {k: 0 for k in spec["limits"]}
    assert spec.get("join_rows_found") == ref["join_rows"]
    # the filters are the dimensions': the first join keeps the fact whole
    if cell == "ssb_q2.1_sf2":
        assert ref["join_rows"][0] == data["n"]["lineorder"]
        assert 0.004 < ref["join_rows"][-1] / data["n"]["lineorder"] < 0.012
    else:
        assert 0.02 < ref["join_rows"][-1] / data["n"]["lineorder"] < 0.05


def test_sums_wrapped_to_32_bits_are_wrong_in_every_row_of_q3_1():
    """The control at the cells' own size (numpy alone: 12,000,000 fact
    rows, about half a minute): the reference's sums wrapped to 32 bits
    are wrong in all 150 rows of Q3.1 (every sum is near 1e10, five times
    2**31) and in no row of Q2.1, whose largest sum is 0.6 to 0.9 of
    2**31 — Q2.1 cannot guard what stopped it, Q3.1 does."""
    with open(os.path.join(HERE, "configs", "ssb_sf2_1chip.json")) as f:
        cfg = json.load(f)
    d = ssb.generate(2**31 + 5, cfg)
    pins = {}
    for cell, traffic in CELLS.items():
        spec = _spec(traffic)
        ref = relational_join.run(spec, d["tables"])
        name = next(iter(spec["aggregates"]))
        ctl = star_join.compare(spec, ref, star_join.as_collected(
            spec, ref, wrap32=True))
        assert ctl["groups_wrong"] == 0 and ctl["columns_missing"] == 0
        pins[cell] = (ctl["sums_wrong"], len(ref["keys"]),
                      float(ref["columns"][name].min()) / 2**31,
                      float(ref["columns"][name].max()) / 2**31)
    wrong, groups, least, largest = pins["ssb_q3.1_sf2"]
    assert wrong == groups == 150 and least > 4 and largest < 6
    wrong, groups, least, largest = pins["ssb_q2.1_sf2"]
    assert wrong == 0 and groups == 280 and 0.6 < largest < 0.9


@pytest.mark.parametrize("cell", list(CELLS))
def test_without_the_supplier_filter_the_answer_is_wrong(data, cell):
    """A guarantee broken, not a precision: Q3.1 groups by the supplier's
    nation, so nations of other regions appear (``groups_wrong``); Q2.1's
    keys name no supplier column, so the groups stay and every sum grows
    (``sums_wrong``)."""
    spec = _spec(CELLS[cell])
    ctl = star_join.check(star_join.control_dropped_filter(data, spec, 1),
                          data, spec, 1)
    assert ctl["groups_wrong" if cell == "ssb_q3.1_sf2"
               else "sums_wrong"] > 100
    assert not _within(ctl, spec["limits"])


def test_compare_sees_each_broken_guarantee(data):
    spec = _spec("ssb_q3.1_collect")
    good = star_join.as_collected(spec, relational_join.run(spec,
                                                            data["tables"]))

    def check(got):
        return star_join.check({"collected": got}, data, spec, 1)

    def copy():
        return {k: (list(v) if isinstance(v, list) else v.copy())
                for k, v in good.items()}
    bad = copy()
    bad["revenue"][3] += 1                     # off by one cent
    assert check(bad)["sums_wrong"] == 1
    bad = copy()
    bad["revenue"] = bad["revenue"].astype(np.float32).astype(np.int64)
    assert check(bad)["sums_wrong"] > 100      # a float32 is not the integer
    rev = {k: v[::-1] for k, v in good.items()}
    assert check(rev)["rows_out_of_order"] > 100
    short = {k: v[1:] for k, v in good.items()}
    r = check(short)
    assert r["groups_wrong"] == 1 and r["rows_returned_wrong"] == 1
    missing = {k: v for k, v in good.items() if k != "revenue"}
    assert check(missing)["columns_missing"] == 1
    # a string key in the order asked for (Q2.1's p_brand1)
    spec2 = _spec("ssb_q2.1_collect")
    good2 = star_join.as_collected(spec2, relational_join.run(
        spec2, data["tables"]))
    swapped = {k: (list(v) if isinstance(v, list) else v.copy())
               for k, v in good2.items()}
    for v in swapped.values():
        v[0], v[1] = v[1], v[0]
    assert star_join.check({"collected": swapped}, data, spec2,
                           1)["rows_out_of_order"] == 1


# -- the two readers, on recorded events ------------------------------------------

def _done(stage, label, overflow=False, **kw):
    return {"event": "stage_done", "stage": stage, "label": label,
            "overflow": overflow, **kw}


def _run(*queries):
    return {"queries": [{"i": i, "events": ev}
                        for i, ev in enumerate(queries)]}


def test_the_readers_on_recorded_events():
    star = [_done(0, "join", join_kernel="lookup"),
            _done(1, "join", join_kernel="lookup"),
            _done(2, "join", join_kernel="lookup"),
            _done(3, "output", int64_sums=1),
            {"event": "span", "name": "run"}]
    assert join_hash_stages.read(_run(star, star, star)) == 0.0
    assert int64_sums.read(_run(star, star, star)) == 1.0
    # Q3 today: two general joins, a float sum
    q3 = [_done(0, "join", join_kernel="hash"),
          _done(1, "join", join_kernel="hash"),
          _done(2, "output", int64_sums=0)]
    assert join_hash_stages.read(_run(q3, q3)) == 2.0
    assert int64_sums.read(_run(q3, q3)) == 0.0
    # the checked form holds the general kernel too; an attempt that
    # overflowed is replayed and counts once
    mixed = [_done(0, "join", overflow=True, join_kernel="lookup"),
             _done(0, "join", join_kernel="lookup"),
             _done(1, "join", join_kernel="checked"),
             _done(2, "groupby", int64_sums=2),
             _done(2, "groupby", int64_sums=2)]
    assert join_hash_stages.read(_run(mixed)) == 1.0
    assert int64_sums.read(_run(mixed)) == 2.0
    # the median is over the queries that have such events
    assert join_hash_stages.read(_run(star, q3, q3)) == 2.0


def test_the_readers_find_nothing_on_an_older_program():
    old = [_done(0, "join", right_unique=False, join_in_bytes=5),
           _done(1, "output", rows=[3])]
    assert join_hash_stages.read(_run(old, old)) is None
    assert int64_sums.read(_run(old, old)) is None
    assert join_hash_stages.read(_run()) is None
    assert int64_sums.read({"queries": [{"i": 0, "events": []}]}) is None


def test_benchmark_json_lists_the_cells_and_the_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    for cell, traffic in CELLS.items():
        assert cells[cell]["config"] == "ssb_sf2_1chip"
        assert cells[cell]["traffic"] == traffic and cells[cell]["chips"] == 1
    per = {m["name"]: m for m in bench["per_layer"]}
    for name in ("join_hash_stages", "int64_sums"):
        assert per[name]["workloads"] == list(CELLS)
        assert per[name]["layer"] == "kernels"
    for m in bench["per_layer"]:
        if "tpch_q3_sf2" in m.get("workloads", ()):
            assert m["workloads"][-2:] == list(CELLS), m["name"]


@pytest.mark.parametrize("cell", list(CELLS))
def test_rehearsal_of_the_cells_is_correct(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--rehearse", "--trace", "1", "--seconds", "1", "--seed",
         str(2**31 + 17)], capture_output=True, text=True, cwd=ROOT,
        timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["sums_wrong"] == {"value": 0, "limit": 0}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["join_hash_stages"] == 0.0 and m["int64_sums"] == 1.0
    assert m["stage_attempts"] == 4.0 and m["compiles_in_window"] == 0.0
    assert m["join_in_MB"] > 0
    assert line["run"]["rows"] == sum(ssb.sizes(CFG, True).values())
