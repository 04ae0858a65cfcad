"""The Star Schema Benchmark's Q2.1 and Q3.1 through ``sql/`` as
published (``perfbench/queries/ssb_q*.sql``): a fact table joined to its
dimensions on the keys their stores declare, filters on the dimensions
only, a group-by on dimension attributes, ``SUM`` of an integer measure.
The benchmark's own generator at the rehearsal's size, the tables written
to stores as the benchmark writes them (``to_store(unique=)``), the answer
compared with the plain numpy reference (``perfbench/ref/``) **exactly**:
the same groups, every sum equal to the integer, in the order asked."""

import json
import os
import sys

import numpy as np
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from dryad_tpu import make_mesh, sql  # noqa: E402
from dryad_tpu.api.dataset import Context  # noqa: E402
from perfbench.kinds import ssb  # noqa: E402
from perfbench.ref import relational_join, star_join  # noqa: E402

CFG = {"rows": 12000000, "rehearse": {"rows": 8192}}
CELLS = {"q2.1": "ssb_q2.1_collect", "q3.1": "ssb_q3.1_collect"}


def _traffic(name):
    with open(os.path.join(_REPO, "perfbench", "traffic",
                           name + ".json")) as f:
        return json.load(f)


def _query(traffic):
    with open(os.path.join(_REPO, "perfbench", traffic["query_file"])) as f:
        return f.read()


@pytest.fixture(scope="module")
def stored(tmp_path_factory):
    """The five tables of one seed in stores, on one partition as the
    cell has them, and the catalog over them."""
    import jax
    data = ssb.generate(2**31 + 36, CFG, rehearse=True)
    events = []
    ctx = Context(mesh=make_mesh(jax.devices()[:1]),
                  event_log=events.append)
    state = ssb.ingest(ctx, data, CFG, str(tmp_path_factory.mktemp("ssb")))
    cat = sql.Catalog()
    for name, path in state["tables"].items():
        cat.register_store(name, path)
    return data, ctx, cat, state, events


def test_the_dimensions_carry_their_keys(stored):
    _, _, cat, state, _ = stored
    assert {t: cat.get(t).unique for t in cat.names()} == {
        "date": ("d_datekey",), "part": ("p_partkey",),
        "supplier": ("s_suppkey",), "customer": ("c_custkey",),
        "lineorder": None}
    assert state["rows"] == sum(ssb.sizes(CFG, True).values())


@pytest.mark.parametrize("cell", list(CELLS))
def test_as_published_equals_the_reference_exactly(stored, cell):
    data, ctx, cat, _, events = stored
    traffic = _traffic(CELLS[cell])
    spec = traffic["reference"]
    del events[:]
    got = sql.query(ctx, cat, _query(traffic)).collect()
    compared = star_join.check({"collected": got}, data, spec, 1)
    assert compared == {k: 0 for k in spec["limits"]}
    ref = relational_join.reference(data, spec)
    assert len(ref["keys"]) > 20
    name = next(iter(spec["aggregates"]))
    assert got[name].dtype == np.int64
    assert [int(x) for x in got[name]] == \
        [int(x) for x in ref["columns"][name]]
    # three joins, each to a dimension on its key: the lookup kernel
    done = {e["stage"]: e for e in events
            if e.get("event") == "stage_done" and not e["overflow"]}
    assert [e["join_kernel"] for e in done.values()
            if "join_kernel" in e] == ["lookup"] * 3
    assert sum(e.get("int64_sums", 0) for e in done.values()) == 1


@pytest.mark.parametrize("cell", list(CELLS))
def test_eight_partitions_answer_the_same(devices8, stored, cell):
    """The same stores read by a mesh of eight: hash exchanges under the
    joins, partial 64-bit sums merged by the wide sum, a range exchange
    for ORDER BY (on the 64-bit sum, descending, in Q3.1)."""
    data, _, cat, _, _ = stored
    traffic = _traffic(CELLS[cell])
    got = sql.query(Context(), cat, _query(traffic)).collect()
    assert star_join.check({"collected": got}, data, traffic["reference"],
                           8) == {k: 0 for k in
                                  traffic["reference"]["limits"]}


def test_q3_1_sums_pass_32_bits_at_the_cells_size():
    """What the cell is for: at 12,000,000 fact rows a Q3.1 group holds
    about 2,900 rows of mean revenue 3.6 M cents — every sum is near
    1e10, five times 2**31 — while a Q2.1 group (about 365 rows) stays
    under it.  Read off the generator's own numbers at a size a test can
    hold: the mean revenue and the rows a group."""
    data = ssb.generate(7, {"rows": 200000, "rehearse": CFG["rehearse"]})
    lo = data["tables"]["lineorder"]
    mean = float(lo["lo_revenue"].mean())
    assert 3.2e6 < mean < 3.8e6
    full = 12000000
    q31_rows = full / 5 / 5 * (6 / 7) / 150       # region x region x years
    q21_rows = full / 25 / 5 / (7 * 40)           # category x region
    assert q31_rows * mean > 4 * 2**31
    assert q21_rows * mean < 2**31
