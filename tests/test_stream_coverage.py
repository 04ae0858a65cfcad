"""Streamed-coverage closure (ISSUE 14 tentpole c): the operators that
used to raise typed StreamPlanErrors in streamed mode — global take,
zip, group_apply / group_median — are REAL lowerings now, oracle-parity
tested on both the single-process streamed path and the 2-process
LocalCluster streamed path (the cluster block env-skips on this jax
build's known gang-SPMD limit, like the rest of the cluster suite)."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import cluster_fns  # noqa: E402

from dryad_tpu import Context  # noqa: E402
from dryad_tpu.utils.config import JobConfig  # noqa: E402
from tests.utils import assert_same_rows  # noqa: E402

CHUNK = 256
N = 5000


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(11)
    return {"k": rng.randint(0, 25, N).astype(np.int32),
            "v": rng.randint(-10**6, 10**6, N).astype(np.int32)}


@pytest.fixture(scope="module")
def store(data, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("cov") / "src")
    Context().from_columns(data).to_store(path)
    return path


# ---------------------------------------------------------------------------
# single-process streamed path


def test_stream_top_n_take_after_sort(store, data):
    """order_by + global take over a single-process stream == the exact
    oracle top-n, in order (the top-k query shape)."""
    ctx = Context(config=JobConfig(ooc_chunk_rows=CHUNK))
    dbg = Context(local_debug=True)

    def q(d):
        return d.order_by([("v", True)]).take(17)

    got = q(ctx.read_store_stream(store, chunk_rows=CHUNK)).collect()
    exp = q(dbg.from_columns(data)).collect()
    assert_same_rows(got, exp, ordered=True)


def test_stream_single_process_parity_sweep(store, data):
    """One sweep pinning all three previously-gapped lowerings on the
    single-process streamed path against local_debug."""
    ctx = Context(config=JobConfig(ooc_chunk_rows=CHUNK))
    dbg = Context(local_debug=True)

    # global take (unsorted: prefix of the stream order)
    sds = ctx.read_store_stream(store, chunk_rows=CHUNK)
    assert sds.take(CHUNK * 3 + 7).count() == CHUNK * 3 + 7
    assert sds.take(N + 99).count() == N

    # zip: positional pairing of two derived streams
    a = sds.select(lambda c: {"x": c["v"]})
    b = sds.select(lambda c: {"y": c["v"] * 2})
    z = a.zip_with(b).collect()
    np.testing.assert_array_equal(np.asarray(z["y"]),
                                  np.asarray(z["x"]) * 2)
    assert len(z["x"]) == N

    # group_median + group_apply
    gm = sds.group_median(["k"], "v", out="med").collect()
    em = dbg.from_columns(data).group_median(["k"], "v",
                                             out="med").collect()
    assert_same_rows(gm, em)
    ga = sds.group_apply(["k"], cluster_fns.second_largest,
                         group_capacity=1024, max_groups=64,
                         out_rows=1, out_capacity=64).collect()
    ea = dbg.from_columns(data).group_apply(
        ["k"], cluster_fns.second_largest, group_capacity=1024,
        max_groups=64, out_rows=1, out_capacity=64).collect()
    assert_same_rows(ga, ea)


# ---------------------------------------------------------------------------
# 2-process LocalCluster streamed path (env-skip on the gang-SPMD limit)


@pytest.fixture(scope="module")
def cluster():
    from dryad_tpu.runtime import LocalCluster
    old = os.environ.get("PYTHONPATH")
    os.environ["PYTHONPATH"] = (os.path.dirname(__file__) + os.pathsep +
                                (old or ""))
    cl = LocalCluster(n_processes=2, devices_per_process=2)
    # this jax build cannot run gang-SPMD collectives on the CPU backend
    # ("Multiprocess computations aren't implemented") — the same
    # pre-existing environmental limit the rest of the cluster suite
    # hits; skip rather than re-report it, but let real failures raise
    try:
        probe = Context(cluster=cl)
        probe.from_columns({"x": np.arange(8, dtype=np.int32)}).count()
    except Exception as e:
        cl.shutdown()
        if old is None:
            os.environ.pop("PYTHONPATH", None)
        else:
            os.environ["PYTHONPATH"] = old
        if "Multiprocess computations" in str(e):
            pytest.skip("gang-SPMD unsupported by this jax build "
                        "(pre-existing environmental limit)")
        raise
    yield cl
    cl.shutdown()
    if old is None:
        os.environ.pop("PYTHONPATH", None)
    else:
        os.environ["PYTHONPATH"] = old


def _cctx(cluster):
    return Context(cluster=cluster,
                   config=JobConfig(ooc_chunk_rows=CHUNK))


def test_cluster_stream_global_take(cluster, store, data):
    """Global take over cluster streams (the retired DTA001): after a
    range-exchanged sort the device-major prefix IS the global top-n —
    exact oracle parity, in order; unsorted take returns exactly n rows
    drawn from the dataset."""
    ctx = _cctx(cluster)
    got = (ctx.read_store_stream(store, chunk_rows=CHUNK)
           .order_by([("v", True)]).take(23).collect())
    exp_v = np.sort(np.asarray(data["v"]))[::-1][:23]
    np.testing.assert_array_equal(np.asarray(got["v"]), exp_v)

    sds = ctx.read_store_stream(store, chunk_rows=CHUNK)
    t = sds.take(CHUNK + 13).collect()
    assert len(t["v"]) == CHUNK + 13
    allowed = set(zip(data["k"].tolist(), data["v"].tolist()))
    assert set(zip((int(x) for x in t["k"]),
                   (int(x) for x in t["v"]))) <= allowed
    assert sds.take(N + 50).count() == N


def test_cluster_stream_zip(cluster, store, data):
    """zip over cluster streams: both sides derive from the SAME store
    (identical partition->device layout), so per-device positional
    pairing equals global row pairing — every x pairs its own 2x."""
    ctx = _cctx(cluster)
    sds = ctx.read_store_stream(store, chunk_rows=CHUNK)
    # module-level functions: a plan that ships to workers may hold no
    # lambda (DTA014)
    a = sds.select(cluster_fns.v_as_x)
    b = sds.select(cluster_fns.v_doubled_as_y)
    z = a.zip_with(b).collect()
    assert len(z["x"]) == N
    np.testing.assert_array_equal(np.asarray(z["y"]),
                                  np.asarray(z["x"]) * 2)
    assert sorted(np.asarray(z["x"]).tolist()) \
        == sorted(data["v"].tolist())


def test_cluster_stream_group_median(cluster, store, data):
    ctx = _cctx(cluster)
    got = (ctx.read_store_stream(store, chunk_rows=CHUNK)
           .group_median(["k"], "v", out="med").collect())
    med = dict(zip((int(x) for x in got["k"]),
                   (int(x) for x in got["med"])))
    k, v = data["k"], data["v"]
    exp = {int(kk): int(np.sort(v[k == kk])[(np.sum(k == kk) - 1) // 2])
           for kk in np.unique(k)}
    assert med == exp


def test_cluster_stream_group_apply(cluster, store, data):
    ctx = _cctx(cluster)
    got = (ctx.read_store_stream(store, chunk_rows=CHUNK)
           .group_apply(["k"], cluster_fns.second_largest,
                        group_capacity=1024, max_groups=64,
                        out_rows=1, out_capacity=64).collect())
    sec = dict(zip((int(x) for x in got["k"]),
                   (int(x) for x in got["second"])))
    k, v = data["k"], data["v"]
    exp = {}
    for kk in np.unique(k):
        s = np.sort(v[k == kk])[::-1]
        exp[int(kk)] = int(s[1] if len(s) >= 2 else s[0])
    assert sec == exp
