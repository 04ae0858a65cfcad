"""Streamed OOC jobs over the real multi-process worker gang (VERDICT r2
item 2): every worker streams its own store-partition subset; the gang
advances through lockstep chunk waves, each wave one sharded exchange over
the (dcn, dp) mesh with host-side bucket spill between waves; output
partitions are written in parallel (one writer per worker).  The data is
many times larger than any single wave's device capacity."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
import cluster_fns  # noqa: E402

from dryad_tpu.api.dataset import Context  # noqa: E402
from dryad_tpu.runtime import LocalCluster  # noqa: E402
from dryad_tpu.utils.config import JobConfig  # noqa: E402

CHUNK = 256
N = 6000  # ~23x the per-wave device chunk capacity


@pytest.fixture(scope="module")
def cluster():
    old = os.environ.get("PYTHONPATH")
    os.environ["PYTHONPATH"] = (os.path.dirname(__file__) + os.pathsep +
                                (old or ""))
    cl = LocalCluster(n_processes=2, devices_per_process=2)
    yield cl
    cl.shutdown()
    if old is None:
        os.environ.pop("PYTHONPATH", None)
    else:
        os.environ["PYTHONPATH"] = old


@pytest.fixture(scope="module")
def data():
    rng = np.random.RandomState(17)
    return {"k": rng.randint(0, 25, N).astype(np.int32),
            "v": rng.randint(-10**6, 10**6, N).astype(np.int32)}


@pytest.fixture(scope="module")
def store(data, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("scluster") / "src")
    Context().from_columns(data).to_store(path)
    return path


def _ctx(cluster):
    return Context(cluster=cluster,
                   config=JobConfig(ooc_chunk_rows=CHUNK))


def test_cluster_stream_sort(cluster, store, data, tmp_path):
    """Streamed TeraSort over the gang: sampled global bounds, per-wave
    range exchange, per-worker recursive bucket sort, PARALLEL output
    (each worker writes its own partitions; process 0 merges meta)."""
    ctx = _ctx(cluster)
    out = str(tmp_path / "sorted")
    ctx.read_store_stream(store, chunk_rows=CHUNK).order_by(
        [("v", False)]).to_store(out)

    from dryad_tpu.io.store import store_meta
    meta = store_meta(out)
    assert meta["npartitions"] == 4  # one per device across the gang
    assert meta["partitioning"] == {"kind": "range", "keys": ["v"]}
    # the allgather carries one digest a partition: the block form, no
    # leaf digests, and the read below verifies by the partition's alone
    assert meta["checksum_algo"] == "fnv64-blocks"
    assert len(meta["checksums"]) == 4 and meta["leaf_checksums"] is None
    back = Context().from_store(out).collect()
    np.testing.assert_array_equal(np.asarray(back["v"]),
                                  np.sort(data["v"]))


def test_cluster_stream_group_collect(cluster, store, data):
    ctx = _ctx(cluster)
    out = (ctx.read_store_stream(store, chunk_rows=CHUNK)
           .group_by(["k"], {"s": ("sum", "v"), "n": ("count", None),
                             "m": ("mean", "v")}).collect())
    k, v = data["k"], data["v"]
    exp_s = {int(kk): int(v[k == kk].sum()) for kk in np.unique(k)}
    got_s = dict(zip((int(x) for x in out["k"]),
                     (int(x) for x in out["s"])))
    assert got_s == exp_s
    got_m = dict(zip((int(x) for x in out["k"]),
                     (float(x) for x in out["m"])))
    for kk in exp_s:
        assert abs(got_m[kk] - float(v[k == kk].mean())) < 0.5


def test_cluster_stream_ops_and_count(cluster, store, data):
    """Chunk-local shipped UDFs compose with the streamed terminals."""
    ctx = _ctx(cluster)
    s = (ctx.read_store_stream(store, chunk_rows=CHUNK)
         .select(cluster_fns.double_v)
         .where(cluster_fns.keep_positive))
    assert s.count() == int((data["v"] * 2 > 0).sum())
    out = s.group_by(["k"], {"s": ("sum", "v")}).collect()
    v2 = data["v"] * 2
    mask = v2 > 0
    exp = {int(kk): int(v2[mask][data["k"][mask] == kk].sum())
           for kk in np.unique(data["k"][mask])}
    got = dict(zip((int(x) for x in out["k"]),
                   (int(x) for x in out["s"])))
    assert got == exp


def test_cluster_stream_group_to_store(cluster, store, data, tmp_path):
    ctx = _ctx(cluster)
    out = str(tmp_path / "grouped")
    (ctx.read_store_stream(store, chunk_rows=CHUNK)
     .group_by(["k"], {"s": ("sum", "v")})).to_store(out)
    from dryad_tpu.io.store import store_meta
    meta = store_meta(out)
    assert meta["partitioning"] == {"kind": "hash", "keys": ["k"]}
    back = Context().from_store(out).collect()
    exp = {int(kk): int(data["v"][data["k"] == kk].sum())
           for kk in np.unique(data["k"])}
    got = dict(zip((int(x) for x in back["k"]),
                   (int(x) for x in back["s"])))
    assert got == exp


def test_cluster_stream_user_decomposable(store, data, monkeypatch):
    """User Decomposable aggregates ride the chunk waves: seed+merge in
    the wave program, merge compaction between waves, FinalReduce per
    bucket (IDecomposable.cs:34 over the cluster, streamed)."""
    # self-sufficient: workers must import cluster_fns regardless of
    # which tests ran before (no reliance on the module fixture's env)
    monkeypatch.setenv(
        "PYTHONPATH", os.path.dirname(__file__) + os.pathsep +
        os.environ.get("PYTHONPATH", ""))
    cl2 = LocalCluster(n_processes=2, devices_per_process=2,
                       fn_modules=("cluster_fns",))
    try:
        ctx = Context(cluster=cl2,
                      config=JobConfig(ooc_chunk_rows=CHUNK),
                      fn_table={"sum_dec": cluster_fns.SUM_DEC})
        out = (ctx.read_store_stream(store, chunk_rows=CHUNK)
               .group_by(["k"], {"s": cluster_fns.SUM_DEC}).collect())
        k, v = data["k"], data["v"]
        exp = {int(kk): int(v[k == kk].sum()) for kk in np.unique(k)}
        got = dict(zip((int(x) for x in out["k"]),
                       (int(x) for x in out["s"])))
        assert got == exp
    finally:
        cl2.shutdown()


def test_cluster_stream_wordcount(cluster, tmp_path):
    """Streamed WordCount over the gang (string keys ride the wave
    exchange)."""
    words = ["ant", "bee", "cat", "dog", "elk", "fox"]
    rng = np.random.RandomState(23)
    lines = [" ".join(words[i] for i in rng.randint(0, 6, 5))
             for _ in range(2000)]
    src = str(tmp_path / "lines")
    Context().from_columns({"line": [l.encode() for l in lines]},
                           str_max_len=64).to_store(src)
    ctx = _ctx(cluster)
    out = (ctx.read_store_stream(src, chunk_rows=CHUNK)
           .split_words("line", out_capacity=CHUNK * 8)
           .group_by(["line"], {"n": ("count", None)})).collect()
    import collections
    exp = collections.Counter(w for l in lines for w in l.split())
    got = {w.decode(): int(n) for w, n in zip(out["line"], out["n"])}
    assert got == dict(exp)


def test_cluster_stream_join(cluster, store, data):
    """Streamed JOIN over the gang: both legs hash-wave-exchanged to
    bucket streams, per-device streamed probe against the materialized
    bucket build side (VERDICT r3 item 3: joins over >HBM cluster
    data)."""
    ctx = _ctx(cluster)
    dim = {"k": np.arange(0, 25, dtype=np.int32),
           "w": (np.arange(25, dtype=np.int32) * 7).astype(np.int32)}
    got = (ctx.read_store_stream(store, chunk_rows=CHUNK)
           .join(ctx.from_columns(dim), ["k"], expansion=2.0).collect())
    exp_w = dict(zip(dim["k"].tolist(), dim["w"].tolist()))
    assert len(got["k"]) == N
    kk = np.asarray(got["k"])
    ww = np.asarray(got["w"])
    assert all(int(w) == exp_w[int(k)] for k, w in zip(kk, ww))


def test_cluster_stream_pagerank_do_while(cluster, tmp_path):
    """>HBM PageRank, 10 iterations, over the 2-process gang: edges
    stream from the store EVERY superstep (device working set stays
    O(chunk_rows)); ranks iterate as cluster-resident do_while state;
    matches the dense numpy oracle (VERDICT r3 item 3 'Done')."""
    from dryad_tpu.apps import pagerank

    n_nodes = cluster_fns.PR_NODES
    edges = pagerank.gen_graph(n_nodes, 600, seed=3)
    estore = str(tmp_path / "edges")
    Context().from_columns(edges).to_store(estore)

    ctx = _ctx(cluster)
    chunk = 128
    deg = (ctx.read_store_stream(estore, chunk_rows=chunk)
           .group_by(["src"], {"deg": ("count", None)}).cache())

    nodes = {"node": np.arange(n_nodes, dtype=np.int32),
             "rank": np.full(n_nodes, 1.0 / n_nodes, np.float32)}
    rank_cap = min(n_nodes, 4 * (-(-n_nodes // ctx.nparts)) + 8)
    ranks0 = ctx.from_columns(nodes).with_capacity(rank_cap)

    def body(ranks):
        contribs = (ctx.read_store_stream(estore, chunk_rows=chunk)
                    .join(deg, ["src"], ["src"], expansion=2.0)
                    .join(ranks, ["src"], ["node"], expansion=2.0)
                    .select(cluster_fns.pr_contrib)
                    .group_by(["node"], {"s": ("sum", "c")})
                    .select(cluster_fns.pr_damp))
        return contribs.with_capacity(rank_cap)

    out = ctx.do_while(ranks0, body, n_iters=10).collect()
    exp = pagerank.pagerank_numpy(edges, n_nodes, n_iters=10)
    got = np.zeros(n_nodes)
    for n_, r_ in zip(out["node"], out["rank"]):
        got[int(n_)] = float(r_)
    np.testing.assert_allclose(got, exp, rtol=2e-3, atol=1e-6)


def test_cluster_stream_worker_death_replays(store, data, tmp_path):
    """CHAOS: a worker killed MID-STREAMED-JOB (waves in flight) is
    detected, the gang restarts, and the driver replays the
    deterministic streamed query to completion (lineage replay over the
    >HBM path — SURVEY.md §3.5 applied to runtime/stream_plan.py)."""
    import signal
    import threading
    import time as _time

    cl = LocalCluster(n_processes=2, devices_per_process=2)
    try:
        ctx = Context(cluster=cl, config=JobConfig(ooc_chunk_rows=CHUNK))
        # kill worker 1 shortly after submission (mid-wave: the job has
        # N/CHUNK ~ 23 waves, each a collective)
        def assassin():
            _time.sleep(3.0)
            os.kill(cl._procs[1].pid, signal.SIGKILL)

        t = threading.Thread(target=assassin, daemon=True)
        t.start()
        t0 = _time.time()
        out = str(tmp_path / "sorted-chaos")
        (ctx.read_store_stream(store, chunk_rows=CHUNK)
         .order_by([("v", False)]).to_store(out))
        t.join()
        if _time.time() - t0 <= 3.0:
            pytest.skip("job finished before the kill landed — replay "
                        "path not exercised on this (fast) run")

        from dryad_tpu.io.store import store_meta
        meta = store_meta(out)
        assert sum(meta["counts"]) == N
        back = Context().from_store(out).collect()
        np.testing.assert_array_equal(np.asarray(back["v"]),
                                      np.sort(data["v"]))
    finally:
        cl.shutdown()


def test_cluster_from_stream_spool_and_whole_group(cluster, tmp_path):
    """from_stream on a CLUSTER Context (VERDICT r4 next-4): the driver
    spools the generator into a worker-reachable store (FromEnumerable
    parity) and the gang streams it through the planned surface —
    including the whole-group group_median, which materializes complete
    key buckets per device post-exchange."""
    rng = np.random.RandomState(9)
    n, chunk = 4000, CHUNK
    k = rng.randint(0, 20, n).astype(np.int32)
    v = rng.randint(0, 1000, n).astype(np.int32)

    def gen(i):
        lo, hi = i * chunk, min((i + 1) * chunk, n)
        return {"k": k[lo:hi], "v": v[lo:hi]}

    from dryad_tpu.exec.ooc import ChunkSource
    cfg = JobConfig(ooc_chunk_rows=chunk,
                    cluster_stream_spool_dir=str(tmp_path))
    ctx = Context(cluster=cluster, config=cfg)
    cs = ChunkSource.from_generator(gen, -(-n // chunk), chunk)
    got = ctx.from_stream(cs).group_median(["k"], "v", out="med").collect()
    med = dict(zip(got["k"].tolist(), got["med"].tolist()))

    ref = Context().from_columns({"k": k, "v": v}) \
        .group_median(["k"], "v", out="med").collect()
    want = dict(zip(ref["k"].tolist(), ref["med"].tolist()))
    assert med == want and len(med) == 20
