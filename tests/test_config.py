"""JobConfig knob-surface tests (DryadLinqContext.cs:728-1053 parity):
every knob must demonstrably change subsystem behavior, not just exist."""

import numpy as np
import pytest

from dryad_tpu import Context
from dryad_tpu.exec.executor import CapacityError
from dryad_tpu.utils.config import JobConfig


def test_config_validation():
    with pytest.raises(ValueError, match="max_capacity_retries"):
        JobConfig(max_capacity_retries=-1)
    with pytest.raises(ValueError, match="spill_compression"):
        JobConfig(spill_compression="zstd")
    with pytest.raises(ValueError, match="duplication_budget"):
        JobConfig(speculation_duplication_budget=1.5)
    assert JobConfig().replace(failure_budget=2).failure_budget == 2


def test_zero_retries_fails_on_first_overflow():
    ctx = Context(config=JobConfig(max_capacity_retries=0))
    rng = np.random.default_rng(0)
    n = 30_000
    k = np.where(rng.random(n) < 0.9, 0,
                 rng.integers(1, 100, n)).astype(np.int32)
    with pytest.raises(CapacityError, match="0 capacity retries"):
        ctx.from_columns({"k": k}).hash_partition(["k"]).collect()


def test_small_range_samples_still_sort_correctly():
    ctx = Context(config=JobConfig(range_samples_per_partition=16))
    v = np.random.default_rng(1).integers(0, 10**6, 5000).astype(np.int32)
    out = ctx.from_columns({"v": v}).order_by([("v", False)]).collect()
    np.testing.assert_array_equal(np.asarray(out["v"]), np.sort(v))


def test_failure_budget_zero():
    from dryad_tpu.exec.recovery import FailureBudgetExceeded, Run
    from dryad_tpu.plan.planner import plan_query
    ctx = Context(config=JobConfig(failure_budget=0))
    ds = ctx.from_columns({"v": np.arange(100, dtype=np.int32)}) \
        .group_by(["v"], {"n": ("count", None)})
    graph = plan_query(ds.node, ctx.nparts, config=ctx.config)
    run = Run(ctx.executor, graph)
    run.output()
    with pytest.raises(FailureBudgetExceeded):
        run.invalidate(graph.out_stage)


def test_auto_broadcast_join_threshold():
    cfg = JobConfig(broadcast_join_threshold=0.5)
    ctx = Context(config=cfg)
    big = ctx.from_columns({"k": np.arange(10_000, dtype=np.int32) % 50,
                            "v": np.arange(10_000, dtype=np.int32)})
    tiny = ctx.from_columns({"k": np.arange(50, dtype=np.int32),
                             "w": np.arange(50, dtype=np.int32) * 2})
    joined = big.join(tiny, ["k"], ["k"])
    assert "broadcast" in joined.explain()     # rewrite fired
    out = joined.collect()
    assert len(out["k"]) == 10_000
    assert (np.asarray(out["w"]) == np.asarray(out["k"]) * 2).all()
    # without the knob the same join hash-exchanges both sides
    ctx2 = Context()
    joined2 = ctx2.from_columns(
        {"k": np.arange(10_000, dtype=np.int32) % 50,
         "v": np.arange(10_000, dtype=np.int32)}).join(
        ctx2.from_columns({"k": np.arange(50, dtype=np.int32),
                           "w": np.arange(50, dtype=np.int32) * 2}),
        ["k"], ["k"])
    assert "broadcast" not in joined2.explain()


def test_join_expansion_default_avoids_retry():
    events, events2 = [], []
    k = np.arange(2000, dtype=np.int32) % 500
    rk = np.repeat(np.arange(500, dtype=np.int32), 4)   # 4x fan-out
    # generous source capacity so the exchange itself never overflows and
    # only the join fan-out is at play
    # default expansion 1.0: output 16x pairs per key -> overflow retry
    ctx = Context(event_log=events.append)
    ctx.from_columns({"k": k}, capacity=600).join(
        ctx.from_columns({"k": rk, "w": rk}, capacity=600),
        ["k"], ["k"]).collect()
    assert any(e.get("overflow") for e in events
               if e.get("event") == "stage_done")
    # config join_expansion=4: right-sized up front, no retry
    ctx2 = Context(event_log=events2.append,
                   config=JobConfig(join_expansion=4.0))
    ctx2.from_columns({"k": k}, capacity=600).join(
        ctx2.from_columns({"k": rk, "w": rk}, capacity=600),
        ["k"], ["k"]).collect()
    assert not any(e.get("overflow") for e in events2
                   if e.get("event") == "stage_done")


def test_text_defaults_from_config(tmp_path):
    p = str(tmp_path / "t.txt")
    with open(p, "w") as f:
        f.write("abcdefghij\nklm\n")
    ctx = Context(config=JobConfig(text_max_line_len=4))
    out = ctx.read_text(p).collect()
    assert out["line"] == [b"abcd", b"klm"]   # truncation knob applied


def test_profile_dir_writes_device_trace(tmp_path):
    """JobConfig.profile_dir wraps executor runs in a jax.profiler trace
    (the Artemis device-timeline role, SURVEY.md §5) — real xplane/trace
    artifacts must land under the directory."""
    import glob

    import numpy as np
    d = str(tmp_path / "prof")
    ctx = Context(config=JobConfig(profile_dir=d))
    out = ctx.from_columns({"k": np.arange(500, dtype=np.int32) % 5,
                            "v": np.arange(500, dtype=np.int32)}).group_by(
        ["k"], {"s": ("sum", "v")}).collect()
    assert len(out["k"]) == 5
    hits = (glob.glob(d + "/**/*.xplane.pb", recursive=True)
            + glob.glob(d + "/**/*.trace.json.gz", recursive=True))
    assert hits, "no profiler artifacts written"


def test_cluster_backend_factory_registry():
    """ICluster/IScheduler factory seam (Interfaces.cs:324,491,545): the
    built-in backend registers as "local"; new deployment targets plug in
    by name without touching the core."""
    import pytest

    from dryad_tpu.runtime import (ClusterBackend, LocalCluster,
                                   cluster_backends, make_cluster,
                                   register_cluster)
    from dryad_tpu.runtime.interfaces import _FACTORIES

    assert "local" in cluster_backends()
    assert _FACTORIES["local"] is LocalCluster
    assert issubclass(LocalCluster, ClusterBackend)

    class Dummy(ClusterBackend):
        n_processes = 1
        event_log = None

        def __init__(self, tag="x"):
            self.tag = tag

        @property
        def nparts(self):
            return 1

        def alive(self):
            return True

        def restart(self):
            pass

        def shutdown(self):
            pass

        def next_job_id(self):
            return 1

        def execute(self, plan_json, source_specs, **kw):
            return {}

        @property
        def sockets(self):
            return {}

        def worker_procs(self):
            return {}

        def recv_frames(self, pid, job):
            return [], True

        def retire_worker(self, pid):
            pass

        def log_tails(self):
            return ""

    register_cluster("dummy", Dummy)
    try:
        cl = make_cluster("dummy", tag="hello")
        assert isinstance(cl, Dummy) and cl.tag == "hello"
        with pytest.raises(KeyError, match="no cluster backend"):
            make_cluster("nope")
    finally:
        _FACTORIES.pop("dummy", None)


def test_compile_cache_env_dir_is_honoured(tmp_path, monkeypatch):
    """Where JAX_COMPILATION_CACHE_DIR is set the environment owns the
    directory: enable_persistent_cache reports it and sets NO directory
    in code, whatever the config says (a path or None)."""
    import jax

    from dryad_tpu.utils import compile_cache as cc

    cc.enable_persistent_cache(None)
    before = jax.config.jax_compilation_cache_dir
    env_dir = str(tmp_path / "from-env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        assert cc.enable_persistent_cache(str(tmp_path / "cfg")) == env_dir
        assert cc.enable_persistent_cache(None) == env_dir
        assert cc.enable_persistent_cache() == env_dir
        assert jax.config.jax_compilation_cache_dir == before
        assert not (tmp_path / "cfg").exists()
    finally:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        cc.enable_persistent_cache(None)


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    """Without the environment variable the cache lives at ONE fixed
    path inside the checkout (<repo>/.jax_cache — no per-machine or
    per-platform subdirectory), the same on every call; None DISABLES
    for the process (the jax config is process-global)."""
    import os

    import jax

    from dryad_tpu.utils import compile_cache as cc
    from dryad_tpu.utils.config import JobConfig

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert JobConfig().compilation_cache_dir == want
    try:
        assert cc.enable_persistent_cache() == want
        assert cc.enable_persistent_cache(
            JobConfig().compilation_cache_dir) == want
        assert os.path.isdir(want)
        assert jax.config.jax_compilation_cache_dir == want
        assert cc.enable_persistent_cache(None) is None
        assert jax.config.jax_compilation_cache_dir is None
    finally:
        cc.enable_persistent_cache()


