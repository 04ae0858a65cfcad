"""The reduction from a device profile to seconds by kernel scope
(``perfbench/kernel_scopes.py``), its six readers, and the compile cache
key that keeps a profile's op names the program's own (ISSUE 38).

A hand-made trace holds a ``conditional`` around a fusion and a sort, a
kernel scope inside a kernel scope, a phase scope around both, an op of a
program that is not a stage's, an unscoped op and an op past the window;
the seconds each scope is owed are worked out by hand below."""

import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import kernel_scopes as K
from perfbench import trace_reduce
from perfbench.spans import Spans

NS = 1e-9
PACK, UNPACK = "exchange_pack", "exchange_unpack"

# name, start, dur, scopes (outermost first) on /device:TPU:0's XLA Ops
OPS = [
    ("cond.1", 100, 400, []),                                   # self 100
    ("fusion.2", 150, 200, [PACK, "index_sort", "row_gather"]),  # 200
    ("sort.3", 360, 100, [PACK, "index_sort"]),                 # 100
    ("while.4", 500, 300, ["hash_join"]),                       # 100
    ("fusion.5", 520, 100, ["hash_join", "search"]),            # 100
    ("fusion.6", 620, 100, ["hash_join", "row_gather"]),        # 100
    ("copy.7", 1150, 100, []),                  # not a stage's program
    ("fusion.8", 1400, 100, [UNPACK]),          # a phase, no kernel
    ("add.9", 1500, 100, []),                                   # 100
    ("fusion.10", 1950, 100, ["lookup_join", "prefix_sum"]),    # 100
    ("fusion.11", 2050, 30, ["index_sort"]),    # starts past the window
]
MODULES = [("jit_stage_a(1)", 100, 800), ("jit_other(2)", 1100, 200),
           ("jit_stage_b(3)", 1400, 700)]
WANT = {"row_gather": 300, "index_sort": 100, "search": 100,
        "hash_join": 100, "prefix_sum": 100, PACK: 300, UNPACK: 100,
        K.UNSCOPED: 200, K.ALL: 1000}


def _trace(with_scopes=True):
    ops = {"name": "XLA Ops",
           "events": [[n, float(s), float(d)] for n, s, d, _ in OPS]}
    if with_scopes:
        ops["scopes"] = [list(sc) for *_, sc in OPS]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{"name": "spans", "events": [
            ["perfbench:query", 0.0, 1000.0],
            ["perfbench:query", 1000.0, 1000.0]]}]},
        {"name": "/device:TPU:0", "lines": [
            ops, {"name": "XLA Modules", "events": [
                [n, float(s), float(d)] for n, s, d in MODULES]}]}]}


def test_self_seconds_by_scope_as_worked_by_hand():
    got = K.reduce(_trace(), "/device:TPU:0")
    assert set(got) == set(WANT), got
    for k, v in WANT.items():
        assert got[k] == pytest.approx(v * NS), k


def test_the_older_reduction_reads_the_same_without_scopes():
    assert trace_reduce.reduce(_trace(True), chips=1) \
        == trace_reduce.reduce(_trace(False), chips=1)


def test_a_trace_without_scopes_names_nothing():
    got = K.reduce(_trace(False), "/device:TPU:0")
    assert got == {}


@pytest.mark.parametrize("events,want", [
    ([["a", 0, 100], ["b", 10, 20], ["c", 40, 30], ["d", 45, 5]],
     [50, 20, 25, 5]),
    ([["a", 0, 10], ["b", 10, 10], ["c", 20, 0]], [10, 10, 0]),
    ([["a", 0, 50], ["b", 0, 50]], [0, 50]),
])
def test_self_time_is_less_the_union_of_what_is_nested(events, want):
    assert K.self_ns(events) == pytest.approx(want)


@pytest.mark.parametrize("op_name,want", [
    ("jit(stage_x)/index_sort/row_gather/jit(_take)/gather:",
     ["index_sort", "row_gather"]),
    ("jit(stage_x)/while/body/search/jit(searchsorted)/scatter:",
     ["search"]),
    ("jit(stage_x)/cond/branch_1_fun/compact", []),    # the op itself
    ("jit(sort)/jit(_take)/gather", []),
    ("", []), (None, []),
])
def test_a_scope_is_a_whole_path_component(op_name, want):
    assert K.scopes_of(op_name) == want


# -- the .xplane.pb as the TPU writes it: op names on the event metadata ----

def _write_xplane(path, base_ns=5_000):
    """Lines' timestamps in ns, events' offsets in ps; the op name in the
    metadata stat ``tf_op``, one by ``str_value``, one by reference."""
    X = K._xspace_class()
    sp = X()
    host = sp.planes.add(name="/host:CPU")
    host.stat_metadata.add(key=1).value.name = "profile_start_time"
    host.stats.add(metadata_id=1, uint64_value=1_000)
    dev = sp.planes.add(name="/device:TPU:0")
    dev.stat_metadata.add(key=7).value.name = "tf_op"
    dev.stat_metadata.add(key=8).value.name = \
        "jit(stage_a)/exchange_pack/index_sort/sort:"
    names = {}
    for i, (n, _s, _d, sc) in enumerate(OPS):
        md = dev.event_metadata.add(key=10 + i).value
        md.id, md.name = 10 + i, "%" + n + " = u32[8]{0} x(), kind=kLoop"
        op = "/".join(["jit(stage_a)"] + sc + ["op"]) + ":"
        if n == "sort.3":
            md.stats.add(metadata_id=7, ref_value=8)
        elif sc or n != "cond.1":
            md.stats.add(metadata_id=7, str_value=op)
        names[n] = 10 + i
    for i, (n, _s, _d) in enumerate(MODULES):
        dev.event_metadata.add(key=100 + i).value.name = n
    ln = dev.lines.add(name="XLA Ops", timestamp_ns=base_ns)
    for n, s, d, _ in OPS:
        ln.events.add(metadata_id=names[n], offset_ps=int(s * 1e3),
                      duration_ps=int(d * 1e3))
    ln = dev.lines.add(name="XLA Modules", timestamp_ns=base_ns)
    for i, (n, s, d) in enumerate(MODULES):
        ln.events.add(metadata_id=100 + i, offset_ps=int(s * 1e3),
                      duration_ps=int(d * 1e3))
    dev.lines.add(name="Steps", timestamp_ns=0).events.add(metadata_id=10)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "wb") as f:
        f.write(sp.SerializeToString())


def test_load_reads_the_op_names_off_the_event_metadata(tmp_path):
    path = str(tmp_path / "t.xplane.pb")
    _write_xplane(path)
    got = K.load(path)
    assert got["profile_start_unix_ns"] == 1_000
    (dev,) = got["planes"]
    by = {ln["name"]: ln for ln in dev["lines"]}
    assert set(by) == {"XLA Ops", "XLA Modules"}
    ops = by["XLA Ops"]
    assert [e[0] for e in ops["events"]] == [
        n + " x kLoop" for n, *_ in OPS]
    assert [e[1] for e in ops["events"]] == [5_000 + s for _, s, _, _ in OPS]
    assert [e[2] for e in ops["events"]] == [d for _, _, d, _ in OPS]
    assert ops["scopes"] == [sc for *_, sc in OPS]
    assert "scopes" not in by["XLA Modules"]
    left = {}
    K.reduce(trace_reduce.add_host_spans(got, [(0, "query", 6e-6, 8e-6)]),
             "/device:TPU:0", left)
    # cond.1's metadata has no op name; copy.7 is not a stage's
    assert left == pytest.approx({"cond.1 x kLoop": 100 * NS,
                                  "add.9 x kLoop jit(stage_a)/op": 100 * NS})


def _run(tmp_path, n_queries=2, real=True):
    """A run as ``perfbench/run.py`` hands it to a reader, its profile
    where the harness writes it: ``<workdir>/trace`` beside the tables."""
    _write_xplane(str(tmp_path / "trace" / "plugins" / "profile" / "1"
                      / "h.xplane.pb"), base_ns=0)
    spans = Spans(True)
    # spans on the wall clock, in s; the profile started at 1,000 ns
    for q, t0 in ((0, 1e-6), (1, 2e-6), (2, 3e-6)):
        spans.rows.append((q, "query", t0, t0 + 1e-6))
    return {"state": {"tables": {"t": str(tmp_path / "t")}},
            "spans": spans, "trace": {
                "real_device": real, "n_queries": n_queries,
                "busiest": "/device:TPU:0"}}


def test_a_run_is_read_from_its_own_profile(tmp_path):
    run = _run(tmp_path)
    got = K.for_run(run)
    for k, v in WANT.items():
        assert got[k] == pytest.approx(v * NS), k
    assert K.for_run(run) is got           # read once a run


READERS = {"kernel_sort_ms": "index_sort", "kernel_gather_ms": "row_gather",
           "kernel_search_ms": "search", "pack_ms": PACK,
           "unpack_ms": UNPACK}


def _reader(name):
    return importlib.import_module(f"perfbench.layers.{name}")


@pytest.mark.parametrize("name", sorted(READERS) + ["kernel_named_share"])
def test_a_reader_finds_nothing_to_read(tmp_path, name):
    r = _reader(name)
    assert r.read({"trace": None}) is None
    assert r.read(_run(tmp_path, real=False)) is None
    no_profile = _run(tmp_path)
    no_profile["state"] = {"tables": {"t": str(tmp_path / "elsewhere" / "t")}}
    assert r.read(no_profile) is None
    bare = _run(tmp_path)
    bare["scope_self_s"] = {K.ALL: 1e-3, K.UNSCOPED: 1e-3}
    assert r.read(bare) is None
    if name != "kernel_named_share":
        other = _run(tmp_path)
        other["scope_self_s"] = {K.ALL: 1e-3, "compact": 1e-3}
        assert r.read(other) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_reader_reads_ms_a_query(tmp_path, name):
    assert _reader(name).read(_run(tmp_path)) == pytest.approx(
        WANT[READERS[name]] * NS / 2 * 1e3)


def test_the_named_share(tmp_path):
    assert _reader("kernel_named_share").read(_run(tmp_path)) \
        == pytest.approx(1 - 200 / 1000)


# -- the compile cache's key holds the scopes --------------------------------

def _key(name):
    from jax._src import cache_key, compiler

    def f(x):
        with jax.named_scope(name):
            return jnp.sort(x) + 1
    module = jax.jit(f).lower(jnp.zeros(8)).compiler_ir("stablehlo")
    dev = jax.devices()[0]
    return cache_key.get(module, np.asarray([dev]),
                         compiler.get_compile_options(1, 1), dev.client)


def test_a_named_scope_moves_the_cache_key():
    """By default JAX hashes a module stripped of its op names, so a
    program that gained scopes would load its predecessor's executable,
    and a profile would show the predecessor's names."""
    from dryad_tpu.utils.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    assert _key("index_sort") != _key("row_gather")
    flag = "jax_compilation_cache_include_metadata_in_key"
    jax.config.update(flag, False)
    try:
        assert _key("index_sort") == _key("row_gather")
    finally:
        jax.config.update(flag, True)
