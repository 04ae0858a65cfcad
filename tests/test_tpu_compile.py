"""Compile the main path's kernels and exchanges for a described TPU.

The TPU compiler is installed on CPU-only machines and compiles for a
chip that is described, not attached (``v5e:2x2``).  These tests hand it
the Pallas kernels, the sort / group kernels and the mesh exchanges at
the packed widths the program really produces — what interpret mode
cannot show: a block-DMA exchange kernel that passed every interpret
test was refused here for a slice not aligned to the (128) tiling.
Nothing runs and nothing here is a chip measurement.

The topology is described inside a module-scoped fixture (only the
worker that runs this file loads the TPU library), the TPU tier is
steered with monkeypatch, and the persistent compile cache is off around
the compiles (a described-device entry cannot be read back).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from dryad_tpu.data.columnar import Batch, StringColumn
from dryad_tpu.ops import kernels, pallas_kernels
from dryad_tpu.parallel import shuffle


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture
def tpu_tier(monkeypatch):
    """pallas_active() -> "compiled", as on a TPU backend."""
    monkeypatch.setattr(pallas_kernels, "_on_tpu", lambda: True)
    assert pallas_kernels.pallas_active() == "compiled"


def _one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _batch(sharding, parts, cap, **cols):
    """A Batch of shapes: ``parts`` is () for one shard or (P,) for the
    stacked form; a column is a dtype or ("str", max_len)."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(parts + shape, dt, sharding=sharding)
    return Batch({k: (StringColumn(s((cap, v[1]), jnp.uint8),
                                   s((cap,), jnp.int32))
                      if isinstance(v, tuple) else s((cap,), v))
                  for k, v in cols.items()}, s((), jnp.int32))


# TeraSort rows: 10-byte key + length + i32 payload = 5 packed u32 words
_TERASORT = {"key": ("str", 10), "payload": jnp.int32}


@pytest.mark.parametrize("n_buckets", [64, 512])
def test_hist_buckets_compiles(topo, tpu_tier, n_buckets):
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.int32, sharding=_one_chip(topo))
    c = _compile(lambda b: pallas_kernels.hist_buckets(b, n_buckets), x)
    assert c.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_prefix_sum_compiles(topo, tpu_tier, dtype):
    x = jax.ShapeDtypeStruct((1 << 20,), dtype, sharding=_one_chip(topo))
    c = _compile(pallas_kernels.prefix_sum, x)
    assert c.as_text().count("tpu_custom_call") == 1


def test_prefix_sum2_compiles(topo, tpu_tier):
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.float32,
                             sharding=_one_chip(topo))
    c = _compile(pallas_kernels.prefix_sum2, x)
    assert c.as_text().count("tpu_custom_call") == 1


def test_prefix_max_compiles(topo, tpu_tier):
    """The running max of hash_join's search phase, at the 15 M rows of
    Q3's merge of ``lineitem`` and ``orders``."""
    x = jax.ShapeDtypeStruct((15_000_000,), jnp.int32,
                             sharding=_one_chip(topo))
    c = _compile(pallas_kernels.prefix_max, x)
    assert c.as_text().count("tpu_custom_call") == 1


def test_sort_by_columns_compiles(topo, tpu_tier):
    b = _batch(_one_chip(topo), (), 4096, **_TERASORT)
    _compile(lambda x: kernels.sort_by_columns(x, [("key", False)]), b)


def test_sort_fused2_compiles(topo, tpu_tier):
    """The TPU-tier two-key-lane runtime fusion (gated on
    pallas_active(), so the CPU suite never lowers it)."""
    b = _batch(_one_chip(topo), (), 4096, a=jnp.int32, b=jnp.int32)
    _compile(lambda x: kernels.sort_by_columns(
        x, [("a", False), ("b", True)]), b)


def test_group_aggregate_compiles(topo, tpu_tier):
    """count + f32 sum + max: the boundary-carry path, whose sums ride
    the prefix_sum2 kernel."""
    b = _batch(_one_chip(topo), (), 4096, k=jnp.int32, v=jnp.float32,
               m=jnp.int32)
    c = _compile(lambda x: kernels.group_aggregate(
        x, ["k"], {"n": ("count", None), "s": ("sum", "v"),
                   "top": ("max", "m")}), b)
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("keys,cols", [
    (["c"], {"c": jnp.int32}),
    (["f", "g"], {"f": ("str", 1), "g": ("str", 1)})],
    ids=["one-hot", "boundary"])
def test_masked_group_aggregate_compiles(topo, tpu_tier, keys, cols):
    """A filter's mask into the group-by (``where=``): Q6's shape (one
    constant key) and Q1's (two 1-byte strings in one mixed-radix slot),
    each the one-hot matmul with the boundary path beside it as the sort
    fallback."""
    sh = _one_chip(topo)
    b = _batch(sh, (), 4096, v=jnp.float32, **cols)
    m = jax.ShapeDtypeStruct((4096,), jnp.bool_, sharding=sh)
    _compile(lambda x, w: kernels.group_aggregate(
        x, keys, {"n": ("count", None), "s": ("sum", "v"),
                  "a": ("mean", "v")}, where=w), b, m)


def test_hash_join_compiles(topo, tpu_tier):
    """The TPU-tier packed single-gather of the join probe
    (kernels._packed_gather, gated on pallas_active())."""
    sh = _one_chip(topo)
    _compile(lambda l, r: kernels.hash_join(l, r, ["k"], ["k"], 4096),
             _batch(sh, (), 4096, k=jnp.int32, a=jnp.int32, b=jnp.int32),
             _batch(sh, (), 1024, k=jnp.int32, c=jnp.int32))


@pytest.mark.parametrize("words", [4, 26])
def test_bounded_gather_compiles_at_the_cells_size(topo, words):
    """The sort fallback's gather branch at 12,000,000 rows (the SQL
    cells' capacity) bounded by a traced count: a ``while`` whose trip
    count is the count's, around one chunk's gather (ISSUE 37)."""
    sh = _one_chip(topo)
    cap = 12_000_000
    lane = jax.ShapeDtypeStruct((cap,), jnp.uint32, sharding=sh)
    text = _compile(
        lambda ls, o, n: kernels._gather_lanes(list(ls), o, n),
        (lane,) * words,
        jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=sh),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=sh)).as_text()
    assert f"[{kernels._GATHER_CHUNK},{words}]" in text


@pytest.mark.parametrize("kind", ["hash", "range"])
def test_exchange_compiles_on_four_chips(topo, tpu_tier, kind):
    """hash_exchange / range_exchange under shard_map on a Mesh of the
    four described devices, TeraSort's real packed width (5 words): the
    pack lowering (tile histogram + value-carry sort + slot gather + ONE
    all_to_all) that only engages with D >= 2 on the TPU tier."""
    import numpy as np
    mesh = Mesh(np.asarray(topo.devices), ("dp",))
    cap = 2048
    batch = _batch(NamedSharding(mesh, P("dp")), (4,), cap, **_TERASORT)
    # splitters over the key's 3 sort lanes and the position lane
    bounds = jax.ShapeDtypeStruct((3, 4), jnp.uint32,
                                  sharding=NamedSharding(mesh, P()))

    def per_shard(b, bnd):
        b = jax.tree.map(lambda x: x[0], b)
        if kind == "hash":
            out, *needs = shuffle.hash_exchange(b, ["key"], cap)
        else:
            out, *needs = shuffle.range_exchange(b, [("key", False)],
                                                 bnd, cap)
        return (jax.tree.map(lambda x: x[None], out),
                jnp.stack([n.astype(jnp.int32) for n in needs])[None])

    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=(P("dp"), P()),
                       out_specs=(P("dp"), P("dp")), check_vma=False)
    text = _compile(fn, batch, bounds).as_text()
    assert "all-to-all" in text
    assert "tpu_custom_call" in text        # hist_buckets sized the slots
