"""Transport + shuffle microbenchmarks.

The north-star metric (BASELINE.md config 2) is shuffle bandwidth vs line
rate.  What "line rate" means depends on the fabric available:

* multi-chip mesh: ICI all-to-all — measured by ``bench_all_to_all``;
* one chip (this environment): the shuffle data plane is HBM (device
  bucket scatter) + the host DMA link (chunk streaming) — measured by
  ``bench_hbm_copy`` / ``bench_transfers``; the effective shuffle rate to
  compare against is ``bench_exchange_effective``.

Every figure is fenced by a device->host FETCH (see _fence): on this
backend block_until_ready returns before execution completes.
"""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["bench_transfers", "bench_hbm_copy", "bench_all_to_all",
           "bench_exchange_effective", "run_all"]


def _time(fn, warmup: int = 1, iters: int = 3) -> float:
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_transfers(mb: int = 64) -> Dict[str, float]:
    """Host->device and device->host GB/s (the OOC streaming line rate).

    D2H must fetch a FRESH device array each iteration — jax.Array caches
    its numpy value after the first np.asarray, so re-fetching the same
    array measures a host memcpy, not the link."""
    n = mb * (1 << 20)
    host = np.random.RandomState(0).randint(0, 255, n, np.uint8)
    dev = jax.device_put(host)
    _fence(dev)
    bump = jax.jit(lambda a: a + jnp.uint8(1))
    _fence(bump(dev))

    # h2d closed by a scalar FETCH (block_until_ready does not block on
    # this backend); the extra round trip is negligible vs MB-scale h2d
    h2d = _time(lambda: _fence(jax.device_put(host)))

    def d2h_once():
        y = bump(dev)          # fresh array, negligible compute
        _fence(y)
        t0 = time.perf_counter()
        np.asarray(y)
        return time.perf_counter() - t0

    d2h = min(d2h_once() for _ in range(2))
    gb = n / (1 << 30)
    return {"h2d_gbps": gb / h2d, "d2h_gbps": gb / d2h, "transfer_mb": mb}


def bench_hbm_copy(mb: int = 512, inner: int = 8) -> Dict[str, float]:
    """On-device copy GB/s (upper bound for device-side bucket scatter).

    ``inner`` sequential passes run inside ONE jit call so the per-call
    dispatch cost is amortized out of the figure."""
    n = mb * (1 << 18)  # float32 elements
    x = jnp.arange(n, dtype=jnp.float32)
    x.block_until_ready()

    def body(_, a):
        return a + 1.0

    f = jax.jit(lambda a: jax.lax.fori_loop(0, inner, body, a))
    _fence(f(x))
    t = _time(lambda: _fence(f(x)))
    gb = 2 * n * 4 * inner / (1 << 30)  # read + write per pass
    # wall-based (fetch-fenced) — the dispatch + fetch round trip
    # inflates t, so this UNDERSTATES the device; hbm_copy_gbps_true
    # (slope) is the honest denominator
    return {"hbm_copy_gbps": gb / t, "hbm_copy_mb": n * 4 / (1 << 20)}


def _fence(tree) -> float:
    """HARD device fence: fetch a scalar reduce of every leaf.

    A device->host FETCH provably waits for the producing computation
    on every backend, so every timed region ends by pulling one scalar.
    The fence's own cost (a reduce dispatch + one round trip) is
    constant per call and cancels in the slope."""
    tot = 0.0
    for l in jax.tree.leaves(tree):
        tot += float(np.asarray(jnp.sum(l.astype(jnp.float32))))
    return tot


def slope_time(body, make_carry, k_lo: int = 4, k_hi: int = 32,
               iters: int = 4) -> float:
    """DEVICE seconds per pass of ``body(i, carry) -> carry``, measured as
    the SLOPE between two in-program fori_loop repetition counts.

    Why: each jit CALL carries a fixed dispatch cost that can swamp
    per-call walls of short programs.  The difference of two call walls
    cancels that floor exactly.  The K spread must be wide enough that
    the device-time delta clears the per-call jitter.

    ``make_carry(j)`` must return a FRESH carry (distinct values per j)
    so that no layer can serve a repeated identical (program, inputs)
    call from a cache.  Timed regions are closed by _fence (a scalar
    FETCH)."""
    walls = {}
    for K in (k_lo, k_hi):
        def run(c, K=K):
            out = jax.lax.fori_loop(0, K, body, c)
            return sum(jnp.sum(l.astype(jnp.float32))
                       for l in jax.tree.leaves(out))
        f = jax.jit(run)
        float(np.asarray(f(make_carry(0))))      # compile + warm + fetch
        best = float("inf")
        for j in range(1, iters + 1):
            c = make_carry((K, j))
            _fence(c)                            # settle inputs
            t0 = time.perf_counter()
            float(np.asarray(f(c)))
            best = min(best, time.perf_counter() - t0)
        walls[K] = best
    return max((walls[k_hi] - walls[k_lo]) / (k_hi - k_lo), 1e-9)


def bench_device_truth(mb: int = 256) -> Dict[str, float]:
    """Slope-measured device-truth numbers: the per-dispatch floor and the
    true HBM copy rate — the denominators honest rooflines need."""
    n = mb * (1 << 18)
    x = jnp.arange(n, dtype=jnp.float32)
    x.block_until_ready()
    bump = jax.jit(lambda a, s: a + s)
    import itertools
    ctr = itertools.count(1)

    def mk(j):
        # monotonic salt: DISTINCT content every call (see slope_time)
        return bump(x, jnp.float32(next(ctr)))

    # wide K spread: the delta must clear the per-call jitter of the
    # dispatch floor
    per_pass = slope_time(lambda i, a: a + 1.0, mk, k_lo=4, k_hi=64)
    true_gbps = 2 * n * 4 / per_pass / (1 << 30)
    # dispatch floor: whole-call wall minus the device time it contains
    # (fresh inputs per call — see slope_time's memoization note)
    f = jax.jit(lambda a: jax.lax.fori_loop(0, 4, lambda i, b: b + 1.0, a))
    _fence(f(x))
    wall = float("inf")
    for j in (11, 12, 13):
        c = mk(j)
        _fence(c)
        t0 = time.perf_counter()
        _fence(f(c))
        wall = min(wall, time.perf_counter() - t0)
    floor = max(wall - 4 * per_pass, 0.0)
    return {"hbm_copy_gbps_true": true_gbps,
            "dispatch_floor_ms": floor * 1e3}


def bench_all_to_all(mesh=None, mb_per_device: int = 64) -> Dict[str, float]:
    """Raw all_to_all GB/s per device over the mesh's partition axis.

    Only meaningful with >1 device (rides ICI on real hardware).  Returns
    {} on a single-device mesh."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from jax import shard_map

    devs = jax.devices() if mesh is None else list(mesh.devices.flat)
    P = len(devs)
    if P < 2:
        return {}
    m = Mesh(np.asarray(devs), ("dp",))
    rows = mb_per_device * (1 << 20) // 4 // P * P
    x = jnp.arange(P * rows, dtype=jnp.float32).reshape(P, rows)
    x = jax.device_put(x, NamedSharding(m, PartitionSpec("dp")))

    def a2a(block):
        b = block.reshape(P, rows // P)
        return jax.lax.all_to_all(b, "dp", 0, 0, tiled=True)

    f = jax.jit(shard_map(a2a, mesh=m, in_specs=PartitionSpec("dp", None),
                          out_specs=PartitionSpec("dp", None)))
    _fence(f(x))
    t = _time(lambda: _fence(f(x)))
    # each device sends (P-1)/P of its block
    gb_sent = rows * 4 * (P - 1) / P / (1 << 30)
    return {"all_to_all_gbps_per_device": gb_sent / t,
            "all_to_all_devices": P}


def bench_exchange_effective(rows: int = 1_000_000,
                             n_buckets: int = 64) -> Dict[str, float]:
    """Effective shuffle GB/s of the real single-chip exchange path: device
    range-bucket scatter (hash lane -> stable sort -> histogram) + D2H
    fetch — the per-chunk shuffle step of exec/ooc.external_sort."""
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.exec.ooc import _make_hash_scatter_fn

    rng = np.random.RandomState(0)
    k = rng.randint(0, 1 << 31, rows).astype(np.int32)
    v = rng.randint(0, 1 << 31, rows).astype(np.int32)
    b = Batch({"k": jax.device_put(k), "v": jax.device_put(v)},
              jnp.asarray(rows, jnp.int32))
    scatter = _make_hash_scatter_fn(("k",), n_buckets)

    def run():
        grouped, hist = scatter(b)
        # fetch to host like the real path does
        np.asarray(grouped.columns["k"])
        np.asarray(grouped.columns["v"])
        np.asarray(hist)

    run()
    t = _time(run)
    gb = rows * 8 / (1 << 30)  # two i32 columns through scatter + D2H
    return {"exchange_effective_gbps": gb / t, "exchange_rows": rows,
            "exchange_buckets": n_buckets}


def bench_compile_probe() -> Dict[str, float]:
    """Time fresh-program compiles (run-unique constants defeat every
    cache): compile time scales PER SHAPE CLASS (small programs compile
    in <1 s while multi-million-row sort programs, whose networks XLA
    unrolls, take minutes).  Two probes: a small elementwise/matmul
    program, and a representative BIG sort (a 3-operand 2M-row sort, the
    shape class every full-size stage leans on)."""
    import uuid
    salt = float(uuid.uuid4().int % 100003)  # unique per invocation
    x = jnp.zeros((512, 512), jnp.float32)
    t0 = time.perf_counter()
    jax.jit(lambda a: jnp.tanh(a * salt) @ a + salt).lower(x).compile()
    small = time.perf_counter() - t0
    out = {"compile_probe_s": small}
    if small > 20:
        # small probe already sick: don't pay a big compile to learn more
        out["compile_probe_big_s"] = float("inf")
        return out
    k = jnp.zeros((1 << 21,), jnp.uint32)
    isalt = jnp.uint32(uuid.uuid4().int % 1000003)

    def big(a):
        s0, s1, s2 = jax.lax.sort(
            (a ^ isalt, a + isalt,
             jax.lax.iota(jnp.uint32, a.shape[0])), num_keys=2,
            is_stable=True)
        return s0[0] + s2[0]

    t0 = time.perf_counter()
    jax.jit(big).lower(k).compile()
    out["compile_probe_big_s"] = time.perf_counter() - t0
    return out


def run_all() -> Dict[str, float]:
    out: Dict[str, float] = {}
    out.update(bench_transfers())
    out.update(bench_hbm_copy())
    out.update(bench_device_truth())
    out.update(bench_compile_probe())
    out.update(bench_all_to_all())
    out.update(bench_exchange_effective())
    return out


if __name__ == "__main__":
    import json

    print(json.dumps(run_all(), indent=1))
