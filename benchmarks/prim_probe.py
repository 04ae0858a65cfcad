"""Primitive-throughput probe for kernel design (run on the real chip).

Measures the building blocks a sort/group kernel could be made of, so the
design is grounded in measured rates instead of guesses:
  * lax.sort variadic (the current lexsort path) at several n
  * 2-D row-wise sort (vmapped bitonic, the run-sort phase of a merge sort)
  * gather / scatter of a permutation (the reorder primitive)
  * cumsum, searchsorted (rank/merge primitives)
  * one-hot matmul histogram (MXU-based counting)
  * segment_sum vs sorted-cumsum-diff (group-aggregate primitives)

Methodology (matches benchmarks/micro.py): K data-dependent passes run
INSIDE one jit program via fori_loop — per-call dispatch and any
call-level caching amortize out; walls are per-pass.
"""

import json
import time

import sys

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.utils.compile_cache import enable_persistent_cache

enable_persistent_cache()

K = 8  # in-program passes


def _p(msg):
    print(msg, file=sys.stderr, flush=True)


_res = {}


def timeit(make_body, carry, iters=3, name=None):
    """make_body(i, carry) -> carry; returns per-pass seconds."""
    f = jax.jit(lambda c: jax.lax.fori_loop(0, K, make_body, c))
    t0 = time.perf_counter()
    jax.block_until_ready(f(carry))  # compile + warm
    compile_s = time.perf_counter() - t0
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(f(carry))
        best = min(best, time.perf_counter() - t0)
    if name:
        _p(f"{name}: {best / K * 1e3:.3f} ms/pass (compile {compile_s:.1f}s)")
        _res[name] = round(best / K, 7)
    return best / K


def main():
    res = {"device": str(jax.devices()[0].platform), "passes": K}
    rng = np.random.RandomState(0)

    for n in (1 << 20, 1 << 22):
        tag = f"n{n>>20}m"
        k1 = jnp.asarray(rng.randint(0, 2**32, n, dtype=np.uint32))
        k2 = jnp.asarray(rng.randint(0, 2**32, n, dtype=np.uint32))
        k3 = jnp.asarray(rng.randint(0, 2**32, n, dtype=np.uint32))
        perm = jnp.asarray(rng.permutation(n).astype(np.int32))
        payload = jnp.asarray(rng.randint(0, 2**32, (n, 4), dtype=np.uint32))
        iota = jnp.arange(n, dtype=jnp.int32)

        # baseline: loop + elementwise only
        res[f"base_{tag}_s"] = timeit(lambda i, a: a + jnp.uint32(1), k1, name=f"base_{tag}_s")

        # 1. single-operand sort (data-dependent across passes)
        res[f"sort1_{tag}_s"] = timeit(lambda i, a: jax.lax.sort(a ^ jnp.uint32(1)), k1, name=f"sort1_{tag}_s")

        # 2. variadic sort: 3 key lanes + iota payload (current lexsort)
        def lex3(i, c):
            a, b, d = c
            s = jax.lax.sort((a ^ jnp.uint32(1), b, d, iota), num_keys=3)
            return (s[0], s[1], s[2])
        res[f"lexsort3_{tag}_s"] = timeit(lex3, (k1, k2, k3), name=f"lexsort3_{tag}_s")

        # 2b. (key, iota) sort, one key lane
        def ski(i, a):
            return jax.lax.sort((a ^ jnp.uint32(1), iota), num_keys=1)[0]
        res[f"sortki_{tag}_s"] = timeit(ski, k1, name=f"sortki_{tag}_s")

        # 3. gather: 16B rows and 4B scalars by permutation
        res[f"gather16B_{tag}_s"] = timeit(lambda i, x: x[perm], payload, name=f"gather16B_{tag}_s")
        res[f"gather4B_{tag}_s"] = timeit(lambda i, x: jnp.take(x, perm), k1, name=f"gather4B_{tag}_s")

        # 4. scatter: permutation apply via .at[].set (unique indices)
        res[f"scatter4B_{tag}_s"] = timeit(
            lambda i, x: jnp.zeros((n,), jnp.uint32).at[perm].set(
                x, unique_indices=True), k1, name=f"scatter4B_{tag}_s")
        res[f"scatter16B_{tag}_s"] = timeit(
            lambda i, x: jnp.zeros((n, 4), jnp.uint32).at[perm].set(
                x, unique_indices=True), payload,
            name=f"scatter16B_{tag}_s")

        # 5. cumsum
        res[f"cumsum_{tag}_s"] = timeit(lambda i, a: jnp.cumsum(a), k1.astype(jnp.int32), name=f"cumsum_{tag}_s")

        # 6. searchsorted n into n
        srt = jnp.sort(k1)
        res[f"searchsorted_{tag}_s"] = timeit(
            lambda i, q: jnp.searchsorted(
                srt, q ^ jnp.uint32(1)).astype(jnp.uint32), k2,
            name=f"searchsorted_{tag}_s")

        # 7. histogram 256 buckets: one-hot f32 matmul vs int compare-sum
        def hist_mm(i, c):
            a, acc = c
            oh = jax.nn.one_hot((a >> 24).astype(jnp.int32), 256,
                                dtype=jnp.float32)
            return (a + jnp.uint32(1), acc + oh.sum(axis=0))
        res[f"hist256_mm_{tag}_s"] = timeit(hist_mm, (k1, jnp.zeros((256,), jnp.float32)), name=f"hist256_mm_{tag}_s")

        # 7b. per-element rank within digit via cumsum over one-hot
        def rank(i, c):
            a, acc = c
            d = (a >> 24).astype(jnp.int32)
            oh = (d[:, None] == jnp.arange(256)[None, :]).astype(jnp.int32)
            r = jnp.take_along_axis(jnp.cumsum(oh, axis=0), d[:, None],
                                    axis=1)[:, 0]
            return (a + jnp.uint32(1), acc + r.astype(jnp.uint32))
        res[f"rank_cumsum256_{tag}_s"] = timeit(rank, (k1, jnp.zeros((n,), jnp.uint32)), name=f"rank_cumsum256_{tag}_s")

        # 8. segment reductions: scatter-add vs sorted cumsum-diff
        seg = jnp.sort(jnp.asarray(rng.randint(0, n // 16, n, np.int32)))
        def ss(i, v):
            return jax.ops.segment_sum(
                v, seg, num_segments=n, indices_are_sorted=True)[seg] + v
        res[f"segsum_scatter_{tag}_s"] = timeit(ss, k1.astype(jnp.float32), name=f"segsum_scatter_{tag}_s")

        def ss_cs(i, v):
            c = jnp.cumsum(v)
            is_end = jnp.concatenate([seg[1:] != seg[:-1],
                                      jnp.ones((1,), jnp.bool_)])
            ends = jnp.nonzero(is_end, size=n, fill_value=n - 1)[0]
            tot = c[ends]
            return (tot - jnp.concatenate([jnp.zeros((1,), v.dtype),
                                           tot[:-1]]))[seg] + v
        res[f"segsum_cumsum_{tag}_s"] = timeit(ss_cs, k1.astype(jnp.float32), name=f"segsum_cumsum_{tag}_s")

    # 9. 2-D row sort (runs for a merge sort)
    for r, c in ((1024, 1024), (2048, 2048)):
        a = jnp.asarray(rng.randint(0, 2**32, (r, c), dtype=np.uint32))
        res[f"sort2d_{r}x{c}_s"] = timeit(lambda i, x: jnp.sort(x ^ jnp.uint32(1), axis=-1), a, name=f"sort2d_{r}x{c}_s")
        iota2 = jnp.broadcast_to(jnp.arange(c, dtype=jnp.int32)[None],
                                 (r, c))
        res[f"sort2dki_{r}x{c}_s"] = timeit(
            lambda i, x: jax.lax.sort((x ^ jnp.uint32(1), iota2),
                                      dimension=1, num_keys=1)[0], a,
            name=f"sort2dki_{r}x{c}_s")

    # 10. hbm copy reference
    big = jnp.asarray(rng.randint(0, 2**32, (1 << 26,), dtype=np.uint32))
    s = timeit(lambda i, x: x + jnp.uint32(1), big)
    res["hbm_rw_gbps"] = (big.size * 4 * 2) / s / (1 << 30)

    for k, v in list(res.items()):
        if k.endswith("_s"):
            res[k] = round(v, 7)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
