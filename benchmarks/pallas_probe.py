"""Pallas building-block probes on the real chip.

Measures the primitive costs every data-plane kernel design decision
hangs on, with the same fetch-fenced slope methodology as micro.py
(the per-call dispatch floor cancels).  Run:  python benchmarks/pallas_probe.py

Questions answered (each maps to a shipped or REJECTED design in
ops/pallas_kernels — the module docstring there carries the verdicts):
  * sort_stage_ps      — XLA variadic sort cost per row per stage (the
                         comparison-network bound all sort paths pay;
                         measured 3.9 ps — why pallas bitonic/radix
                         sorts were rejected)
  * gather_ns_row      — random-gather cost (~10.7 ns/row — why every
                         argsort+gather path loses to value-carry sorts)
  * hist_pallas vs hist_sort — the shipped tile-histogram kernel vs
                         XLA's bincount lowering (72x at 2M)
  * compact_sort       — the sort-based compact's true rate (0.86 G
                         rows/s — beat the rejected permutation-matmul
                         pallas compaction's 0.45)
  * cumsum_pallas vs cumsum_xla — the shipped streaming prefix-scan vs
                         XLA's log-depth cumsum (4.5x at 512k)

Round-6 probes (exchange pack/unpack + sort/join fusions — the shipped
vs REJECTED verdicts live in the ops/pallas_kernels docstring):
  * compact_unstable_rank vs compact_sort — the rank-fused UNSTABLE
                         compaction (row index as second sort KEY)
                         that replaced the stable 1-key form in
                         kernels.compact
  * slot_expand_dma vs slot_expand_gather — the send-slot block-DMA
                         kernel vs the D*C-row random-gather form (the
                         kernel compiles on TPU; elsewhere both sides
                         measure the same XLA fallback — run this one
                         on the chip)
  * pack_sort_unstable vs pack_argsort — the exchange pack pipeline's
                         sort: unstable (dest, idx) value-carry vs
                         stable argsort + composed gather.  REJECTED on
                         cpu (-56% at 262k, BENCH_kernels.json) -> the pack
                         lowering is gated to the TPU tier
                         (parallel/shuffle._exchange_one_axis).
  * packed_gather vs percol_gather — the join output materialization:
                         one [cap, W] word-matrix gather vs one gather
                         per column.  REJECTED on cpu (~2x slower at
                         262k; the stack/unpack copies dominate) -> 
                         kernels._packed_gather gates to the TPU tier.
  Rejected WITHOUT shipping anywhere (probe-refuted designs, r06): a
  pallas MULTI-KEY bitonic sort (wider comparator, identical network —
  no headroom vs XLA's, same verdict as the 1-key probe above; the
  multi-key win ships as runtime key-lane FUSION, kernels._sort_fused2)
  and a per-row-DMA join gather (one async copy per matched row: the
  descriptor cost >> the ~20 B payload, ~3x worse than the batched XLA
  gather — the exchange's DMAs stay BLOCK-sized instead).
"""

from __future__ import annotations

import itertools
import json
import math

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.micro import slope_time

_salt = itertools.count(1)


def _mk_u32(n, seed=0):
    return jnp.asarray(
        np.random.RandomState(seed).randint(0, 1 << 31, n, np.int64)
        .astype(np.uint32))


def probe_sort_stages(n: int = 1 << 21) -> dict:
    """ps per row per compare-exchange stage, 1-key/2-carry u32 sort."""
    k = _mk_u32(n)
    v1 = _mk_u32(n, 1)
    v2 = _mk_u32(n, 2)
    vary = jax.jit(lambda a, s: a ^ s)

    def body(i, kk):
        s = jax.lax.sort((kk, v1, v2), num_keys=1, is_stable=False)
        return s[0] ^ kk

    t = slope_time(body, lambda j: vary(k, jnp.uint32(next(_salt))),
                   k_hi=16)
    lg = math.ceil(math.log2(n))
    stages = lg * (lg + 1) // 2
    return {"sort_n": n, "sort_s": t,
            "sort_stage_ps_row": t / n / stages * 1e12}


def probe_gather(n: int = 1 << 21) -> dict:
    """random jnp.take ns/row (3 carried u32 words per row)."""
    idx = jnp.asarray(np.random.RandomState(3).permutation(n)
                      .astype(np.int32))
    w = jnp.stack([_mk_u32(n, 4), _mk_u32(n, 5), _mk_u32(n, 6)], axis=1)
    vary = jax.jit(lambda a, s: (a + s) % n)

    def body(i, ix):
        g = jnp.take(w, ix, axis=0)
        return (ix + g[:, 0].astype(jnp.int32)) % n

    t = slope_time(body, lambda j: vary(idx, jnp.int32(next(_salt))),
                   k_hi=8)
    return {"gather_n": n, "gather_ns_row": t / n * 1e9}


def probe_hist_sort(n: int = 1 << 21, B: int = 64) -> dict:
    """sort-based histogram (the argsort/bincount family's cost)."""
    bid = jnp.asarray((np.random.RandomState(7).randint(0, B, n))
                      .astype(np.int32))
    vary = jax.jit(lambda a, s: (a + s) % B)

    def body(i, b):
        h = jnp.bincount(b, length=B)
        return (b + h[0]) % B

    t = slope_time(body, lambda j: vary(bid, jnp.int32(next(_salt))),
                   k_hi=16)
    return {"hist_sort_n": n, "hist_sort_ms": t * 1e3,
            "hist_sort_grows_s": n / t / 1e9}


def probe_hist_pallas(n: int = 1 << 21, B: int = 64,
                      tile: int = 16384) -> dict:
    from dryad_tpu.ops.pallas_kernels import hist_buckets
    bid = jnp.asarray((np.random.RandomState(7).randint(0, B, n))
                      .astype(np.int32))
    vary = jax.jit(lambda a, s: (a + s) % B)

    def body(i, b):
        h = hist_buckets(b, B)
        return (b + h[0]) % B

    t = slope_time(body, lambda j: vary(bid, jnp.int32(next(_salt))),
                   k_hi=16)
    return {"hist_pallas_n": n, "hist_pallas_ms": t * 1e3,
            "hist_pallas_grows_s": n / t / 1e9}


def probe_compact_sort(n: int = 1 << 21, W: int = 5) -> dict:
    """sort-based stable compaction (current kernels.compact cost
    shape: 1 mask lane + W carried u32 words)."""
    keep = jnp.asarray((np.random.RandomState(9).rand(n) < 0.5))
    lanes = [_mk_u32(n, 10 + i) for i in range(W)]
    vary = jax.jit(lambda a, s: a ^ (s > 0))

    def body(i, kp):
        out = jax.lax.sort(
            ((~kp).astype(jnp.uint32),) + tuple(lanes),
            num_keys=1, is_stable=True)
        return kp ^ (out[1] > 0)

    t = slope_time(body, lambda j: vary(keep, jnp.int32(next(_salt) % 2)),
                   k_hi=8)
    return {"compact_sort_n": n, "compact_sort_ms": t * 1e3,
            "compact_sort_grows_s": n / t / 1e9}


def probe_cumsum_xla(n: int = 1 << 19) -> dict:
    x = jnp.asarray(np.random.RandomState(5).rand(n).astype(np.float32))
    vary = jax.jit(lambda v, s: v + s)

    def body(i, v):
        return v + jnp.cumsum(v)[-1] * 1e-9

    t = slope_time(body, lambda j: vary(x, jnp.float32(next(_salt))),
                   k_hi=64)
    return {"cumsum_xla_n": n, "cumsum_xla_ms": t * 1e3}


def probe_cumsum_pallas(n: int = 1 << 19) -> dict:
    from dryad_tpu.ops.pallas_kernels import prefix_sum
    x = jnp.asarray(np.random.RandomState(5).rand(n).astype(np.float32))
    vary = jax.jit(lambda v, s: v + s)

    def body(i, v):
        return v + prefix_sum(v) * 1e-9

    t = slope_time(body, lambda j: vary(x, jnp.float32(next(_salt))),
                   k_hi=64)
    return {"cumsum_pallas_n": n, "cumsum_pallas_ms": t * 1e3}


def probe_compact_unstable_rank(n: int = 1 << 21, W: int = 5) -> dict:
    """The rank-fused UNSTABLE compaction that replaced compact's stable
    1-key sort: (drop, row index) is a total order, so the unstable
    network reproduces the stable result without XLA's stability
    machinery (same operand set — the index replaces the iota a stable
    sort materializes internally)."""
    keep = jnp.asarray((np.random.RandomState(9).rand(n) < 0.5))
    lanes = [_mk_u32(n, 10 + i) for i in range(W)]
    iota = jnp.arange(n, dtype=jnp.uint32)
    vary = jax.jit(lambda a, s: a ^ (s > 0))

    def body(i, kp):
        out = jax.lax.sort(
            ((~kp).astype(jnp.uint32), iota) + tuple(lanes),
            num_keys=2, is_stable=False)
        return kp ^ (out[2] > 0)

    t = slope_time(body, lambda j: vary(keep, jnp.int32(next(_salt) % 2)),
                   k_hi=8)
    return {"compact_unstable_n": n, "compact_unstable_ms": t * 1e3,
            "compact_unstable_grows_s": n / t / 1e9}


def _slot_fixture(n, D, C, W):
    rng = np.random.RandomState(11)
    words = jnp.asarray(rng.randint(0, 1 << 30, (n, W)).astype(np.uint32))
    cuts = np.sort(rng.randint(0, n + 1, D - 1))
    counts = np.diff(np.concatenate([[0], cuts, [n]])).astype(np.int32)
    offsets = jnp.asarray((np.cumsum(counts) - counts).astype(np.int32))
    return words, offsets


def probe_slot_expand_dma(n: int = 1 << 20, D: int = 8,
                          W: int = 4) -> dict:
    """The shipped send-slot block-DMA kernel (slot_expand).  On
    non-TPU backends this measures its XLA fallback — compare against
    probe_slot_expand_gather ON THE CHIP."""
    from dryad_tpu.ops.pallas_kernels import slot_expand
    C = -(-2 * n // D)
    words, offsets = _slot_fixture(n, D, C, W)
    vary = jax.jit(lambda w, s: w ^ s)

    def body(i, w):
        send = slot_expand(w, offsets, C)
        return w ^ (send[:n] & 1)

    t = slope_time(body, lambda j: vary(words, jnp.uint32(next(_salt))),
                   k_hi=8)
    return {"slot_expand_dma_n": n, "slot_expand_dma_ms": t * 1e3}


def probe_slot_expand_gather(n: int = 1 << 20, D: int = 8,
                             W: int = 4) -> dict:
    """The pre-kernel D*C-row random-gather slot expansion."""
    C = -(-2 * n // D)
    words, offsets = _slot_fixture(n, D, C, W)
    d_idx = jnp.repeat(jnp.arange(D, dtype=jnp.int32), C)
    j_idx = jnp.tile(jnp.arange(C, dtype=jnp.int32), D)
    vary = jax.jit(lambda w, s: w ^ s)

    def body(i, w):
        src = jnp.clip(jnp.take(offsets, d_idx) + j_idx, 0, n - 1)
        send = jnp.take(w, src, axis=0)
        return w ^ (send[:n] & 1)

    t = slope_time(body, lambda j: vary(words, jnp.uint32(next(_salt))),
                   k_hi=8)
    return {"slot_expand_gather_n": n, "slot_expand_gather_ms": t * 1e3}


def probe_packed_gather(n: int = 1 << 20, W: int = 5) -> dict:
    """One [n, W] word-matrix gather (the join's packed output
    materialization, TPU tier) vs one gather per column."""
    lanes = [_mk_u32(n, 20 + i) for i in range(W)]
    idx = jnp.asarray(
        np.random.RandomState(21).randint(0, n, n).astype(np.int32))
    vary = jax.jit(lambda ix, s: (ix + s) % n)

    def packed(i, ix):
        w = jnp.stack(lanes, axis=1)
        g = jnp.take(w, ix, axis=0)
        return (ix + (g.sum(dtype=jnp.uint32) & 1)).astype(jnp.int32) % n

    def percol(i, ix):
        tot = jnp.zeros((), jnp.uint32)
        for ln in lanes:
            tot = tot + jnp.take(ln, ix).sum(dtype=jnp.uint32)
        return (ix + (tot & 1)).astype(jnp.int32) % n

    tp = slope_time(packed, lambda j: vary(idx, jnp.int32(next(_salt))),
                    k_hi=16)
    tc = slope_time(percol, lambda j: vary(idx, jnp.int32(next(_salt))),
                    k_hi=16)
    return {"packed_gather_n": n, "packed_gather_ms": tp * 1e3,
            "percol_gather_ms": tc * 1e3}


def run_all() -> dict:
    out = {}
    for name, fn in [("sort", probe_sort_stages),
                     ("gather", probe_gather),
                     ("hist_sort", probe_hist_sort),
                     ("hist_pallas", probe_hist_pallas),
                     ("compact_sort", probe_compact_sort),
                     ("compact_unstable", probe_compact_unstable_rank),
                     ("slot_expand_dma", probe_slot_expand_dma),
                     ("slot_expand_gather", probe_slot_expand_gather),
                     ("packed_gather", probe_packed_gather),
                     ("cumsum_xla", probe_cumsum_xla),
                     ("cumsum_pallas", probe_cumsum_pallas)]:
        try:
            out.update(fn())
        except Exception as e:  # keep probing the rest
            out[name + "_error"] = f"{type(e).__name__}: {e}"[:200]
    return out


if __name__ == "__main__":
    print(json.dumps(run_all(), indent=1))
