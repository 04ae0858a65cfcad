"""Hand-written pallas TPU kernels for the data-plane hot spots where the
XLA lowering measurably leaves bandwidth on the table.

This is the TPU-native answer to the reference's hand-tuned native
byte-pump (DryadVertex record/channel plumbing,
channelbuffernativewriter.cpp:1-2773, recorditem.cpp:1-1140): the
reference hand-rolls buffer management because its CPUs need it; on TPU
the XLA sort/fusion machinery already runs the comparison-network paths
at VPU speed (measured 3.9 ps/row/stage, benchmarks/pallas_probe.py), so
pallas is reserved for the primitives XLA lowers badly:

  * ``hist_buckets`` — bucket-count histogram.  XLA's bincount lowers to
    sort+segment machinery (measured 18.3 ms for 2M keys); the pallas
    kernel broadcast-compares each tile against the bucket iota along the
    (free) leading axis and accumulates per-lane partial counts in VMEM —
    0.26 ms for 2M keys, 72x.  Feeds exchange slot sizing (exact first
    waves) and the OOC bucket scatter.
  * ``prefix_sum`` — 1-D inclusive scan.  XLA's cumsum is a log-depth
    pass chain over HBM (0.54 ms / 500k f32); the pallas kernel is ONE
    streamed pass with an SMEM carry between sequential grid steps
    (in-VMEM Hillis-Steele per tile) — 0.12 ms / 512k, 4.5x.  Feeds the
    boundary-carry group aggregation (ops/kernels.group_aggregate).
  * ``prefix_max`` — the same pass with ``max`` for ``+``: 1.9 ms over
    15 M int32 where ``lax.cummax`` takes 7.1.  Feeds hash_join's search
    phase (run starts, slot owners).
  * ``slot_expand`` / ``slot_compact`` — exchange pack/unpack: the
    send-side slot expansion (first min(count, C) rows of each
    destination run -> the [D, C] slot grid) and the receive-side slot
    compaction (valid prefix of each source block -> dense rows).
    These are XLA gathers, NOT pallas kernels.  A block-DMA design (one
    dynamic-offset copy per destination run / source block) passed in
    interpret mode only: the TPU compiler (Mosaic, compiling for a
    described v5e) refuses it at every packed row width the program
    produces — "Slice shape along dimension 1 must be aligned to tiling
    (128), but is W" on the ``[rows, W]`` HBM ref (real rows pack to a
    handful of u32 words, never a multiple of 128), and at W=128,
    C=262144 ``RESOURCE_EXHAUSTED`` (a 256 MiB double-buffered VMEM
    output window against 128 MiB).  The pallas branch was deleted; a
    block-DMA kernel that compiles is future work (ROADMAP S3).
    Feeds parallel/shuffle._exchange_one_axis (every hash/range
    repartition wave).

Probe provenance (real v5e, fetch-fenced slopes — benchmarks/pallas_probe
reproduces): designs that LOST to XLA and were therefore not shipped:
per-tile permutation-matmul compaction peaked at 0.45 G rows/s vs the
XLA sort-based compact's 0.86 G rows/s (the [T,T] one-hot build costs T
compares/row); bitonic pallas sorts matched XLA's network (~4 ps/row/
stage, VPU-bound) with no algorithmic headroom because the chip has no
scatter unit and random gathers run ~10.7 ns/row — the same verdict held
for a pallas MULTI-KEY bitonic sort (the comparator is wider, the
network identical), so multi-key sort speedups ship as the XLA-level
runtime key-lane fusion in ops/kernels.sort_by_columns instead; a
per-row-DMA join gather (one async copy per matched right row, probe +
verify + gather fused per tile) bottomed out at the DMA issue rate
(descriptor cost >> 20-byte payload, ~3x WORSE than the batched XLA
gather), so the join probe fusion also ships at the XLA level
(ops/kernels.hash_join packed single-gather + rank-fused compaction).

Gating (hist_buckets, prefix_sum, prefix_sum2, prefix_max): compiled
kernels on TPU backends; ``interpret=True`` under ``force_interpret()``
(tests exercise the kernel logic on CPU); plain XLA fallbacks otherwise,
so every caller works on any backend.
"""

from __future__ import annotations

import contextlib
import functools
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

__all__ = ["hist_buckets", "prefix_sum", "prefix_sum2", "prefix_max",
           "slot_expand", "slot_compact",
           "pallas_active", "force_interpret"]

_FORCE_INTERPRET = False


@contextlib.contextmanager
def force_interpret():
    """Run the pallas kernels in interpreter mode (any backend) — used by
    the CPU test suite to exercise the real kernel bodies."""
    global _FORCE_INTERPRET
    prev = _FORCE_INTERPRET
    _FORCE_INTERPRET = True
    try:
        yield
    finally:
        _FORCE_INTERPRET = prev


@functools.lru_cache(maxsize=1)
def _on_tpu() -> bool:
    # a backend that fails to initialize raises here: answering "not a
    # TPU" would silently drop every kernel to its XLA form
    return jax.default_backend() == "tpu"


def pallas_active() -> Optional[str]:
    """None (use XLA fallback), "compiled", or "interpret"."""
    if os.environ.get("DRYAD_NO_PALLAS"):
        return None
    if _FORCE_INTERPRET:
        return "interpret"
    if _on_tpu():
        return "compiled"
    return None


def _pad_to(x: jax.Array, mult: int) -> jax.Array:
    n = x.shape[0]
    rem = (-n) % mult
    return jnp.pad(x, (0, rem)) if rem else x


# ---------------------------------------------------------------------------
# histogram

_HIST_R = 128            # tile rows of 128 lanes -> 16k elements per step
_HIST_MAX_B = 512        # acc is [B, 128] i32 in VMEM (256 KB at 512)


def _hist_kernel_body(B: int, R: int):
    import jax.experimental.pallas as pl

    def kern(x_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _():
            o_ref[:] = jnp.zeros_like(o_ref)
        x = x_ref[:]                                        # [R, 128] i32
        # bucket ids along the LEADING axis: broadcasting x there is free
        # (no lane<->sublane relayout), and the [B, R, 128] compare is
        # pure VPU work summed immediately down to [B, 128]
        iota = jax.lax.broadcasted_iota(jnp.int32, (B, 1, 1), 0)
        m = x[None, :, :] == iota
        o_ref[:] = o_ref[:] + jnp.sum(m, axis=1, dtype=jnp.int32)

    return kern


def hist_buckets(bid: jax.Array, n_buckets: int) -> jax.Array:
    """Counts of each bucket id in [0, n_buckets); other values (e.g. an
    invalid-row sentinel of ``n_buckets``) are ignored.  bid: i32 [n].

    Replaces jnp.bincount on the exchange/OOC paths (which XLA lowers to
    sort+segment machinery — measured 72x slower at 2M keys)."""
    mode = pallas_active()
    if mode is None or n_buckets > _HIST_MAX_B:
        oob = jnp.where(bid < 0, n_buckets, jnp.minimum(bid, n_buckets))
        return jnp.bincount(oob, length=n_buckets + 1)[:n_buckets]
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = bid.shape[0]
    tile = _HIST_R * 128
    x = _pad_to(bid.astype(jnp.int32), tile)
    # pad rows fall outside [0, B) only if the caller's ids stay inside;
    # shift everything by +1 so the 0-pad never counts
    x = jnp.where(jnp.arange(x.shape[0]) < n, x + 1, 0)
    B = n_buckets + 1
    grid = x.shape[0] // tile
    acc = pl.pallas_call(
        _hist_kernel_body(B, _HIST_R),
        grid=(grid,),
        in_specs=[pl.BlockSpec((_HIST_R, 128), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((B, 128), lambda i: (0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, 128), jnp.int32),
        interpret=(mode == "interpret"),
    )(x.reshape(-1, 128))
    return jnp.sum(acc, axis=1)[1:]


# ---------------------------------------------------------------------------
# prefix sum

_SCAN_R = 256            # 32k elements per grid step


def _scan_kernel_body(R: int, dt):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kern(x_ref, o_ref, carry):
        @pl.when(pl.program_id(0) == 0)
        def _():
            carry[0] = jnp.zeros((), dt)
        t = x_ref[:]                                        # [R, 128]
        zero = jnp.zeros((), dt)
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1)
        d = 1
        while d < 128:          # Hillis-Steele within each row's lanes
            t = t + jnp.where(lane >= d, pltpu.roll(t, d, 1), zero)
            d *= 2
        row_tot = t[:, 127:128]                             # [R, 1]
        sub = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        base = row_tot
        d = 1
        while d < R:            # prefix over the row totals (sublanes)
            base = base + jnp.where(sub >= d, pltpu.roll(base, d, 0), zero)
            d *= 2
        o_ref[:] = t + (base - row_tot) + carry[0]
        carry[0] = carry[0] + base[R - 1, 0]

    return kern


def _dd_add(hi1, lo1, hi2, lo2):
    """Double-single (compensated) f32 add via Knuth TwoSum + Dekker
    renormalization — the ONE implementation the pallas kernel body, the
    XLA fallback scan and the one-hot group-by's chunk accumulator
    (ops/kernels._group_aggregate_smallkey) use (drift here silently
    changes error bounds)."""
    s = hi1 + hi2
    bb = s - hi1
    err = (hi1 - (s - bb)) + (hi2 - bb)
    lo = lo1 + lo2 + err
    hi_n = s + lo
    lo_n = lo - (hi_n - s)
    return hi_n, lo_n


def _scan2_kernel_body(R: int):
    """Compensated (double-single f32) scan: every partial prefix is an
    unevaluated (hi, lo) pair combined with TwoSum, so the running error
    stays ~eps^2 x prefix instead of eps x prefix.  This is what makes
    the boundary-carry group aggregation's adjacent-difference sums safe
    for f32: the per-group error is bounded near ulp(group_sum), not
    ulp(global_prefix) (the accuracy cliff a plain cumsum would have)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    add2 = _dd_add

    def kern(x_ref, hi_ref, lo_ref, carry):
        @pl.when(pl.program_id(0) == 0)
        def _():
            carry[0] = jnp.zeros((), jnp.float32)
            carry[1] = jnp.zeros((), jnp.float32)
        hi = x_ref[:]                                       # [R, 128]
        lo = jnp.zeros_like(hi)
        zero = jnp.zeros((), jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1)
        d = 1
        while d < 128:
            m = lane >= d
            hi, lo = add2(hi, lo,
                          jnp.where(m, pltpu.roll(hi, d, 1), zero),
                          jnp.where(m, pltpu.roll(lo, d, 1), zero))
            d *= 2
        rt_hi, rt_lo = hi[:, 127:128], lo[:, 127:128]
        sub = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        b_hi, b_lo = rt_hi, rt_lo
        d = 1
        while d < R:
            m = sub >= d
            b_hi, b_lo = add2(b_hi, b_lo,
                              jnp.where(m, pltpu.roll(b_hi, d, 0), zero),
                              jnp.where(m, pltpu.roll(b_lo, d, 0), zero))
            d *= 2
        e_hi, e_lo = add2(b_hi, b_lo, -rt_hi, -rt_lo)       # exclusive
        o_hi, o_lo = add2(hi, lo, e_hi, e_lo)
        o_hi, o_lo = add2(o_hi, o_lo, carry[0], carry[1])
        hi_ref[:] = o_hi
        lo_ref[:] = o_lo
        c_hi, c_lo = add2(b_hi[R - 1, 0], b_lo[R - 1, 0],
                          carry[0], carry[1])
        carry[0] = c_hi
        carry[1] = c_lo

    return kern


@jax.named_scope("prefix_sum")
def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive 1-D prefix sum (f32/i32/u32) — one streamed pass with an
    SMEM carry across sequential grid steps, vs XLA cumsum's log-depth
    HBM pass chain (measured 4.5x at 512k f32).  For f32, see
    prefix_sum2 — the compensated variant group sums should use."""
    mode = pallas_active()
    if mode is None:
        return jnp.cumsum(x)
    return _scan_call(_scan_kernel_body(_SCAN_R, x.dtype), x, mode)


def _scan_call(kern, x: jax.Array, mode: str) -> jax.Array:
    """Run a scan kernel body over ``x`` (padded to whole tiles of
    ``_SCAN_R`` x 128) in sequential grid steps with a one-value SMEM
    carry; the first ``len(x)`` outputs."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = x.shape[0]
    dt = x.dtype
    tile = _SCAN_R * 128
    xp = _pad_to(x, tile)
    grid = xp.shape[0] // tile
    y = pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=[pl.BlockSpec((_SCAN_R, 128), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((_SCAN_R, 128), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0] // 128, 128), dt),
        scratch_shapes=[pltpu.SMEM((1,), dt)],
        interpret=(mode == "interpret"),
    )(xp.reshape(-1, 128))
    return y.reshape(-1)[:n]


def _max_scan_kernel_body(R: int, dt):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lowest = jnp.iinfo(dt).min

    def kern(x_ref, o_ref, carry):
        @pl.when(pl.program_id(0) == 0)
        def _():
            carry[0] = jnp.full((), lowest, dt)
        low = jnp.full((), lowest, dt)
        t = x_ref[:]                                        # [R, 128]
        lane = jax.lax.broadcasted_iota(jnp.int32, (R, 128), 1)
        d = 1
        while d < 128:          # Hillis-Steele within each row's lanes
            t = jnp.maximum(t, jnp.where(lane >= d, pltpu.roll(t, d, 1), low))
            d *= 2
        sub = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
        base = t[:, 127:128]                                # [R, 1]
        d = 1
        while d < R:            # running max over the row maxima
            base = jnp.maximum(base,
                               jnp.where(sub >= d, pltpu.roll(base, d, 0), low))
            d *= 2
        before = jnp.where(sub >= 1, pltpu.roll(base, 1, 0), low)
        o_ref[:] = jnp.maximum(jnp.maximum(t, before), carry[0])
        carry[0] = jnp.maximum(carry[0], base[R - 1, 0])

    return kern


@jax.named_scope("prefix_sum")
def prefix_max(x: jax.Array) -> jax.Array:
    """Inclusive 1-D running max of an integer vector — prefix_sum's one
    streamed pass with ``max`` for ``+``.  On the chip 1.9 ms over 15 M
    int32 where XLA's ``lax.cummax`` (a reduce-window) takes 7.1 ms
    (benchmarks/join_search_probe.py); ``lax.cummax`` is the fallback."""
    mode = pallas_active()
    if mode is None:
        return jax.lax.cummax(x)
    return _scan_call(_max_scan_kernel_body(_SCAN_R, x.dtype), x, mode)


@jax.named_scope("prefix_sum")
def prefix_sum2(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Compensated f32 inclusive prefix sum: returns an unevaluated
    (hi, lo) pair per prefix (hi + lo = the prefix to ~2x f32 precision).
    Consumers differencing adjacent prefixes (group sums) difference BOTH
    lanes: (hi_b - hi_a) + (lo_b - lo_a) has error near ulp of the
    difference itself — the plain-cumsum error was proportional to the
    GLOBAL prefix magnitude, unbounded relative to a small group's sum.

    Fallback (no pallas): jnp.cumsum of f64 when x64 is enabled, else a
    Dekker two-float running pair via associative_scan."""
    mode = pallas_active()
    if mode is None:
        if jax.config.jax_enable_x64:
            c = jnp.cumsum(x.astype(jnp.float64))
            hi = c.astype(jnp.float32)
            lo = (c - hi.astype(jnp.float64)).astype(jnp.float32)
            return hi, lo

        def comb(a, b):
            return _dd_add(a[0], a[1], b[0], b[1])

        return jax.lax.associative_scan(
            comb, (x, jnp.zeros_like(x)))
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = x.shape[0]
    tile = _SCAN_R * 128
    xp = _pad_to(x, tile)
    grid = xp.shape[0] // tile
    hi, lo = pl.pallas_call(
        _scan2_kernel_body(_SCAN_R),
        grid=(grid,),
        in_specs=[pl.BlockSpec((_SCAN_R, 128), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=[pl.BlockSpec((_SCAN_R, 128), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)] * 2,
        out_shape=[jax.ShapeDtypeStruct((xp.shape[0] // 128, 128),
                                        jnp.float32)] * 2,
        scratch_shapes=[pltpu.SMEM((2,), jnp.float32)],
        interpret=(mode == "interpret"),
    )(xp.reshape(-1, 128))
    return hi.reshape(-1)[:n], lo.reshape(-1)[:n]


# ---------------------------------------------------------------------------
# exchange pack/unpack (slot expansion / slot compaction)
#
# Both sides of a repartition move CONTIGUOUS row runs: after the dest
# sort, destination d's rows occupy [offsets[d], offsets[d]+counts[d]);
# after the all_to_all, source block s's valid rows are the prefix of
# slot block [s*C, (s+1)*C).  Both moves are XLA gathers over
# scatter-shaped index math (clip(offsets[d]+j) / argsort(~valid)); the
# module docstring records why the block-DMA kernels were removed.


@jax.named_scope("row_gather")
def slot_expand(words: jax.Array, offsets: jax.Array, C: int) -> jax.Array:
    """Send-slot expansion: ``words`` is the dest-sorted packed row matrix
    [cap, W] u32; destination d's rows start at ``offsets[d]`` (i32 [D]).
    Returns the [D*C, W] send buffer whose block d holds rows
    offsets[d] .. offsets[d]+C (clamped to the array; slots past the
    run's count are garbage the receiver masks via send_counts)."""
    D = offsets.shape[0]
    cap = words.shape[0]
    d_idx = jnp.repeat(jnp.arange(D, dtype=jnp.int32), C)
    j_idx = jnp.tile(jnp.arange(C, dtype=jnp.int32), D)
    src = jnp.clip(jnp.take(offsets, d_idx) + j_idx, 0, cap - 1)
    return jnp.take(words, src, axis=0)


def slot_compact(words: jax.Array, counts: jax.Array, C: int,
                 out_rows: int) -> jax.Array:
    """Receive-slot compaction: ``words`` is the received slot buffer
    [D*C, W] u32 where source block s's valid rows are the prefix
    ``counts[s]`` (i32 [D], <= C) of rows [s*C, (s+1)*C).  Returns
    [out_rows, W] with the valid rows dense at the front and zeros past
    the total (unspecified-padding rows by the Batch contract)."""
    S = words.shape[0]
    counts = jnp.minimum(counts.astype(jnp.int32), C)
    idx = jnp.arange(S, dtype=jnp.int32)
    rvalid = (idx % C) < jnp.take(counts, idx // C)
    # stable valid-first sort of the row ids, then one packed gather
    with jax.named_scope("index_sort"):
        perm = jnp.argsort(~rvalid, stable=True)
    with jax.named_scope("row_gather"):
        g = jnp.take(words, perm[:out_rows], axis=0) if S >= out_rows \
            else jnp.pad(jnp.take(words, perm, axis=0),
                         ((0, out_rows - S), (0, 0)))
    total = rvalid.sum(dtype=jnp.int32)
    gmask = jnp.arange(out_rows, dtype=jnp.int32) < total
    return jnp.where(gmask[:, None], g, 0)
