"""Chunk sizing from measured link and dispatch rates.

The streamed (>HBM) path moves every chunk across the host<->device link
and pays a fixed dispatch cost per chunk program.  ``chunk_rows`` was a
hand-set knob; this module picks it from what the environment actually
measures:

    per-chunk wall  ~=  rows x row_bytes / link_rate  +  dispatch_floor

so the floor is amortized to at most (1 - target_efficiency) of the
chunk wall.  On a local link (floor ~micro-seconds) the lower clamp
wins; on a high-latency, low-rate link (~0.1 s floor, ~MB/s) the tuner
picks large chunks.  The upper clamp keeps the per-chunk sort program
inside the
compile-size guard (ops/kernels._VALOPS_MAX_ELEMS: XLA:TPU unrolls sort
networks, measured 53 MB executables past it).

Reference role: the channel buffer sizing the native byte pump tunes per
fifo (channelbufferqueue.cpp:777 buffered block sizing).
"""

from __future__ import annotations

import time
from typing import Optional, Tuple

__all__ = ["pick_chunk_rows", "measured_rates"]

_RATES: Optional[Tuple[float, float]] = None   # (link_bytes_per_s, floor_s)

_MIN_ROWS = 4096
_MAX_ROWS = 4 << 20


def measured_rates(probe_mb: int = 4) -> Tuple[float, float]:
    """(d2h link bytes/s, per-dispatch floor seconds), measured once per
    process with a tiny probe (the d2h direction is the slower one of
    the streamed cycle)."""
    global _RATES
    if _RATES is not None:
        return _RATES
    import numpy as np

    import jax
    import jax.numpy as jnp

    n = probe_mb << 20
    bump = jax.jit(lambda a, s: a + s)
    x = jnp.zeros((n,), jnp.uint8)
    # warm (compile + first transfer path)
    np.asarray(bump(x, jnp.uint8(1)))
    t0 = time.perf_counter()
    np.asarray(bump(x, jnp.uint8(2)))
    link_wall = time.perf_counter() - t0
    # floor: fetch ONE scalar — all dispatch+round-trip, ~zero payload
    s = jax.jit(lambda a, q: jnp.sum(a[:8] + q))
    float(np.asarray(s(x, jnp.uint8(3))))
    t0 = time.perf_counter()
    float(np.asarray(s(x, jnp.uint8(4))))
    floor = time.perf_counter() - t0
    link = n / max(link_wall - floor, 1e-9)
    _RATES = (link, floor)
    return _RATES


def pick_chunk_rows(row_bytes: int, config=None,
                    rates: Optional[Tuple[float, float]] = None,
                    target_efficiency: float = 0.85,
                    row_lanes: Optional[int] = None) -> int:
    """Smallest chunk_rows that keeps the dispatch floor amortized to
    <= (1 - target_efficiency) of the per-chunk wall, clamped to
    [4096, 4M] and to the sort-program-size guard.

    row_bytes: bytes one row moves across the link per cycle (schema
    row width); row_lanes: packed u32 lanes per row (caps the chunk so
    chunk_rows x lanes stays inside _VALOPS_MAX_ELEMS)."""
    link, floor = rates if rates is not None else measured_rates()
    e = min(max(target_efficiency, 0.01), 0.99)
    # floor / (transfer + floor) <= 1-e  =>  transfer >= floor * e/(1-e)
    need_transfer_s = floor * e / (1.0 - e)
    rows = int(need_transfer_s * link / max(row_bytes, 1))
    rows = max(_MIN_ROWS, min(rows, _MAX_ROWS))
    if row_lanes:
        from dryad_tpu.ops.kernels import _VALOPS_MAX_ELEMS
        rows = min(rows, max(_MIN_ROWS,
                             _VALOPS_MAX_ELEMS // max(row_lanes, 1) // 4))
    # power-of-two-ish granularity keeps compiled chunk programs reusable
    # across sources with nearby widths
    g = 4096
    return max(g, rows // g * g)
