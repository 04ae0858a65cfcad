"""Sharded exchanges: hash/range repartition and broadcast as XLA collectives.

This module replaces the reference's entire shuffle transport (SURVEY.md
§2.8: producer temp files + GM URI rewriting (kernel/DrCluster.cpp:553-569) +
ranged HTTP GETs (managedchannel/HttpReader.cs:78-105) served by
ProcessService FileServer) with in-HBM ``all_to_all`` over the mesh, and the
dynamic broadcast tree (DrDynamicBroadcast.h:23) with ``all_gather``.

All functions run INSIDE ``shard_map`` over the partition axes.  On a 1-D
``(dp,)`` mesh an exchange is one all_to_all over ICI.  On a 2-D
``(dcn, dp)`` mesh a global exchange is TWO hops — within-host over ``dp``
(ICI), then across hosts over ``dcn`` (DCN) — the standard 2-hop all-to-all
that keeps the scarce DCN hop dense; single-axis exchanges (used by the
hierarchical aggregation lowering) touch only their own axis.

Capacities are static; skew beyond the per-destination capacity sets the
overflow flag (checked host-side by the executor, which re-plans with a
larger capacity — the dynamic-repartition role of
DrDynamicDistributionManager).
"""

from __future__ import annotations

import os
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from dryad_tpu.data.columnar import Batch
from dryad_tpu.ops.hashing import hash_batch_keys
from dryad_tpu.ops.kernels import (_pack_columns_u32, _unpack_columns_u32,
                                   _sort_carrying, sort_lanes_for)
from dryad_tpu.ops.pallas_kernels import (hist_buckets, pallas_active,
                                          slot_compact, slot_expand)
from dryad_tpu.parallel.mesh import PARTITION_AXIS

__all__ = ["exchange_by_dest", "hash_exchange", "range_exchange",
           "broadcast_gather", "range_key_lanes", "global_position",
           "range_dest", "range_tie_rows", "zip_exchange",
           "skew_join_exchange"]

_DEST = "__dest"


def _exchange_one_axis(batch: Batch, dest: jax.Array, axis: str,
                       out_capacity: int, send_slack: int,
                       all_axes: tuple, slot_rows: int | None = None
                       ) -> Tuple[Batch, jax.Array, jax.Array, jax.Array]:
    """Send each valid row to index ``dest[row]`` along ``axis``; compact
    received rows.

    Returns ``(batch, need_recv_rows, need_slack, slot_used)`` — the NEED
    channels are 0 when everything fit; otherwise they carry the MEASURED
    requirement (max rows any destination must hold / send-slot slack
    factor needed), so the executor re-plans ONCE at the right size
    instead of laddering through blind capacity doublings.  ``slot_used``
    is ALWAYS the measured max rows any source sent one destination
    (pmax'd): repeated exchanges (streamed waves, re-run stages) pass it
    back as ``slot_rows`` to ship EXACT send slots instead of the
    structural slack padding — wire bytes converge to ~useful bytes (the
    reference's pull shuffle ships exact file sizes; this is the SPMD
    form of its dynamic distribution feedback,
    DrDynamicDistributor.cpp:388)."""
    D = jax.lax.axis_size(axis)
    cap = batch.capacity
    valid = batch.valid_mask()
    dest = jnp.where(valid, dest.astype(jnp.int32), D)  # invalid -> sentinel

    # per-destination slot capacity in the send buffer: worst-case a single
    # destination receives this partition's whole batch, but sizing for that
    # squares the buffer; slack scales with the executor's overflow retry —
    # and a MEASURED slot_rows (from a prior wave/run) overrides both with
    # the exact need
    if slot_rows is not None:
        C = max(1, min(cap, slot_rows))
    else:
        C = max(1, min(cap, -(-send_slack * cap // D)))

    if os.environ.get("DRYAD_NO_SORT_OPT") or pallas_active() is None:
        # the pack pipeline is shaped for the TPU data plane (tile
        # histogram + value-carry sort + packed slot gather + ONE
        # all_to_all); on the CPU backend it measured ~3x SLOWER than
        # the gather lowering (BENCH_kernels.json: XLA's stable argsort
        # + composed gather wins there), so non-TPU backends keep the
        # plain-XLA form — the module contract's fallback tier.  Which
        # is faster on the chip is unmeasured (ROADMAP S3).
        # force_interpret() routes tests through the pack path on CPU.
        return _exchange_one_axis_gather(batch, dest, axis, out_capacity,
                                         C, all_axes)

    # PACK: one tile-histogram for the per-destination counts (pallas —
    # XLA's bincount lowers to sort+segment machinery, measured 72x
    # slower at 2M), one UNSTABLE value-carry sort by (dest, row index)
    # moving every column's packed u32 words (the index operand makes
    # the unstable network exactly stable — no stable-sort machinery),
    # then slot expansion (pallas_kernels.slot_expand): each
    # destination's run is CONTIGUOUS in the sorted buffer, so the send
    # grid is one gather of the packed [cap, W] matrix.
    with jax.named_scope("exchange_pack"):
        lanes, spec = _pack_columns_u32(dict(batch.columns))
        counts = hist_buckets(dest, D)                      # full counts [D]
        offsets = jnp.cumsum(counts) - counts               # exclusive prefix
        iota = jnp.arange(cap, dtype=jnp.uint32)
        _, slanes = _sort_carrying([dest.astype(jnp.uint32), iota], lanes,
                                   cap, stable=False)
        words = jnp.stack(slanes, axis=1)                   # [cap, W] u32
        send_words = slot_expand(words, offsets.astype(jnp.int32), C)
        send_counts = jnp.minimum(counts, C)

    # ONE all_to_all moves the whole packed matrix (the per-column form
    # issued one collective per column, two per StringColumn)
    recv_words = jax.lax.all_to_all(send_words, axis, 0, 0, tiled=True)
    recv_counts = jax.lax.all_to_all(send_counts, axis, 0, 0, tiled=True)

    # UNPACK: the valid rows of each received source block are a prefix
    # (pallas_kernels.slot_compact: valid-first sort + one packed
    # gather); every sender clamped its send_counts to C already
    with jax.named_scope("exchange_unpack"):
        total = recv_counts.sum(dtype=jnp.int32)
        out_words = slot_compact(recv_words, recv_counts, C, out_capacity)
        W = len(slanes)
        out = Batch(_unpack_columns_u32(
            [out_words[:, j] for j in range(W)], spec),
            jnp.minimum(total, out_capacity))

    # measured requirements (pre-truncation, so they are exact even when
    # this run dropped rows): true rows per destination over this axis...
    totals = jax.lax.psum(counts, axis)  # [D], same on every shard
    max_total = jnp.max(totals).astype(jnp.int32)
    need_recv = jnp.where(max_total > out_capacity, max_total, 0)
    # ...and the send-slot slack that would have fit the largest slot
    max_cnt = jnp.max(counts).astype(jnp.int32)
    need_slack_l = jnp.where(max_cnt > C, -(-max_cnt * D // cap), 0)
    # any shard's shortfall poisons the whole exchange
    need_recv = jax.lax.pmax(need_recv, all_axes)
    need_slack = jax.lax.pmax(need_slack_l, all_axes)
    slot_used = jax.lax.pmax(max_cnt, all_axes)
    return out, need_recv, need_slack, slot_used


def _exchange_one_axis_gather(batch: Batch, dest: jax.Array, axis: str,
                              out_capacity: int, C: int, all_axes: tuple
                              ) -> Tuple[Batch, jax.Array, jax.Array,
                                         jax.Array]:
    """The pre-kernel exchange lowering (stable dest argsort + composed
    random gather + per-column all_to_all + stable valid-sort unpack) —
    kept verbatim behind ``DRYAD_NO_SORT_OPT`` as the A/B reference for
    benchmarks/pallas_probe provenance and as a belt-and-braces escape
    hatch."""
    D = jax.lax.axis_size(axis)
    cap = batch.capacity

    order = jnp.argsort(dest, stable=True)
    sdest = jnp.take(dest, order)
    counts = jnp.bincount(jnp.minimum(sdest, D), length=D + 1)[:D]
    offsets = jnp.cumsum(counts) - counts  # exclusive prefix

    d_idx = jnp.repeat(jnp.arange(D, dtype=jnp.int32), C)
    j_idx = jnp.tile(jnp.arange(C, dtype=jnp.int32), D)
    src = jnp.clip(jnp.take(offsets, d_idx) + j_idx, 0, cap - 1)
    # ONE gather: compose the dest-sort permutation with the slot
    # selection instead of materializing the sorted batch first (a full
    # extra all-columns gather per exchange hop)
    send = batch.gather(jnp.take(order, src))
    send_counts = jnp.minimum(counts, C)

    def a2a(x):
        return jax.lax.all_to_all(x, axis, 0, 0, tiled=True)

    recv_cols = {k: jax.tree.map(a2a, v) for k, v in send.columns.items()}
    recv_counts = jax.lax.all_to_all(send_counts, axis, 0, 0, tiled=True)

    s_idx = jnp.repeat(jnp.arange(D, dtype=jnp.int32), C)
    jj = jnp.tile(jnp.arange(C, dtype=jnp.int32), D)
    rvalid = jj < jnp.take(recv_counts, s_idx)
    total = rvalid.sum(dtype=jnp.int32)
    recv = Batch(recv_cols, total)
    perm = jnp.argsort(~rvalid, stable=True)
    if out_capacity >= D * C:
        out = recv.gather(perm).pad_to(out_capacity)
    else:
        out = recv.gather(perm[:out_capacity])
    out = out.with_count(jnp.minimum(total, out_capacity))

    totals = jax.lax.psum(counts, axis)  # [D], same on every shard
    max_total = jnp.max(totals).astype(jnp.int32)
    need_recv = jnp.where(max_total > out_capacity, max_total, 0)
    max_cnt = jnp.max(counts).astype(jnp.int32)
    need_slack_l = jnp.where(max_cnt > C, -(-max_cnt * D // cap), 0)
    need_recv = jax.lax.pmax(need_recv, all_axes)
    need_slack = jax.lax.pmax(need_slack_l, all_axes)
    slot_used = jax.lax.pmax(max_cnt, all_axes)
    return out, need_recv, need_slack, slot_used


def exchange_by_dest(batch: Batch, dest: jax.Array, out_capacity: int,
                     send_slack: int = 2,
                     axes: tuple = (PARTITION_AXIS,),
                     slot_rows: int | None = None
                     ) -> Tuple[Batch, jax.Array, jax.Array, jax.Array]:
    """Send each valid row to GLOBAL partition ``dest[row]`` (index over all
    mesh axes, outermost-major).  1-D mesh: one all_to_all hop.  2-D mesh:
    two hops — to the target dp column within the host, then to the target
    host over dcn.  Returns (batch, need_recv_rows, need_slack,
    slot_used)."""
    if len(axes) == 1:
        return _exchange_one_axis(batch, dest, axes[0], out_capacity,
                                  send_slack, axes, slot_rows=slot_rows)
    # N-D mesh: dimension-ordered routing, innermost axis first (the
    # cheapest fabric carries the first hop; each later hop fixes one
    # more coordinate of the mixed-radix destination).  2-D: the classic
    # ICI-then-DCN two-hop; 3-D adds the pod level
    # (DrDynamicAggregateManager.h:99 machine->pod->overall).
    cur = batch.with_columns({_DEST: dest.astype(jnp.int32)})
    nr = ns = su = None
    radix = 1
    for ax in reversed(axes):
        sz = jax.lax.axis_size(ax)
        coord = (cur.columns[_DEST] // radix) % sz
        cur, nr_i, ns_i, su_i = _exchange_one_axis(
            cur, coord, ax, out_capacity, send_slack, axes,
            slot_rows=slot_rows)
        nr = nr_i if nr is None else jnp.maximum(nr, nr_i)
        ns = ns_i if ns is None else jnp.maximum(ns, ns_i)
        su = su_i if su is None else jnp.maximum(su, su_i)
        radix *= sz
    out_cols = {k: v for k, v in cur.columns.items() if k != _DEST}
    return Batch(out_cols, cur.count), nr, ns, su


def hash_exchange(batch: Batch, keys: Sequence[str], out_capacity: int,
                  send_slack: int = 2, axes: tuple = (PARTITION_AXIS,),
                  axis: str | None = None, slot_rows: int | None = None
                  ) -> Tuple[Batch, jax.Array, jax.Array, jax.Array]:
    """Repartition rows by key hash (HashPartition / shuffle-for-GroupBy).

    With ``axis`` set, the exchange touches only that mesh axis — used by
    the hierarchical aggregation lowering (combine over ICI first, then
    DCN), the mesh-axis form of the reference's machine->pod->overall trees
    (DrDynamicAggregateManager.h:99).  Key->place mapping is consistent
    across the per-axis and global forms: global partition of key k is
    (lo(k) // |dp|) % |dcn| on dcn, lo(k) % |dp| on dp.
    """
    _, lo = hash_batch_keys(batch, keys)
    if axis is None:
        dest = _canonical_hash_dest(lo, axes)
        return exchange_by_dest(batch, dest, out_capacity, send_slack,
                                axes, slot_rows=slot_rows)
    if axis not in axes:
        raise ValueError(axis)
    # per-axis hop of the hierarchical lowering: this axis's coordinate
    # of the SAME mixed-radix key->place mapping the global form uses
    # (combine innermost first — machine->pod->overall trees)
    radix = jnp.uint32(1)
    for a in reversed(axes):
        if a == axis:
            break
        radix = radix * jnp.uint32(jax.lax.axis_size(a))
    sz = jax.lax.axis_size(axis)
    dest = ((lo // radix) % jnp.uint32(sz)).astype(jnp.int32)
    return _exchange_one_axis(batch, dest, axis, out_capacity, send_slack,
                              axes, slot_rows=slot_rows)


def _canonical_hash_dest(lo: jax.Array, axes: tuple) -> jax.Array:
    """Global destination partition of a key's lo-hash — the SAME
    mixed-radix mapping for every mesh rank: coordinate on each axis =
    (lo // inner_radix) % axis_size, innermost axis least significant."""
    radix = jnp.uint32(1)
    dest = jnp.zeros(lo.shape, jnp.uint32)
    for a in reversed(axes):
        sz = jnp.uint32(jax.lax.axis_size(a))
        dest = dest + ((lo // radix) % sz) * radix
        radix = radix * sz
    return dest.astype(jnp.int32)


def _total_parts(axes: tuple) -> int:
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


def _left_heavy_hitters(lo: jax.Array, valid: jax.Array, axes: tuple,
                        topk: int, hot_factor: float):
    """Find globally hot key hashes from per-partition heavy hitters.

    Each partition nominates its top-``topk`` most frequent lo-hashes (a
    local segment count); candidates are all_gathered, their GLOBAL counts
    summed by cross-matching, and a candidate is hot when its global count
    exceeds ``hot_factor`` x the balanced per-partition share — the SPMD
    form of the reference's dynamic-distribution histogram decision
    (DrDynamicDistributor.h:79).  Returns (cand [P*topk] u32,
    hot_mask [P*topk] bool), identical on every shard."""
    from dryad_tpu.ops.kernels import (_hash_sort_segments, _segment_bounds)

    cap = lo.shape[0]
    n_valid = valid.sum(dtype=jnp.int32)
    order, seg, is_start, num_groups = _hash_sort_segments(lo, lo, valid)
    start_pos, end_excl = _segment_bounds(is_start, num_groups, n_valid)
    idx = jnp.arange(cap, dtype=jnp.int32)
    counts = jnp.where(idx < num_groups, end_excl - start_pos, 0)
    slo = jnp.take(lo, order)
    rep = jnp.take(slo, jnp.where(idx < num_groups, start_pos, 0))
    top = jnp.argsort(-counts)[:topk]
    cand_local = jnp.take(rep, top)
    cnt_local = jnp.take(counts, top)
    cand = jax.lax.all_gather(cand_local, axes).reshape(-1)   # [P*topk]
    cnts = jax.lax.all_gather(cnt_local, axes).reshape(-1)
    eq = cand[:, None] == cand[None, :]
    global_cnt = (eq * cnts[None, :]).sum(axis=1)
    total = jax.lax.psum(n_valid, axes)
    P = _total_parts(axes)
    share = jnp.maximum(total // jnp.int32(P), 1)
    hot = (cnts > 0) & (global_cnt.astype(jnp.float32)
                        > jnp.float32(hot_factor) * share.astype(
                            jnp.float32))
    return cand, hot


def _is_member(lo: jax.Array, cand: jax.Array, mask: jax.Array
               ) -> jax.Array:
    return ((lo[:, None] == cand[None, :]) & mask[None, :]).any(axis=1)


def skew_join_exchange(left: Batch, right: Batch, left_keys, right_keys,
                       left_cap: int, right_cap: int,
                       hot_factor: float = 4.0, topk: int = 8,
                       send_slack: int = 2,
                       axes: tuple = (PARTITION_AXIS,)):
    """Hot-key-salted join repartition (the escape hatch a 95%-hot join
    key needs: without it one destination must hold ~all left rows).

    Left rows of HOT keys spread over ALL partitions ((canonical + i) % P
    with a per-row salt); the right side splits — hot-key rows REPLICATE
    everywhere (broadcast), the rest hash-exchange canonically — so every
    matching pair still meets exactly once.  Per-device left capacity
    then tracks ~N/P instead of ~N.  Output placement is NOT hash by key
    anymore; the planner only permits salting on stages whose placement
    no downstream stage assumed (Stage.salt_ok).  Reference:
    DrDynamicDistributor.h:79 dynamic hash redistribution.

    Returns (left', right', need_left_rows, need_right_rows, need_slack).
    """
    from dryad_tpu.ops.kernels import compact, concat2
    from dryad_tpu.ops.hashing import hash_batch_keys

    _, llo = hash_batch_keys(left, list(left_keys))
    lvalid = left.valid_mask()
    cand, hot = _left_heavy_hitters(llo, lvalid, axes, topk, hot_factor)
    P = _total_parts(axes)

    is_hot_l = _is_member(llo, cand, hot)
    base_l = _canonical_hash_dest(llo, axes)
    salt = (jnp.arange(left.capacity, dtype=jnp.int32) % P)
    ldest = jnp.where(is_hot_l, (base_l + salt) % P, base_l)
    lout, lnr, lnsl, _ls = exchange_by_dest(left, ldest, left_cap,
                                            send_slack=send_slack,
                                            axes=axes)

    _, rlo = hash_batch_keys(right, list(right_keys))
    rvalid = right.valid_mask()
    is_hot_r = _is_member(rlo, cand, hot) & rvalid
    r_hot = compact(right, is_hot_r)
    r_non = compact(right, rvalid & ~is_hot_r)
    # hot right rows must be visible on every salted destination
    rh, rnr1, _ = broadcast_gather(r_hot, right_cap, axes=axes)
    # compaction REORDERED the rows — destinations must come from the
    # compacted batch's own keys
    _, rnlo = hash_batch_keys(r_non, list(right_keys))
    rn, rnr2, rnsl, _rs = exchange_by_dest(
        r_non, _canonical_hash_dest(rnlo, axes), right_cap,
        send_slack=send_slack, axes=axes)
    rout = concat2(rh, rn)   # capacity 2 * right_cap
    need_slack = jnp.maximum(lnsl, rnsl)
    return lout, rout, lnr, jnp.maximum(rnr1, rnr2), need_slack


def range_key_lanes(batch: Batch, keys: Sequence[Tuple[str, bool]]
                    ) -> list:
    """The uint32 lanes a range partitioner compares: every sort lane of
    every ``(column, descending)`` key in key order (see
    ops.kernels.sort_lanes_for).  A descending key's lanes are inverted,
    so ascending lexicographic order of the lanes IS the requested order
    and no destination is ever flipped."""
    lanes = []
    for name, desc in keys:
        lanes.extend(sort_lanes_for(batch.columns[name], desc))
    return lanes


def global_position(batch: Batch, axes: tuple = (PARTITION_AXIS,)
                    ) -> jax.Array:
    """uint32 position of each row slot in the input taken in partition
    order (valid rows of the partitions before this one, plus the local
    index): the range partitioner's tiebreak lane.  A function of the
    input alone, so a re-executed stage places every row where the first
    run did.  Wraps beyond 2**32 rows, where it only blunts the
    tiebreak's balance, never the order."""
    counts = jax.lax.all_gather(batch.count, axes).reshape(-1)
    me = jax.lax.axis_index(axes)
    start = jnp.sum(jnp.where(jnp.arange(counts.shape[0]) < me, counts, 0))
    return (start.astype(jnp.uint32)
            + jnp.arange(batch.capacity, dtype=jnp.uint32))


def range_dest(key_lanes: Sequence[jax.Array], bounds: jax.Array,
               position: jax.Array | None = None
               ) -> Tuple[jax.Array, jax.Array]:
    """THE range destination rule: a row goes to the number of splitters
    that are <= it, rows and splitters compared lexicographically as
    tuples of uint32 lanes.

    ``bounds`` is ``[P-1, K]`` (``[P-1, K+1]`` with ``position``): K
    columns over the first K of ``key_lanes`` and, with ``position``, a
    last column over that tiebreak lane.  With every key lane and the
    position compared, a run of equal keys is cut by position wherever a
    splitter's key falls inside it, so no key law can unbalance the
    partitions; with a prefix of the lanes and no position (the streamed
    paths sample the first lane alone) rows equal in the prefix stay
    together.  Either way a later partition never holds a smaller
    compared tuple than an earlier one.

    Returns ``(dest int32 [n], tie bool [n])``; ``tie`` marks the rows
    whose compared key lanes equal some splitter's — the ones only the
    tiebreak (or the side of the comparison) placed."""
    K = bounds.shape[1] - (position is not None)
    n = key_lanes[0].shape[0]
    dest = jnp.zeros((n,), jnp.int32)
    tie = jnp.zeros((n,), bool)
    # P-1 splitters, each a handful of 1-D compares that fuse into one
    # elementwise pass over the rows; no [n, P-1] intermediate
    for j in range(bounds.shape[0]):
        ge = (position >= bounds[j, K] if position is not None
              else jnp.ones((n,), bool))
        eq = jnp.ones((n,), bool)
        for k in range(K - 1, -1, -1):
            same = key_lanes[k] == bounds[j, k]
            ge = (key_lanes[k] > bounds[j, k]) | (same & ge)
            eq = eq & same
        dest = dest + ge.astype(jnp.int32)
        tie = tie | eq
    return dest, tie


def range_exchange(batch: Batch, keys: Sequence[Tuple[str, bool]],
                   bounds: jax.Array, out_capacity: int,
                   tiebreak: bool = True,
                   send_slack: int = 2, axes: tuple = (PARTITION_AXIS,),
                   slot_rows: int | None = None
                   ) -> Tuple[Batch, jax.Array, jax.Array, jax.Array]:
    """Repartition by range: row -> ``range_dest`` of its key lanes (and,
    with ``tiebreak``, its global input position) against ``bounds``,
    the sampled splitters (the reference computes these in a sampling
    stage: DryadLinqSampler.cs:42 + DrDynamicRangeDistributor.h:23).
    Partition p then holds only rows that sort at or before those of
    partition p+1 under ``keys``; equal keys may straddle partitions,
    in input order."""
    dest, _ = range_dest(range_key_lanes(batch, keys), bounds,
                         global_position(batch, axes) if tiebreak else None)
    return exchange_by_dest(batch, dest, out_capacity, send_slack, axes,
                            slot_rows=slot_rows)


def range_tie_rows(batch: Batch, keys: Sequence[Tuple[str, bool]],
                   bounds: jax.Array) -> jax.Array:
    """Valid rows of this shard whose key equals a splitter's key (the
    rows the tiebreak placed) — the ``tie_rows`` trace counter.
    ``bounds`` as ``range_exchange`` takes them with ``tiebreak``."""
    _, tie = range_dest(range_key_lanes(batch, keys), bounds[:, :-1])
    return (tie & batch.valid_mask()).sum(dtype=jnp.int32)


def zip_exchange(a: Batch, b: Batch, suffix: str = "_r",
                 send_slack: int = 2, axes: tuple = (PARTITION_AXIS,)
                 ) -> Tuple[Batch, jax.Array, jax.Array]:
    """Globally-aligned positional Zip (LINQ Zip semantics across
    partitions).

    The naive per-partition pairing silently mispairs whenever the two
    sides' per-partition counts differ (anything downstream of a filter) —
    VERDICT r1 weak item 5.  Correct global semantics: right row with
    global index g must pair with left global row g.  So right rows are
    exchanged to the partition whose left rows cover g (an all_to_all keyed
    on the left side's partition offsets), re-ordered by g, and then paired
    positionally.  Rows past the left side's total are dropped
    (shorter-side semantics; symmetric truncation happens in zip2's
    min-count).
    """
    from dryad_tpu.ops.kernels import zip2

    zero = jnp.zeros((), jnp.int32)
    counts_a = jax.lax.all_gather(a.count, axes)  # [P]
    P = counts_a.shape[0]
    if P == 1:  # single partition: already globally aligned
        return zip2(a, b, suffix), zero, zero
    starts_a = jnp.cumsum(counts_a) - counts_a  # exclusive prefix
    ends_a = starts_a + counts_a
    total_a = counts_a.sum()
    gidx = global_position(b, axes).astype(jnp.int32)
    from dryad_tpu.ops.kernels import searchsorted_small
    dest = searchsorted_small(ends_a, gidx, side="right").astype(jnp.int32)
    dest = jnp.where(gidx < total_a, dest, P)  # beyond left total: drop

    b2 = b.with_columns({"__zip_gidx": gidx})
    recv, need_recv, need_slack, _slot = exchange_by_dest(
        b2, dest, out_capacity=a.capacity, send_slack=send_slack, axes=axes)
    g = recv.columns["__zip_gidx"].astype(jnp.uint32)
    invalid = (~recv.valid_mask()).astype(jnp.uint32)
    recv = recv.gather(jnp.lexsort((g, invalid)))
    recv = Batch({k: v for k, v in recv.columns.items()
                  if k != "__zip_gidx"}, recv.count)
    return zip2(a, recv, suffix=suffix), need_recv, need_slack


def broadcast_gather(batch: Batch, out_capacity: int,
                     axes: tuple = (PARTITION_AXIS,)
                     ) -> Tuple[Batch, jax.Array, jax.Array]:
    """Replicate all partitions' rows to every partition (all_gather +
    compact).  Used for broadcast joins and k-means centroids.
    Returns (batch, need_recv_rows, need_slack=0)."""
    cap = batch.capacity

    def ag(x):
        return jax.lax.all_gather(x, axes, axis=0, tiled=True)

    cols = {k: jax.tree.map(ag, v) for k, v in batch.columns.items()}
    counts = jax.lax.all_gather(batch.count, axes)  # [P]
    D = counts.shape[0]
    s_idx = jnp.repeat(jnp.arange(D, dtype=jnp.int32), cap)
    jj = jnp.tile(jnp.arange(cap, dtype=jnp.int32), D)
    rvalid = jj < jnp.take(counts, s_idx)
    total = rvalid.sum(dtype=jnp.int32)
    merged = Batch(cols, total)
    # unstable 2-key sort (valid flag, row index): stable-equivalent
    # order without the stable machinery (see ops/kernels.compact)
    _, perm = jax.lax.sort(
        ((~rvalid).astype(jnp.uint32),
         jnp.arange(D * cap, dtype=jnp.int32)),
        num_keys=2, is_stable=False)
    if out_capacity >= D * cap:
        out = merged.gather(perm).pad_to(out_capacity)
        need = jnp.zeros((), jnp.int32)
    else:
        out = merged.gather(perm[:out_capacity])
        need = jnp.where(total > out_capacity, total, 0).astype(jnp.int32)
    return (out.with_count(jnp.minimum(total, out_capacity)), need,
            jnp.zeros((), jnp.int32))
