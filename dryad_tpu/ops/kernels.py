"""Per-partition operator kernels over columnar Batches.

These are the record-streaming operator implementations of the reference's
vertex runtime (LinqToDryad/DryadLinqVertex.cs:51 — Where/Select/GroupBy/
Join/sorts/partitioners), re-designed for XLA: every kernel is a pure,
shape-static function on ``Batch`` pytrees, so a fused pipeline of them jits
into ONE XLA program per stage (the reference gets the same effect from
supernode pipelining + subgraphvertex.cpp fused processes; we get it from the
compiler).

Key idioms:
  * validity is a prefix: ``count`` valid rows then padding;
  * compaction (filter) = stable argsort of the drop-mask;
  * group-by = 64-bit key hash -> lexsort -> segment boundaries -> segment
    reductions (sort-based, like the reference's hash/merge GroupBy but
    tensorized);
  * join = sort the right side by key hash, find each left row's candidate
    range with one merge sort of both sides' hashes, hand each output slot
    its left row with a running max, then verify real key equality.

Device scopes: each kernel's body lies in a ``jax.named_scope`` of one
fixed vocabulary — ``index_sort``, ``row_gather``, ``search``,
``prefix_sum`` (pallas_kernels), ``group_aggregate``, ``compact``,
``hash_join``, ``lookup_join``, and the exchange's phases
``exchange_pack`` / ``exchange_unpack`` (parallel/shuffle.py) — so a
device profile's ``op_name`` says which kernel an op belongs to
(perfbench/kernel_scopes.py sums self time by it).  A scope is trace-time
metadata: it changes no instruction.
"""

from __future__ import annotations

import contextlib
import os
import threading
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from dryad_tpu.data.columnar import Batch, Int64Column, StringColumn
from dryad_tpu.ops.hashing import hash_batch_keys

__all__ = [
    "compact", "filter_rows", "sort_by_columns", "group_aggregate",
    "group_decompose_partial", "group_decompose_merge",
    "group_decompose_local", "distinct",
    "group_top_k", "group_rank_select", "group_regroup_apply",
    "scalar_aggregate", "hash_join", "semi_anti_join",
    "concat2", "take", "AGG_KINDS",
]

AGG_KINDS = ("sum", "count", "min", "max", "mean", "any", "all", "sum64")


@jax.named_scope("search")
def searchsorted_small(bounds: jax.Array, q: jax.Array,
                       side: str = "left") -> jax.Array:
    """searchsorted against a SMALL sorted array (partition bounds, bucket
    splitters).  jnp.searchsorted's default 'scan' method lowers to a
    while loop of random gathers — measured ~180 ms per 1M queries on TPU
    — while 'compare_all' fuses into |bounds| vectorized compares
    (~free for |bounds| <= a few thousand)."""
    return jnp.searchsorted(bounds, q, side=side, method="compare_all")


# ---------------------------------------------------------------------------
# packed row transport: u32 word lanes carried as sort VALUE operands
#
# TPU random gathers cost ~9 ns/row and scatters serialize, while the
# variadic sort network streams its value operands with vector-unit
# memory access — measured 3.5x faster to CARRY a packed 20-byte payload
# through lax.sort than to lexsort indices and gather the columns
# (benchmarks/prim_probe.py).  So every argsort+gather pair below is
# expressed as ONE stable lax.sort over (key lanes..., packed words...).


def _pack_columns_u32(cols: Dict[str, Any]) -> Tuple[List[jax.Array], List]:
    """Columns -> list of uint32 word lanes [cap] + a reassembly spec."""
    lanes: List[jax.Array] = []
    spec: List[Tuple] = []
    for name in cols:
        v = cols[name]
        if isinstance(v, StringColumn):
            L = v.max_len
            L4 = -(-L // 4) * 4
            d = jnp.pad(v.data, ((0, 0), (0, L4 - L))) if L4 != L else v.data
            w = jax.lax.bitcast_convert_type(
                d.reshape(d.shape[0], L4 // 4, 4), jnp.uint32)
            k = w.shape[1]
            lanes.extend(w[:, j] for j in range(k))
            lanes.append(v.lengths.astype(jnp.uint32))
            spec.append((name, "str", L, k + 1))
        elif isinstance(v, Int64Column):
            lanes.append(jax.lax.bitcast_convert_type(v.hi, jnp.uint32))
            lanes.append(v.lo)
            spec.append((name, "i64", None, 2))
        else:
            tail = v.shape[1:]
            flat = v.reshape(v.shape[0], -1) if tail else v[:, None]
            if flat.dtype.itemsize == 4:
                w = jax.lax.bitcast_convert_type(flat, jnp.uint32)
            elif flat.dtype.itemsize == 8:
                w = jax.lax.bitcast_convert_type(flat, jnp.uint32)
                w = w.reshape(w.shape[0], -1)
            elif flat.dtype.itemsize == 2:
                # f16/bf16/i16/u16: BIT-level widening (a numeric astype
                # would truncate half-precision fractions)
                w = jax.lax.bitcast_convert_type(
                    flat, jnp.uint16).astype(jnp.uint32)
            else:  # bool / u8 / i8 widen losslessly (mod-256 roundtrip)
                w = flat.astype(jnp.uint32)
            k = w.shape[1]
            lanes.extend(w[:, j] for j in range(k))
            spec.append((name, "dense", (v.dtype, tail), k))
    return lanes, spec


def _unpack_columns_u32(lanes: List[jax.Array], spec: List) -> Dict[str, Any]:
    cols: Dict[str, Any] = {}
    i = 0
    for name, kind, meta, k in spec:
        w = lanes[i:i + k]
        i += k
        if kind == "str":
            L = meta
            data4 = jax.lax.bitcast_convert_type(
                jnp.stack(w[:-1], axis=1), jnp.uint8)
            data = data4.reshape(data4.shape[0], -1)[:, :L]
            cols[name] = StringColumn(data, w[-1].astype(jnp.int32))
        elif kind == "i64":
            cols[name] = Int64Column(
                jax.lax.bitcast_convert_type(w[0], jnp.int32), w[1])
        else:
            dtype, tail = meta
            if dtype.itemsize == 4:
                flat = jax.lax.bitcast_convert_type(
                    jnp.stack(w, axis=1), dtype)
            elif dtype.itemsize == 8:
                flat = jax.lax.bitcast_convert_type(
                    jnp.stack(w, axis=1).reshape(w[0].shape[0], -1, 2),
                    dtype)
            elif dtype.itemsize == 2:
                flat = jax.lax.bitcast_convert_type(
                    jnp.stack(w, axis=1).astype(jnp.uint16), dtype)
            else:
                flat = jnp.stack(w, axis=1).astype(dtype)
            cols[name] = flat.reshape((flat.shape[0],) + tail) if tail \
                else flat[:, 0]
    return cols



def _lane_differs(*lanes: jax.Array) -> jax.Array:
    """Per-row "key differs from previous row" mask over SORTED key lanes
    (row 0 always True) — the input _segment_flags expects.  The single
    home of the adjacent-compare; every segment sorter and the
    boundary-carry aggregator call it."""
    d = None
    for l in lanes:
        dl = l[1:] != l[:-1]
        d = dl if d is None else (d | dl)
    return jnp.concatenate([jnp.ones((1,), jnp.bool_), d])


def _sentinel_fold(hi: jax.Array, lo: jax.Array, valid: jax.Array):
    """Fold invalid rows to the all-ones 64-bit hash sentinel so they
    sort last without an extra invalid lane (collision budget documented
    on _hash_sort_segments)."""
    big = jnp.uint32(0xFFFFFFFF)
    return jnp.where(valid, hi, big), jnp.where(valid, lo, big)


def _dense_key_lane(kcol) -> jax.Array:
    """Order lane of a dense-fast GROUPING key.  Grouping equality
    canonicalizes signed zero (-0.0 == +0.0, matching hashing._hash_dense
    and the shuffle partitioner); the order-transform lane would
    otherwise split them.  Shared by both group_aggregate lowerings."""
    if jnp.issubdtype(kcol.dtype, jnp.floating):
        kcol = jnp.where(kcol == 0, jnp.zeros((), kcol.dtype), kcol)
    return _dense_sort_lanes(kcol, False)[0]


def _dense_fast_key(batch: Batch, key_names: Sequence[str]) -> bool:
    """Single <=32-bit 1-D dense key: group by its EXACT order lane (no
    hashing, rebuilt from the sorted lane) — shared predicate of the
    grouping kernels."""
    if len(key_names) != 1:
        return False
    kcol0 = batch.columns[key_names[0]]
    return (_lanes_reconstructible(kcol0)
            and not isinstance(kcol0, StringColumn)
            and len(_dense_sort_lanes(kcol0, False)) == 1)


def _segment_flags(differs: jax.Array, n_valid):
    """Shared boundary derivation for the segment sorters: given the
    per-row "key differs from previous row" mask over SORTED rows (row 0
    always True), mark each segment's first/last row among the valid
    prefix.  The single home of this subtle logic — both the hash and the
    dense-key sorters call it."""
    cap = differs.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    svalid = idx < n_valid
    is_start = svalid & differs
    nxt_start = jnp.concatenate([is_start[1:], jnp.ones((1,), jnp.bool_)])
    is_end = svalid & (nxt_start | (idx + 1 == n_valid))
    num_groups = is_start.sum(dtype=jnp.int32)
    return is_start, is_end, num_groups


@jax.named_scope("index_sort")
def _sort_segments_carry(hi: jax.Array, lo: jax.Array, valid: jax.Array,
                         n_valid, value_lanes, stable: bool = True,
                         bounded: bool = True):
    """Value-carry hash segmentation: ONE stable variadic sort groups rows
    by the 64-bit hash (invalid rows fold to the all-ones sentinel and
    sort last — same collision budget as _hash_sort_segments), carrying
    ``value_lanes`` as sort value operands.  Returns (sorted value lanes,
    is_start, is_end, num_groups); is_start/is_end mark each hash
    segment's first/last SORTED row among the valid prefix.  The single
    home of this subtle boundary logic — group_aggregate, distinct, and
    _hash_membership all call it.

    ``stable=False`` drops the in-segment order guarantee (XLA's stable
    sort costs ~2x the unstable one, measured) — safe only when nothing
    downstream observes the order of rows WITHIN a hash segment.

    The valid rows are the first ``n_valid`` sorted rows, and that is
    what bounds the value lanes' gather (_sort_carrying's ``live``: the
    lanes past it come back zero); ``bounded=False`` is for a caller
    that reads the sorted padding rows' lanes too."""
    cap = hi.shape[0]
    hi_s, lo_s = _sentinel_fold(hi, lo, valid)
    (shi, slo), sorted_vals = _sort_carrying(
        [hi_s, lo_s], value_lanes, cap, stable=stable,
        live=n_valid if bounded else None)
    is_start, is_end, num_groups = _segment_flags(
        _lane_differs(shi, slo), n_valid)
    return sorted_vals, is_start, is_end, num_groups


@jax.named_scope("index_sort")
def _sort_segments_dense(key_lane: jax.Array, valid: jax.Array, n_valid,
                         value_lanes):
    """Dense-key segmentation: like _sort_segments_carry but grouping by a
    single order-transformed u32 lane holding the EXACT key (no hash, no
    collision budget).  An explicit invalid flag is the most significant
    sort key (a real key may legitimately hit the all-ones lane value, so
    the sentinel fold used for 64-bit hashes is not sound here).  The sort
    is UNSTABLE: in-segment value order is not observed by any caller
    (aggregates are commutative; representatives only read key columns,
    which are equal within a segment).  Returns (sorted key lane, sorted
    value lanes, is_start, is_end, num_groups)."""
    cap = key_lane.shape[0]
    inv = (~valid).astype(jnp.uint32)
    (sinv, skey), sorted_vals = _sort_carrying(
        [inv, key_lane], value_lanes, cap, stable=False, live=n_valid)
    is_start, is_end, num_groups = _segment_flags(
        _lane_differs(skey), n_valid)
    return skey, sorted_vals, is_start, is_end, num_groups


# multi-key sorts with exactly two u32 key lanes runtime-fuse them into
# ONE lane when the measured lane spans allow (span_a * span_b <= 2^32);
# both lowerings live in one lax.cond, so the gate bounds the doubled
# sort-program size (XLA unrolls sort networks — see _VALOPS_MAX_ELEMS)
_SORT_FUSE_MAX_CAP = 1 << 21

# value-carry beats lexsort+gather until the packed row is so wide that
# carrying it through every compare-exchange pass costs more than one
# ~9 ns/row random gather (measured crossover ~32 words = 128 B/row)
_VALOPS_MAX_WORDS = 32
# ...and until the PROGRAM gets too big: XLA:TPU unrolls sort networks,
# so executable size scales ~log^2(n) x operands (measured 53 MB for an
# 8-operand sort at 250k rows) — huge caps with many carried words make
# compiles take minutes and binaries enormous.  Above this
# cap x operand budget, reorder via the 3-operand index sort + ONE
# packed gather instead: the gather pays ~10 ns a row it fetches, so a
# caller that knows how many of its sorted rows are live hands that
# count down and the gather fetches those alone (_gather_live).
_VALOPS_MAX_ELEMS = 48 << 20
# rows a trip of the bounded gather's loop fetches (_gather_live); one
# value for every site.  On the chip 16 Ki, 64 Ki and 256 Ki rows a trip
# cost the same to the millisecond at every live share
# (benchmarks/gather_live_probe.py): the middle one, which rounds a
# count up by at most a millisecond's rows
_GATHER_CHUNK = 1 << 16

# open tallies of this thread by kind (gather_tally, onehot_tally): the
# trace they were opened under and their entries, or nothing
_TALLY = threading.local()


@contextlib.contextmanager
def _open_tally(kind: str):
    prev = getattr(_TALLY, kind, None)
    entries: List[Any] = []
    setattr(_TALLY, kind, (jax.core.get_opaque_trace_state(), entries))
    try:
        yield entries
    finally:
        setattr(_TALLY, kind, prev)


def _tally(kind: str, entry) -> None:
    """Add ``entry`` to the open tally of ``kind`` if it was opened under
    the trace being traced now: a value traced under another trace — a
    ``lax.cond`` branch, a loop body — cannot leave it."""
    tally = getattr(_TALLY, kind, None)
    if tally is not None and tally[0] == jax.core.get_opaque_trace_state():
        tally[1].append(entry)


def gather_tally():
    """Collect what the bounded gathers traced inside the block fetch:
    a list of ``(rows fetched: traced int32 scalar, rows the unbounded
    gather would have fetched: int)``, one entry a gather.  A stage
    program sums them into its info vector (exec/executor).  A gather
    traced under another trace than the block's own — a ``lax.cond``
    branch, a loop body — is left out: its scalars cannot leave it."""
    return _open_tally("gather")


def onehot_tally():
    """Collect the run-time predicates of the group-bys traced inside
    the block that can take the one-hot lowering
    (``_group_aggregate_smallkey``): a list of traced bool scalars, True
    where the one-hot branch runs.  Left out as ``gather_tally`` leaves
    a gather out."""
    return _open_tally("onehot")


@jax.named_scope("row_gather")
def _gather_live(src: jax.Array, idx: jax.Array, live=None,
                 lanes_first: bool = False) -> jax.Array:
    """``jnp.take(src, idx, axis=0)`` in rows ``[0, live)`` and zeros in
    rows ``[live, len(idx))``, at a cost that follows ``live``.  For the
    callers whose rows past a count are padding that they mask anyway.

    A zero buffer is filled ``_GATHER_CHUNK`` rows a trip, ``ceil(live /
    chunk)`` trips (the last chunk is clamped to the buffer's end and
    fetches some rows twice).  Where more than three quarters of the rows
    are live the one whole take runs instead (a run-time choice on the
    count, ``lax.cond``): on the chip a row of the loop costs up to 1.8
    of a row of the whole take (benchmarks/gather_live_probe.py), so a
    full batch pays what it paid before there was a loop.  An index
    vector no longer than one chunk is always the whole take.

    ``lanes_first``: ``src`` is a ``[rows, W]`` word matrix and the
    result its transpose, ``[W, len(idx)]`` — each chunk is written
    lane-major, so the lanes come out as rows with nothing unstacked at
    capacity.  ``live=None`` is the one take."""
    def whole():
        g = jnp.take(src, idx, axis=0)
        return g.T if lanes_first else g

    cap = idx.shape[0]
    if live is None or cap == 0:
        return whole()
    chunk = _GATHER_CHUNK
    tail = src.shape[1:]
    live = jnp.clip(jnp.asarray(live, jnp.int32), 0, cap)

    def before_live(n, at=0):
        keep = at + jnp.arange(n, dtype=jnp.int32) < live
        return keep[None, :] if lanes_first else \
            keep.reshape((n,) + (1,) * len(tail))

    def whole_masked():
        return jnp.where(before_live(cap), whole(), jnp.zeros((), src.dtype))

    if cap <= chunk:        # one trip would fetch it all: nothing to bound
        out, fetched = whole_masked(), cap
    else:
        trips = (live + (chunk - 1)) // chunk

        def trip(i, out):
            at = jnp.minimum(i * chunk, cap - chunk)
            rows = jnp.take(
                src, jax.lax.dynamic_slice(idx, (at,), (chunk,)), axis=0)
            rows = jnp.where(before_live(chunk, at),
                             rows.T if lanes_first else rows,
                             jnp.zeros((), src.dtype))
            return jax.lax.dynamic_update_slice(
                out, rows,
                (0, at) if lanes_first else (at,) + (0,) * len(tail))

        def chunks():
            shape = tail + (cap,) if lanes_first else (cap,) + tail
            return jax.lax.fori_loop(0, trips, trip,
                                     jnp.zeros(shape, src.dtype))

        dense = live > cap - cap // 4
        out = jax.lax.cond(dense, whole_masked, chunks)
        fetched = jnp.where(dense, cap, jnp.minimum(trips * chunk, cap))
    _tally("gather", (fetched, cap))
    return out


def _carry_fits(cap: int, n_key_lanes: int, n_val_lanes: int) -> bool:
    return (n_val_lanes <= _VALOPS_MAX_WORDS
            and cap * (n_key_lanes + n_val_lanes) <= _VALOPS_MAX_ELEMS)


@jax.named_scope("row_gather")
def _gather_lanes(value_lanes, order: jax.Array, live=None):
    """The lanes' rows in ``order``: ONE packed gather of the stacked
    ``[cap, W]`` word matrix, of the first ``live`` rows alone where
    given (_gather_live)."""
    words = jnp.stack(value_lanes, axis=1)
    if live is None:
        g = jnp.take(words, order, axis=0)
        return [g[:, j] for j in range(len(value_lanes))]
    out = _gather_live(words, order, live, lanes_first=True)
    return [out[j] for j in range(len(value_lanes))]


@jax.named_scope("index_sort")
def _sort_carrying(key_lanes, value_lanes, cap: int, stable: bool = True,
                   live=None):
    """Sort by uint32 ``key_lanes`` (stable by default) returning the value
    lanes in sorted order — value-carry when the program-size budget
    allows, else index sort + one packed gather (see _VALOPS_MAX_ELEMS).

    ``live`` (optional, a traced count): the caller's promise that only
    the first ``live`` SORTED rows are read — the rest are padding it
    masks.  The gather branch then fetches those rows alone and returns
    zeros in the value lanes past them; the value-carry branch, and the
    sorted key lanes of either, do not look at it."""
    value_lanes = list(value_lanes)
    if _carry_fits(cap, len(key_lanes), len(value_lanes)):
        out = jax.lax.sort(tuple(key_lanes) + tuple(value_lanes),
                           num_keys=len(key_lanes), is_stable=stable)
        return list(out[:len(key_lanes)]), list(out[len(key_lanes):])
    out = jax.lax.sort(tuple(key_lanes)
                       + (jnp.arange(cap, dtype=jnp.int32),),
                       num_keys=len(key_lanes), is_stable=True)
    order = out[len(key_lanes)]
    if not value_lanes:
        return list(out[:len(key_lanes)]), []
    return (list(out[:len(key_lanes)]),
            _gather_lanes(value_lanes, order, live))


@jax.named_scope("index_sort")
def _sort_fused2(lanes: List[jax.Array], packed: List[jax.Array],
                 cap: int, live=None):
    """Runtime key-lane fusion for 2-key-lane sorts (multi-key sort key
    packing): when the VALID rows' lane spans satisfy
    span_a * span_b <= 2^32, the two lex lanes collapse into ONE fused
    lane ``(la - la_min) * span_b + (lb - lb_min)`` — the sort network's
    cost is linear in operands (measured, see sort_by_columns), so the
    fused program runs one comparator lane where the general one runs
    two.  The spans are runtime values, so the choice is a lax.cond
    between the two lowerings (the _group_aggregate_smallkey pattern);
    wide-span inputs pay two tiny reductions and ride the general path.
    ``lanes`` is [invalid, la, lb]; returns the same
    ([sinv, sla, slb], svals) structure either way (the fused branch
    rebuilds the sorted lanes from the fused lane — exact for valid
    rows; invalid rows' lanes are garbage both ways and every caller
    masks them).  ``live`` bounds either branch's gather
    (_sort_carrying)."""
    inv, la, lb = lanes
    valid = inv == 0
    big = jnp.uint32(0xFFFFFFFF)
    zero = jnp.uint32(0)
    la_min = jnp.min(jnp.where(valid, la, big))
    la_max = jnp.max(jnp.where(valid, la, zero))
    lb_min = jnp.min(jnp.where(valid, lb, big))
    lb_max = jnp.max(jnp.where(valid, lb, zero))
    any_valid = valid.any()
    span_a = la_max - la_min + 1
    span_b = lb_max - lb_min + 1
    # fused max = span_a*span_b - 1 must fit u32; the conservative test
    # span_a <= big // span_b never wraps (off by < span_b rows)
    ok = (any_valid & (la_max >= la_min) & (lb_max >= lb_min)
          & (span_a != 0) & (span_b != 0)
          & (span_a <= big // jnp.maximum(span_b, 1)))

    def fused(args):
        inv, la, lb, packed = args
        f = (la - la_min) * span_b + (lb - lb_min)
        (sinv, sf), svals = _sort_carrying([inv, f], list(packed), cap,
                                           live=live)
        sla = sf // span_b + la_min
        slb = sf % span_b + lb_min
        return [sinv, sla, slb], list(svals)

    def general(args):
        inv, la, lb, packed = args
        skeys, svals = _sort_carrying([inv, la, lb], list(packed), cap,
                                      live=live)
        return list(skeys), list(svals)

    return jax.lax.cond(ok, fused, general, (inv, la, lb, tuple(packed)))


@jax.named_scope("index_sort")
def permute_by_sort(batch: Batch, key_lanes: Sequence[jax.Array],
                    count=None, stable: bool = True, live=None) -> Batch:
    """Sort the batch's rows by the given uint32 key lanes (most
    significant first; stable by default), moving ALL columns as packed
    value operands of one variadic lax.sort — zero random gathers.
    Falls back to lexsort+single-packed-gather for very wide rows, of
    the first ``live`` sorted rows alone where the caller says that the
    rest are padding (_sort_carrying)."""
    lanes, spec = _pack_columns_u32(dict(batch.columns))
    new_count = batch.count if count is None else count
    _, svals = _sort_carrying(list(key_lanes), lanes, batch.capacity,
                              stable=stable, live=live)
    return Batch(_unpack_columns_u32(svals, spec), new_count)


# ---------------------------------------------------------------------------
# filtering / compaction


@jax.named_scope("compact")
def compact(batch: Batch, keep: jax.Array) -> Batch:
    """Move rows where ``keep`` (and valid) to the front, preserving order.

    Rank-fused UNSTABLE value-carry sort: the row index rides as a
    second sort KEY, so (drop, index) is a total order — the unstable
    network produces exactly the stable compaction without paying XLA's
    stable-sort machinery (measured ~2x on the same operand set; the
    index operand replaces the iota a stable sort materializes
    internally anyway).  ``DRYAD_NO_SORT_OPT=1`` restores the stable
    1-key form (A/B lever for benchmarks/pallas_probe provenance)."""
    keep = keep & batch.valid_mask()
    n_keep = keep.sum(dtype=jnp.int32)
    if os.environ.get("DRYAD_NO_SORT_OPT"):
        return permute_by_sort(batch, ((~keep).astype(jnp.uint32),),
                               count=n_keep, live=n_keep)
    iota = jnp.arange(batch.capacity, dtype=jnp.uint32)
    # the kept rows are the first n_keep sorted rows: all that is fetched
    return permute_by_sort(batch, ((~keep).astype(jnp.uint32), iota),
                           count=n_keep, stable=False, live=n_keep)


def filter_rows(batch: Batch, predicate) -> Batch:
    """predicate: dict[str, Column] -> bool[capacity]."""
    keep = predicate(batch.columns)
    return compact(batch, keep)


@jax.named_scope("row_gather")
def take(batch: Batch, n) -> Batch:
    return batch.with_count(jnp.minimum(batch.count, jnp.asarray(n, jnp.int32)))


# ---------------------------------------------------------------------------
# sorting


def _dense_sort_lanes(col: jax.Array, descending: bool) -> List[jax.Array]:
    """Represent a dense column as a list of uint32 sort lanes (most
    significant first) whose unsigned lex order == the column's order."""
    if jnp.issubdtype(col.dtype, jnp.floating):
        f = col.astype(jnp.float32)
        bits = jax.lax.bitcast_convert_type(f, jnp.uint32)
        # flip: negative floats reverse order; standard total-order trick
        sign = (bits >> 31).astype(jnp.uint32)
        bits = jnp.where(sign == 1, ~bits, bits | jnp.uint32(0x80000000))
        lanes = [bits]
    elif col.dtype in (jnp.int64, jnp.uint64):
        u = col.astype(jnp.int64)
        hi = (u >> 32).astype(jnp.uint32)
        if col.dtype == jnp.int64:
            hi = hi ^ jnp.uint32(0x80000000)
        lo = u.astype(jnp.uint32)
        lanes = [hi, lo]
    elif jnp.issubdtype(col.dtype, jnp.signedinteger):
        lanes = [col.astype(jnp.uint32) ^ jnp.uint32(0x80000000)]
    elif col.dtype == jnp.bool_:
        lanes = [col.astype(jnp.uint32)]
    else:
        lanes = [col.astype(jnp.uint32)]
    if descending:
        lanes = [~l for l in lanes]
    return lanes


def _string_sort_lanes(col: StringColumn, descending: bool) -> List[jax.Array]:
    """Lexicographic byte order as packed uint32 lanes (4 bytes per lane).

    Shorter strings sort first among equal prefixes because padding packs
    as 0x00 bytes with the length as tiebreak.  When the last lane has at
    least two spare pad bytes, the length (u16) FOLDS into them — one
    fewer lexsort pass (every lexsort lane is a full stable device sort,
    so a 10-byte TeraSort key drops from 4 sort passes to 3).  Mirrored
    EXACTLY by exec/ooc._host_sort_lanes.
    """
    L = col.max_len
    mask = (jnp.arange(L, dtype=jnp.int32)[None, :] < col.lengths[:, None])
    b = jnp.where(mask, col.data, 0).astype(jnp.uint32)
    pad = (-L) % 4
    lens = col.lengths.astype(jnp.uint32)
    fold_len = pad >= 2 and L <= 0xFFFF
    if fold_len:
        cols = [b, (lens >> 8)[:, None], (lens & 0xFF)[:, None]]
        if pad == 3:
            cols.append(jnp.zeros((b.shape[0], 1), jnp.uint32))
        b = jnp.concatenate(cols, axis=1)
    elif pad:
        b = jnp.pad(b, ((0, 0), (0, pad)))
    b4 = b.reshape(b.shape[0], -1, 4)
    lanes = list(jnp.moveaxis(
        (b4[..., 0] << 24) | (b4[..., 1] << 16) | (b4[..., 2] << 8) | b4[..., 3],
        -1, 0))
    if not fold_len:
        lanes.append(lens)
    if descending:
        lanes = [~l for l in lanes]
    return lanes


def sort_lanes_for(col, descending: bool = False) -> List[jax.Array]:
    if isinstance(col, StringColumn):
        return _string_sort_lanes(col, descending)
    if isinstance(col, Int64Column):
        # the signed upper word, sign bit flipped, then the lower word
        lanes = [jax.lax.bitcast_convert_type(col.hi, jnp.uint32)
                 ^ jnp.uint32(0x80000000), col.lo]
        return [~l for l in lanes] if descending else lanes
    return _dense_sort_lanes(col, descending)


def _lanes_reconstructible(col) -> bool:
    """Can this column be rebuilt exactly from its sort lanes?  True for
    strings (byte lanes + length, fold or no fold) and for 1-D dense
    <=32-bit columns (the lane transforms are bijections).  64-bit ints
    are excluded: without jax x64 their lane build already degrades, so
    they keep riding the packed value path."""
    if isinstance(col, StringColumn):
        return True
    if isinstance(col, Int64Column) or col.ndim != 1:
        return False
    if col.dtype in (jnp.int64, jnp.uint64, jnp.float64):
        return False
    if col.dtype in (jnp.float16, jnp.bfloat16):
        # the float lane goes through a NUMERIC f32 cast, which
        # canonicalizes NaN payloads — not bit-injective, so half floats
        # keep riding the bit-exact packed value path (same hazard the
        # _pack_columns_u32 widening comment documents)
        return False
    return True


def _dense_lanes_invert(lanes: List[jax.Array], dtype, descending: bool
                        ) -> jax.Array:
    """Inverse of _dense_sort_lanes for the reconstructible dtypes."""
    ls = [~l for l in lanes] if descending else list(lanes)
    b = ls[0]
    if jnp.issubdtype(dtype, jnp.floating):
        # forward: neg -> ~bits, pos -> bits | 0x80000000
        neg = (b >> 31) == 0
        bits = jnp.where(neg, ~b, b ^ jnp.uint32(0x80000000))
        f = jax.lax.bitcast_convert_type(bits, jnp.float32)
        return f.astype(dtype)
    if jnp.issubdtype(dtype, jnp.signedinteger):
        return (b ^ jnp.uint32(0x80000000)).astype(dtype)
    if dtype == jnp.bool_:
        return b != 0
    return b.astype(dtype)


def _string_lanes_invert(lanes: List[jax.Array], max_len: int,
                         descending: bool) -> StringColumn:
    """Inverse of _string_sort_lanes (fold and no-fold layouts)."""
    ls = [~l for l in lanes] if descending else list(lanes)
    L = max_len
    pad = (-L) % 4
    fold_len = pad >= 2 and L <= 0xFFFF
    if fold_len:
        byte_lanes = ls
    else:
        byte_lanes, lens_lane = ls[:-1], ls[-1]
    w = jnp.stack(byte_lanes, axis=1)                      # [cap, nl] u32
    b4 = jnp.stack([(w >> 24) & 0xFF, (w >> 16) & 0xFF,
                    (w >> 8) & 0xFF, w & 0xFF], axis=2)    # [cap, nl, 4]
    flat = b4.reshape(w.shape[0], -1)
    data = flat[:, :L].astype(jnp.uint8)
    if fold_len:
        lens = ((flat[:, L] << 8) | flat[:, L + 1]).astype(jnp.int32)
    else:
        lens = lens_lane.astype(jnp.int32)
    # canonicalize: forward lanes zero bytes past the length, and invalid
    # rows may hold sentinel lanes — clamp + remask below in the caller
    return StringColumn(data, lens)


@jax.named_scope("index_sort")
def sort_by_columns(batch: Batch, keys: Sequence[Tuple[str, bool]]) -> Batch:
    """Sort valid rows by the given (column, descending) keys; padding stays
    at the end.  Stable.

    The key columns are NOT carried as packed value operands when their
    sort lanes already determine them (strings and 1-D dense <=32-bit
    columns — the lane transforms are bijections): they are rebuilt from
    the SORTED key lanes instead.  For the TeraSort shape (10-byte string
    key + i32 payload) this halves the variadic sort from 8 operands
    (3 key lanes + 5 packed) to 4 (3 key lanes + payload), and the sort
    network's cost is linear in operands (measured ~2x end-to-end).
    Two-key-lane sorts additionally RUNTIME-fuse their lanes into one
    when the measured spans fit 32 bits (_sort_fused2 — multi-key key
    packing; e.g. two small-span ints, or an i64 whose values span
    < 2^32), dropping another comparator lane.
    Reference role: the vertex sorter reads each record once
    (DryadVertex/.../recorditem.cpp:1-1140); carrying a second copy of the
    key bytes through every compare-exchange pass has no analogue there.
    """
    lanes: List[jax.Array] = []
    recon: Dict[str, Tuple[int, int, bool]] = {}
    for name, desc in keys:
        col = batch.columns[name]
        ls = sort_lanes_for(col, desc)
        if name not in recon and _lanes_reconstructible(col):
            recon[name] = (len(lanes), len(ls), desc)
        lanes.extend(ls)
    invalid = ~batch.valid_mask()
    col0 = batch.columns[keys[0][0]]
    if (len(keys) == 1 and not keys[0][1]
            and isinstance(col0, StringColumn)
            and (-col0.max_len) % 4 >= 2 and col0.max_len <= 0xFFFF):
        # single ascending folded-length string key: a VALID row's last
        # lane is strictly below 0xFFFFFFFF (its length bytes are
        # <= max_len < 0xFFFF), so setting every lane to all-ones for
        # invalid rows sorts them last EXACTLY — one fewer lexsort pass
        # (each pass is a full stable device sort; this is the TeraSort
        # shape)
        big = jnp.uint32(0xFFFFFFFF)
        lanes = [jnp.where(invalid, big, l) for l in lanes]
        base = 0
    else:
        # general case: explicit invalid flag as the most significant key
        lanes = [invalid.astype(jnp.uint32)] + lanes
        base = 1
    carry_cols = {k: v for k, v in batch.columns.items() if k not in recon}
    packed, spec = _pack_columns_u32(carry_cols)
    from dryad_tpu.ops.pallas_kernels import pallas_active
    if (base == 1 and len(lanes) == 3
            and batch.capacity <= _SORT_FUSE_MAX_CAP
            and pallas_active() is not None
            and not os.environ.get("DRYAD_NO_SORT_OPT")):
        # multi-key sort key packing: two key lanes runtime-fuse into
        # one when the measured spans allow (see _sort_fused2).  The
        # comparator-lane cost model is the TPU sort network's (cost
        # linear in operands); on cpu the fusion measured a wash
        # (BENCH_kernels r06), so it rides the same backend tier as the
        # pallas kernels.
        skeys, svals = _sort_fused2(lanes, packed, batch.capacity,
                                    live=batch.count)
    else:
        # invalid rows sort last either way: the first count are valid
        skeys, svals = _sort_carrying(lanes, packed, batch.capacity,
                                      live=batch.count)
    cols = _unpack_columns_u32(svals, spec)
    valid_sorted = jnp.arange(batch.capacity, dtype=jnp.int32) < batch.count
    for name, (off, cnt, desc) in recon.items():
        kl = skeys[base + off: base + off + cnt]
        col = batch.columns[name]
        if isinstance(col, StringColumn):
            newcol = _string_lanes_invert(kl, col.max_len, desc)
        else:
            newcol = _dense_lanes_invert(kl, col.dtype, desc)
        # padding rows may hold sentinel lanes — zero them (canonical form)
        cols[name] = _mask_rows(newcol, valid_sorted)
    return Batch(cols, batch.count)


# ---------------------------------------------------------------------------
# group-by (sort + segment reduce)


@jax.named_scope("index_sort")
def _hash_sort_segments(hi: jax.Array, lo: jax.Array, valid: jax.Array,
                        extra_lanes: Tuple[jax.Array, ...] = ()):
    """Shared segment machinery: sort rows by 64-bit hash (invalid last),
    label equal-hash runs among valid rows as segments.  ``extra_lanes``
    are uint32 lanes LEAST significant first, ordering rows WITHIN a key
    segment (the group-contents family sorts segments by a value column).

    Returns (order, seg, is_start, num_groups); seg for invalid rows is n
    (out of range — dropped by segment reductions).

    Grouping is by the full 64-bit key hash (both uint32 lanes) without
    true-key verification: two distinct keys colliding in all 64 bits would
    be merged.  P(any collision) ~ n^2/2^64 per partition — negligible at
    per-partition sizes (1e-9 even for 100M-row partitions).

    Invalid rows sort last by FOLDING the all-ones sentinel into the hash
    lanes instead of adding an invalid lane — one fewer lexsort pass on
    every group/distinct/semi-join (each pass is a full stable device
    sort).  A valid row whose 64-bit hash is exactly all-ones would sort
    among the padding and drop — P ~ n/2^64, strictly smaller than the
    collision-merge budget above.
    """
    n = hi.shape[0]
    hi, lo = _sentinel_fold(hi, lo, valid)
    order = jnp.lexsort(tuple(extra_lanes) + (lo, hi))
    shi, slo = jnp.take(hi, order), jnp.take(lo, order)
    svalid = jnp.take(valid, order)
    differs = _lane_differs(shi, slo)
    is_start = svalid & differs
    seg = jnp.cumsum(is_start.astype(jnp.int32)) - 1
    seg = jnp.where(svalid, seg, n)
    num_groups = is_start.sum(dtype=jnp.int32)
    return order, seg, is_start, num_groups


def _group_segments(batch: Batch, key_names: Sequence[str]):
    """Sort batch by key hash; return (sorted batch, seg_id, is_start,
    num_groups).  See _hash_sort_segments for collision semantics."""
    hi, lo = hash_batch_keys(batch, key_names)
    order, seg, is_start, num_groups = _hash_sort_segments(
        hi, lo, batch.valid_mask())
    return batch.gather(order), seg, is_start, num_groups


def _first_row_per_segment(is_start: jax.Array,
                           num_groups: jax.Array) -> jax.Array:
    """Index of the first (sorted) row of each segment; 0 past num_groups.
    Scatter-free: the g-th True in ``is_start`` is segment g's first row
    (TPU scatters serialize; the bool argsort rides the vector units)."""
    cap = is_start.shape[0]
    start_pos = jnp.argsort(~is_start, stable=True).astype(jnp.int32)
    return jnp.where(jnp.arange(cap) < num_groups, start_pos, 0)


def _segment_bounds(is_start: jax.Array, num_groups: jax.Array,
                    n_valid: jax.Array):
    """(start_pos, end_excl) per segment slot, scatter-free.

    Rows are segment-sorted (valid first), so the g-th True in ``is_start``
    is segment g's first row: a stable argsort of ``~is_start`` lists those
    positions in order — one cheap bool sort instead of a segment_min
    SCATTER (TPU scatters serialize; sorts ride the vector units)."""
    cap = is_start.shape[0]
    start_pos = jnp.argsort(~is_start, stable=True).astype(jnp.int32)
    idx = jnp.arange(cap, dtype=jnp.int32)
    nxt = jnp.roll(start_pos, -1)
    end_excl = jnp.where(idx + 1 < num_groups, nxt, n_valid)
    return start_pos, end_excl


def _seg_sum_sorted(v: jax.Array, start_pos, end_excl, num_groups,
                    n_valid) -> jax.Array:
    """Segment sums over segment-sorted rows via cumsum boundary
    differences — no scatter.  Exact for integer dtypes (two's-complement
    wraparound cancels in the difference); float32 sums trade the
    per-segment accumulation order for a global prefix (documented on
    group_aggregate)."""
    cap = v.shape[0]
    idx = jnp.arange(cap, dtype=jnp.int32)
    mask = (idx < n_valid).reshape((cap,) + (1,) * (v.ndim - 1))
    c = jnp.cumsum(jnp.where(mask, v, 0), axis=0)
    top = jnp.take(c, jnp.clip(end_excl - 1, 0, cap - 1), axis=0)
    bot_i = start_pos - 1
    bot = jnp.take(c, jnp.clip(bot_i, 0, cap - 1), axis=0)
    bot = jnp.where((bot_i >= 0).reshape((cap,) + (1,) * (v.ndim - 1)),
                    bot, 0)
    out = top - bot
    gmask = (idx < num_groups).reshape((cap,) + (1,) * (v.ndim - 1))
    return jnp.where(gmask, out, 0)


def _neutral_for(kind: str, dtype):
    if kind in ("sum", "count"):
        return 0
    if kind == "min":
        return jnp.finfo(dtype).max if jnp.issubdtype(dtype, jnp.floating) \
            else jnp.iinfo(dtype).max
    if kind == "max":
        return jnp.finfo(dtype).min if jnp.issubdtype(dtype, jnp.floating) \
            else jnp.iinfo(dtype).min
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# 64-bit integer sums on two 32-bit words (the package runs without x64)
#
# ``("sum64", column)`` over a 1-D integer column of at most 32 bits, and
# ``("sum", column)`` over an Int64Column (the merge of partial wide
# sums), give an Int64Column: exact modulo 2**64, so exact for any 2**24
# rows of any int32 values (|total| < 2**55).


def _is_wide_sum(kind: str, col) -> bool:
    return kind == "sum64" or (kind == "sum"
                               and isinstance(col, Int64Column))


def _wide_words(col) -> Tuple[jax.Array, jax.Array]:
    """(upper word int32, lower word uint32) of the 64-bit values a wide
    sum adds up: an Int64Column as it is, a narrower integer column
    (_check_wide_aggs has seen to that) sign- or zero-extended."""
    if isinstance(col, Int64Column):
        return col.hi, col.lo
    if jnp.issubdtype(col.dtype, jnp.unsignedinteger):
        return (jnp.zeros(col.shape, jnp.int32), col.astype(jnp.uint32))
    v = col.astype(jnp.int32)
    return v >> 31, jax.lax.bitcast_convert_type(v, jnp.uint32)


def _wide_add(a, b):
    """(hi, lo) + (hi, lo) modulo 2**64 — associative, so it serves a
    scan and a reduce."""
    lo = a[1] + b[1]
    return a[0] + b[0] + (lo < b[1]).astype(jnp.int32), lo


def _wide_sub(a, b):
    """(hi, lo) - (hi, lo) modulo 2**64: the lower words' difference and
    its borrow."""
    return (a[0] - b[0] - (a[1] < b[1]).astype(jnp.int32), a[1] - b[1])


@jax.named_scope("prefix_sum")
def _wide_prefix(hi: jax.Array, lo: jax.Array):
    """Inclusive prefix sums of 64-bit values held as words, in two
    streamed 32-bit passes: the lower word is the wrapping uint32 prefix;
    a step carried where that prefix came out below what it added; the
    upper word is the prefix of the values' upper words and the carries."""
    from dryad_tpu.ops.pallas_kernels import prefix_sum
    plo = prefix_sum(lo)
    return prefix_sum(hi + (plo < lo).astype(jnp.int32)), plo


def _check_wide_aggs(batch: Batch, aggs) -> None:
    """An Int64Column can be summed and nothing else (no lowering holds a
    64-bit min/max/mean); ``sum64`` reads a narrow integer column."""
    for _out, (kind, vname) in aggs.items():
        if kind == "count":
            continue
        col = batch.columns[vname]
        if isinstance(col, Int64Column) and kind != "sum":
            raise ValueError(f"aggregate {kind!r} over the 64-bit integer "
                             f"column {vname!r}: only sum is provided")
        if kind == "sum64" and (
                isinstance(col, (StringColumn, Int64Column))
                or col.ndim != 1
                or not jnp.issubdtype(col.dtype, jnp.integer)
                or col.dtype.itemsize > 4):
            raise ValueError("a 64-bit sum (sum64) needs a 1-D integer "
                             f"column of at most 32 bits, got {col!r}")


def _boundary_eligible(batch: Batch, aggs) -> Tuple[bool, str | None]:
    """Can this agg set run on the boundary-carry path?  Returns
    (ok, the single min/max order column or None).  Requirements: sum/
    mean/any/all columns are 1-D 4-byte dense (native prefix_sum dtypes);
    all min/max aggregates share ONE 1-D single-lane reconstructible
    column (it rides as a sort key; its extremes then sit at segment
    boundaries).  Everything else falls back to the segmented-scan path."""
    minmax: set = set()
    for _out, (kind, vname) in aggs.items():
        if kind == "count":
            continue
        col = batch.columns[vname]
        if _is_wide_sum(kind, col):
            continue
        if isinstance(col, StringColumn) or col.ndim != 1:
            return False, None
        if kind in ("sum", "mean"):
            if col.dtype.itemsize != 4:
                return False, None
        elif kind in ("min", "max"):
            if not _lanes_reconstructible(col) \
                    or len(_dense_sort_lanes(col, False)) != 1:
                return False, None
            minmax.add(vname)
        elif kind in ("any", "all"):
            pass
        else:
            return False, None
    if len(minmax) > 1:
        return False, None
    return True, (next(iter(minmax)) if minmax else None)


def _shift_fwd(a: jax.Array, fill) -> jax.Array:
    """[fill, a[0], ..., a[-2]] — previous-row view on dense outputs."""
    return jnp.concatenate([jnp.full((1,), fill, a.dtype), a[:-1]])


def _live_rows(batch: Batch, where):
    """(valid mask, valid count) of a group-by's input rows: the batch's
    own prefix, less the rows a ``where`` mask drops.  ``where=None``
    traces exactly what the lowerings traced before they took a mask."""
    valid = batch.valid_mask()
    if where is None:
        return valid, batch.count
    valid = valid & where
    return valid, valid.sum(dtype=jnp.int32)


@jax.named_scope("group_aggregate")
def group_aggregate(batch: Batch, key_names: Sequence[str],
                    aggs: Dict[str, Tuple[str, str | None]],
                    where=None) -> Batch:
    """GroupBy + decomposable aggregation.

    aggs: out_name -> (kind, value_column | None).  Kinds: sum, count, min,
    max, mean, any, all, sum64.  Output batch has the key columns (one
    representative row per group) plus one column per aggregate; count =
    number of groups.

    ``sum`` accumulates in its column's own type (an ``int32`` sum wraps);
    ``sum64`` over an integer column of at most 32 bits, and ``sum`` over
    an Int64Column (the merge of partial 64-bit sums), accumulate in two
    32-bit words and give an Int64Column, exact modulo 2**64, on the
    boundary and the scan lowering (the one-hot path takes float sums
    only).

    where: optional ``bool[capacity]`` row mask (a scalar broadcasts) —
    the groups of the rows that are valid AND kept, i.e. of
    ``compact(batch, where)``, without the compaction: every lowering
    reads a row's validity from a mask and never from its position (the
    sort-based ones send a dropped row to the back in their own segment
    sort, the one-hot one gives it the slot that matches nothing and
    zeroes its values), so a filter in front of a group-by only has to
    change that mask (exec.executor._fuse_stage_ops).  A dropped row may
    hold anything, NaN and inf included.  Float sums can differ from the
    compacted form's in the last ulp (rows meet the unstable segment
    sort in another order); counts, min/max and keys are exact.

    This is the map-side combine of the reference's IDecomposable protocol
    (reference LinqToDryad/IDecomposable.cs:34): all kinds here are
    associative, so re-applying the same kernel after a shuffle (with sum for
    count/mean-parts) merges partial aggregates — that is how the distributed
    GroupBy works (planner splits it into local combine -> shuffle -> merge).

    Lowering: a small-span integer key, or short keys (``_short_key``)
    whose spans multiply to a small number, take the one-hot MXU path (a
    runtime span check, _group_aggregate_smallkey); then the
    boundary-carry path when the agg set allows it; else the
    segmented-scan path (_group_aggregate_scan).

    NaN note: the boundary path ranks float min/max by the total order
    -NaN < -inf < ... < +inf < +NaN (the IEEE totalOrder the sort lanes
    induce — and the comparer order the reference's LINQ Min/Max uses),
    while the scan path's jnp.minimum/maximum PROPAGATE any NaN to both
    extremes.  Groups containing NaN can therefore answer differently
    across the two lowerings; all other inputs agree exactly.
    """
    _check_wide_aggs(batch, aggs)
    ok, minmax_col = _boundary_eligible(batch, aggs)
    if ok:
        fallback = lambda b: _group_aggregate_boundary(  # noqa: E731
            b, key_names, aggs, minmax_col, where)
    else:
        fallback = lambda b: _group_aggregate_scan(  # noqa: E731
            b, key_names, aggs, where)
    if _matmul_group_eligible(batch, key_names, aggs):
        return _group_aggregate_smallkey(batch, key_names, aggs, fallback,
                                         where)
    return fallback(batch)


_SMALLKEY_SLOTS = 512      # one-hot width: span <= this rides the MXU
_SMALLKEY_CHUNK = 16384    # rows per accumulation step (bounds the
                           # materialized [chunk, slots] one-hot to 32 MB)


def _short_key(col) -> bool:
    """A grouping key whose values span at most 2^16 whatever they are: a
    bool, an integer of at most 16 bits, a string of at most one byte
    (SQL's ``CHAR(1)``).  Keys that are all short can share one
    mixed-radix slot of the one-hot lowering."""
    if isinstance(col, StringColumn):
        return col.max_len == 1
    if isinstance(col, Int64Column) or col.ndim != 1:
        return False
    return col.dtype == jnp.bool_ or (
        jnp.issubdtype(col.dtype, jnp.integer) and col.dtype.itemsize <= 2)


def _matmul_group_eligible(batch: Batch, key_names, aggs) -> bool:
    """Static half of the MXU group gate: one integer dense key of any
    width, or keys that are all short (``_short_key``); sums/means over
    4-byte float columns only (a float accumulator is not exact for wide
    integers), a partition below 2^24 rows (a mean divides by its count
    in float32).  From shapes and dtypes alone: a key set that fails it
    traces no span check and no second branch."""
    cols = [batch.columns[k] for k in key_names]
    one_int = (_dense_fast_key(batch, key_names)
               and jnp.issubdtype(cols[0].dtype, jnp.integer))
    if not (one_int or (cols and all(_short_key(c) for c in cols))):
        return False
    if batch.capacity >= (1 << 24):
        return False
    for _out, (kind, vname) in aggs.items():
        if kind == "count":
            continue
        if kind not in ("sum", "mean"):
            return False
        col = batch.columns[vname]
        if isinstance(col, (StringColumn, Int64Column)) or \
                not jnp.issubdtype(col.dtype, jnp.floating) or \
                col.dtype.itemsize != 4:
            return False
    return True


def _slot_digit(col) -> jax.Array:
    """A key's digit of the one-hot slot, in the key's own order: a
    one-byte string is 0 when empty, else its byte + 1 (the empty string
    first, as _string_sort_lanes orders it); a bool or a short integer
    its value as int32; a 32-bit integer key its value."""
    if isinstance(col, StringColumn):
        return jnp.where(col.lengths > 0,
                         col.data[:, 0].astype(jnp.int32) + 1, 0)
    if col.dtype.itemsize < 4:
        return col.astype(jnp.int32)
    return col


def _digit_column(d: jax.Array, like):
    """The key column of digits ``d`` (_slot_digit's inverse)."""
    if isinstance(like, StringColumn):
        return StringColumn(
            jnp.maximum(d - 1, 0).astype(jnp.uint8)[:, None],
            (d > 0).astype(like.lengths.dtype))
    return d.astype(like.dtype)


def _group_aggregate_smallkey(batch: Batch, key_names: Sequence[str],
                              aggs: Dict[str, Tuple[str, str | None]],
                              fallback, where=None) -> Batch:
    """One-hot MXU group aggregation for keys of a small span.

    The sort-based lowerings pay ~log^2(n) compare-exchange stages per
    row; when the key span fits ``_SMALLKEY_SLOTS``, per-group sums are
    ONE matmul against the one-hot slot matrix — the systolic array does
    the scatter-add the chip has no scatter unit for (k-means recenter,
    reference role: the broadcast/aggregation ML loops of BASELINE
    config 5).  Several keys share one mixed-radix slot, the first key
    most significant: ``sum((digit_i - min_i) * prod(span_j, j > i))``
    over their live rows' digits (_slot_digit), so the groups come out in
    the keys' lexicographic order and each key is rebuilt from its
    digit.  The span is a runtime property, so the choice is a lax.cond
    against the sort fallback: a batch whose spans multiply past the
    slots pays one min/max reduction a key, nothing else.

    Sums accumulate chunk by chunk in a compensated (hi, lo) pair
    (pallas_kernels._dd_add): 733 chunk sums of a 12 M-row batch added
    into one float32 would lose up to ~733 roundings of the group's sum;
    the pair keeps the error near one.  Counts accumulate in int32.
    """
    from dryad_tpu.ops.pallas_kernels import _dd_add
    keys = [batch.columns[k] for k in key_names]
    cap = batch.capacity
    valid, n_valid = _live_rows(batch, where)
    S = _SMALLKEY_SLOTS
    use = n_valid > 0
    mins, spans = [], []
    for d in map(_slot_digit, keys):
        dmin = jnp.min(jnp.where(valid, d, jnp.iinfo(d.dtype).max))
        dmax = jnp.max(jnp.where(valid, d, jnp.iinfo(d.dtype).min))
        # i32 wraparound on huge true spans lands negative -> fallback
        span = dmax - dmin + 1
        use = use & (dmax >= dmin) & (span > 0) & (span <= S)
        mins.append(dmin)
        spans.append(span)
    if len(keys) > 1:
        # short keys: each span <= 2^16 + 1, the running product is
        # clamped past the slots, so nothing here wraps
        slots = jnp.ones((), jnp.int32)
        for span in spans:
            slots = jnp.minimum(slots * jnp.clip(span, 0, S + 1), S + 1)
        use = use & (slots <= S)
    _tally("onehot", use)

    def mm_branch(b: Batch) -> Batch:
        slot = None
        for k, dmin, span in zip(key_names, mins, spans):
            off = (_slot_digit(b.columns[k]) - dmin).astype(jnp.int32)
            slot = off if slot is None else slot * span + off
        slot = jnp.clip(slot, 0, S - 1)
        slot = jnp.where(valid, slot, S)          # padding matches nothing
        vals: Dict[str, jax.Array] = {}
        shapes: Dict[str, Tuple] = {}
        for _o, (kind, vname) in aggs.items():
            if kind != "count" and vname not in vals:
                v = b.columns[vname]
                shapes[vname] = v.shape[1:]
                # padding rows hold unspecified bytes (inf/NaN included);
                # a zero one-hot row does NOT neutralize them in the
                # contraction (0 * NaN = NaN) — zero the values themselves
                v = _mask_rows(v, valid)
                vals[vname] = v.reshape(cap, -1)
        names = list(vals)
        m_tot = sum(vals[n].shape[1] for n in names) if names else 0
        pad = (-cap) % _SMALLKEY_CHUNK
        nb = (cap + pad) // _SMALLKEY_CHUNK
        slot_p = jnp.pad(slot, (0, pad), constant_values=S) \
            .reshape(nb, _SMALLKEY_CHUNK)
        if names:
            vcat = jnp.concatenate([vals[n] for n in names], axis=1)
            vcat = jnp.pad(vcat, ((0, pad), (0, 0))) \
                .reshape(nb, _SMALLKEY_CHUNK, m_tot)

        def step(acc, xs):
            cnt_acc, sum_hi, sum_lo = acc
            sl = xs[0]
            hit = sl[:, None] == jnp.arange(S, dtype=jnp.int32)[None, :]
            cnt_acc = cnt_acc + jnp.sum(hit, axis=0, dtype=jnp.int32)
            if names:
                part = jax.lax.dot_general(
                    hit.astype(jnp.float32), xs[1], (((0,), (0,)), ((), ())),
                    precision=jax.lax.Precision.HIGHEST)  # [S, m]
                sum_hi, sum_lo = _dd_add(sum_hi, sum_lo, part,
                                         jnp.zeros_like(part))
            return (cnt_acc, sum_hi, sum_lo), None

        zeros = jnp.zeros((S, max(m_tot, 1)), jnp.float32)
        (cnts, sum_hi, sum_lo), _ = jax.lax.scan(
            step, (jnp.zeros((S,), jnp.int32), zeros, zeros),
            (slot_p, vcat) if names else (slot_p,))
        sums = sum_hi + sum_lo
        nonempty = cnts > 0
        num_groups = nonempty.sum(dtype=jnp.int32)
        order = jnp.argsort(~nonempty, stable=True)       # [S], tiny
        rank = jnp.arange(S, dtype=jnp.int32)
        gvalid_s = rank < num_groups

        def place(a_s):
            """[S, ...] slot-ordered -> [cap, ...] group-compacted."""
            g = jnp.take(a_s, order, axis=0)
            g = _mask_rows(g, gvalid_s)
            if cap >= S:
                padw = ((0, cap - S),) + ((0, 0),) * (g.ndim - 1)
                return jnp.pad(g, padw)
            return g[:cap]

        # each slot's digits: mixed radix, the last key least significant
        if len(keys) == 1:
            digits = [mins[0] + rank]
        else:
            digits, r = [], rank
            for dmin, span in zip(mins[::-1], spans[::-1]):
                span = jnp.maximum(span, 1)
                digits.insert(0, dmin + r % span)
                r = r // span
        out_cols: Dict[str, Any] = {}
        for k, col, d in zip(key_names, keys, digits):
            out_cols[k] = jax.tree.map(place, _digit_column(d, col))
        cnt_g = place(cnts)
        off = 0
        col_sums: Dict[str, jax.Array] = {}
        for n in names:
            m = vals[n].shape[1]
            col_sums[n] = place(sums[:, off:off + m]) \
                .reshape((cap,) + shapes[n])
            off += m
        for out_name, (kind, vname) in aggs.items():
            if kind == "count":
                out_cols[out_name] = cnt_g
            elif kind == "sum":
                out_cols[out_name] = col_sums[vname]
            else:  # mean
                c = jnp.maximum(cnt_g, 1).reshape(
                    (cap,) + (1,) * len(shapes[vname]))
                out_cols[out_name] = col_sums[vname] / c.astype(jnp.float32)
        return Batch(out_cols, num_groups)

    return jax.lax.cond(use, mm_branch, fallback, batch)


def _group_aggregate_boundary(batch: Batch, key_names: Sequence[str],
                              aggs: Dict[str, Tuple[str, str | None]],
                              minmax_col: str | None, where=None) -> Batch:
    """Boundary-carry group aggregation — scan-free.

    The round-4 profile (scratch probes, re-runnable via
    benchmarks/pallas_probe.py methodology) showed the segmented-scan
    lowering spending only 0.11 ms of its 2.55 ms in the segment sort at
    500k rows: the associative scans (0.80 ms, log-depth HBM passes) and
    the densify sort's carried aggregate lanes dominated.  This path
    removes the scans entirely:

      * the min/max order column rides as an extra SORT KEY, so each
        segment's min sits at its first row and its max at its last —
        no scan, and the column is rebuilt from its own sorted lane;
      * sums ride as ONE global prefix_sum (pallas streaming scan,
        ops/pallas_kernels — 4.5x XLA's cumsum); per-group sums are then
        ADJACENT DIFFERENCES of the csum lane on the DENSE output rows
        (integer-exact; f32 inherits the global-prefix cancellation
        bound documented on _seg_sum_sorted);
      * counts are adjacent differences of the carried row index —
        segments tile the valid prefix, so end_idx[g] - end_idx[g-1] is
        exactly group g's size;
      * group g's MIN is the order lane of the row AFTER segment g-1's
        end — carried as a shifted lane and read off the previous dense
        row (group 0 reads sorted row 0).

    One unstable segment sort + one stable boundary densify + one
    streamed prefix pass — nothing else touches HBM.
    """
    valid, n_valid = _live_rows(batch, where)
    cap = batch.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)

    kcol0 = batch.columns[key_names[0]]
    dense_fast = _dense_fast_key(batch, key_names)

    # --- sort keys: grouping lanes (+ the min/max order lane) ----------
    if dense_fast:
        klane = _dense_key_lane(kcol0)
        key_lanes = [(~valid).astype(jnp.uint32), klane]
        n_group_lanes = 2
    else:
        hi, lo = hash_batch_keys(batch, key_names)
        hi_s, lo_s = _sentinel_fold(hi, lo, valid)
        key_lanes = [hi_s, lo_s]
        n_group_lanes = 2
    if minmax_col is not None:
        key_lanes.append(_dense_sort_lanes(batch.columns[minmax_col],
                                           False)[0])

    # --- carries: native-dtype lanes for each summed column ------------
    def _as_u32(a):
        return jax.lax.bitcast_convert_type(a, jnp.uint32) \
            if a.dtype != jnp.uint32 else a

    sum_cols: Dict[str, jax.Array] = {}     # cumsum inputs, native dtype
    wide: set = set()       # the entries summed in 64 bits ("#w:" + name)
    for _out, (kind, vname) in aggs.items():
        if kind != "count" and _is_wide_sum(kind, batch.columns[vname]):
            sum_cols["#w:" + vname] = batch.columns[vname]
            wide.add("#w:" + vname)
        elif kind in ("sum", "mean") and vname not in sum_cols:
            sum_cols[vname] = batch.columns[vname]
        elif kind in ("any", "all"):
            ik = "#i:" + vname
            if ik not in sum_cols:
                sum_cols[ik] = batch.columns[vname].astype(jnp.int32)
    # the min/max column's order lane already determines its values
    # (bijection), so when it is ALSO summed it does not ride as a carry:
    # the sorted column is rebuilt from the sorted key lane instead —
    # one fewer sort operand (sort cost is linear in operands, measured)
    rebuild_sum = (minmax_col is not None and minmax_col in sum_cols)
    carry = [_as_u32(lane) for name, v in sum_cols.items()
             if not (rebuild_sum and name == minmax_col)
             for lane in jax.tree.leaves(v)]
    if dense_fast:
        pack_spec = None
    else:
        kp, pack_spec = _pack_columns_u32(
            {k: batch.columns[k] for k in key_names})
        carry = kp + carry

    # dropped and padding rows sort last: the first n_valid are live
    skeys, scarry = _sort_carrying(key_lanes, carry, cap, stable=False,
                                   live=n_valid)
    if dense_fast:
        skey = skeys[1]
        differs = _lane_differs(skey)
    else:
        differs = _lane_differs(skeys[0], skeys[1])
    _is_start, is_end, num_groups = _segment_flags(differs, n_valid)
    svord = skeys[n_group_lanes] if minmax_col is not None else None

    # --- streamed prefix sums over the sorted value lanes ---------------
    # f32 prefixes are COMPENSATED (hi, lo) pairs: the adjacent-difference
    # group sums below would otherwise carry error proportional to the
    # GLOBAL prefix magnitude — unbounded relative to a small group's own
    # sum (pallas_kernels.prefix_sum2).  Integer prefixes are exact under
    # modular wraparound and ride the plain scan.
    from dryad_tpu.ops.pallas_kernels import prefix_sum, prefix_sum2
    n_pack = 0 if dense_fast else sum(s[3] for s in pack_spec)
    svalid = idx < n_valid
    csums: Dict[str, Tuple[jax.Array, ...]] = {}
    j = 0
    for name, v in sum_cols.items():
        if name in wide:
            if isinstance(v, Int64Column):
                sv = Int64Column(jax.lax.bitcast_convert_type(
                    scarry[n_pack + j], jnp.int32), scarry[n_pack + j + 1])
                j += 2
            else:
                sv = scarry[n_pack + j]
                j += 1
                if v.dtype != jnp.uint32:
                    sv = jax.lax.bitcast_convert_type(sv, v.dtype)
            csums[name] = _wide_prefix(
                *(jnp.where(svalid, w, 0) for w in _wide_words(sv)))
            continue
        if rebuild_sum and name == minmax_col:
            sv = _dense_lanes_invert([svord], v.dtype, False)
        else:
            sv = scarry[n_pack + j]
            j += 1
            if v.dtype != jnp.uint32:
                sv = jax.lax.bitcast_convert_type(sv, v.dtype)
        masked = jnp.where(svalid, sv, jnp.zeros((), v.dtype))
        if v.dtype == jnp.float32:
            csums[name] = prefix_sum2(masked)
        else:
            csums[name] = (prefix_sum(masked),)

    # --- densify segment-END rows to the front (group order) ------------
    dlanes: List[jax.Array] = []
    if dense_fast:
        dlanes.append(skey)
    else:
        dlanes.extend(scarry[:n_pack])
    if minmax_col is not None:
        dlanes.append(svord)
        # order-lane of the row after each end = next segment's min
        dlanes.append(jnp.concatenate([svord[1:], svord[-1:]]))
    cs_off: Dict[str, int] = {}
    for name in sum_cols:
        cs_off[name] = len(dlanes)
        dlanes.extend(_as_u32(lane) for lane in csums[name])
    # UNSTABLE 2-key sort: the row index is both the order tiebreak
    # (so end-rows keep group order deterministically) and the count
    # payload — one operand doing double duty vs a stable 1-key sort
    # (XLA's stable sort pays for an internal iota anyway, measured)
    dkeys, dl = _sort_carrying(
        [(~is_end).astype(jnp.uint32), idx.astype(jnp.uint32)],
        dlanes, cap, stable=False, live=num_groups)
    didx_lane = dkeys[1]

    gmask = idx < num_groups
    out_cols: Dict[str, Any] = {}
    if dense_fast:
        out_cols[key_names[0]] = _mask_rows(
            _dense_lanes_invert([dl[0]], kcol0.dtype, False), gmask)
        p = 1
    else:
        kcols = _unpack_columns_u32(dl[:n_pack], pack_spec)
        for k in key_names:
            out_cols[k] = _mask_rows(kcols[k], gmask)
        p = n_pack
    if minmax_col is not None:
        mm_dtype = batch.columns[minmax_col].dtype
        vmax = _dense_lanes_invert([dl[p]], mm_dtype, False)
        minfeed = _shift_fwd(dl[p + 1], 0)
        vmin = _dense_lanes_invert([minfeed], mm_dtype, False)
        # group 0's min = the very first sorted row's order lane
        v0 = _dense_lanes_invert([svord[0:1]], mm_dtype, False)[0]
        vmin = jnp.where(idx == 0, v0, vmin)
        p += 2
    dcs: Dict[str, jax.Array] = {}
    for name, v in sum_cols.items():
        o = cs_off[name]
        c = dl[o]
        if name in wide:
            # a group's sum: its end row's prefix less the previous
            # group's, the borrow taken off the upper word
            c = (jax.lax.bitcast_convert_type(c, jnp.int32), dl[o + 1])
            dcs[name] = Int64Column(*_wide_sub(
                c, (_shift_fwd(c[0], 0), _shift_fwd(c[1], 0))))
            continue
        if v.dtype != jnp.uint32:
            c = jax.lax.bitcast_convert_type(c, v.dtype)
        if v.dtype == jnp.float32:
            clo = jax.lax.bitcast_convert_type(dl[o + 1], jnp.float32)
            # difference BOTH compensated lanes: error ~ ulp(group sum)
            dcs[name] = ((c - _shift_fwd(c, 0))
                         + (clo - _shift_fwd(clo, 0)))
        else:
            dcs[name] = c - _shift_fwd(c, 0)
    didx = didx_lane.astype(jnp.int32)
    cnt_g = didx - _shift_fwd(didx, -1)

    for out_name, (kind, vname) in aggs.items():
        if kind == "count":
            o = cnt_g
        elif _is_wide_sum(kind, batch.columns[vname]):
            o = dcs["#w:" + vname]
        elif kind == "sum":
            o = dcs[vname]
        elif kind == "mean":
            s = dcs[vname]
            c = jnp.maximum(cnt_g, 1)
            o = s / c.astype(s.dtype) \
                if jnp.issubdtype(s.dtype, jnp.floating) \
                else s.astype(jnp.float32) / c
        elif kind == "min":
            o = vmin
        elif kind == "max":
            o = vmax
        elif kind == "any":
            o = dcs["#i:" + vname] > 0
        elif kind == "all":
            o = dcs["#i:" + vname] == cnt_g
        out_cols[out_name] = _mask_rows(o, gmask)
    return Batch(out_cols, num_groups)


def _group_aggregate_scan(batch: Batch, key_names: Sequence[str],
                          aggs: Dict[str, Tuple[str, str | None]],
                          where=None) -> Batch:
    """Segmented-scan group aggregation — the general path (2-D value
    columns, 8-byte sums, string or multi-column min/max)."""
    # Scatter- and gather-free lowering (TPU: scatters serialize, random
    # gathers cost ~9 ns/row): ONE variadic sort carries the agg value
    # columns as packed words alongside the grouping lanes; segmented
    # associative scans produce running reduces whose per-group totals sit
    # at each segment's LAST row; a second value-carry sort on the is_end
    # flag densifies those rows to the front in group order.
    #
    # Dense-key fast path: a single <=32-bit dense key groups by its EXACT
    # order lane — no hashing (exact, no 64-bit collision budget), the key
    # column rides as one raw lane and is rebuilt from the sorted lane,
    # and the segment sort runs UNSTABLE (measured ~2x cheaper; nothing
    # observes in-segment value order).
    valid, n_valid = _live_rows(batch, where)
    cap = batch.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)

    kcol0 = batch.columns[key_names[0]]
    dense_fast = _dense_fast_key(batch, key_names)

    needed_vals = list(dict.fromkeys(
        v for _, v in aggs.values() if v and v not in
        (key_names if dense_fast else ())))
    if dense_fast:
        needed = needed_vals
    else:
        needed = list(dict.fromkeys(list(key_names) + needed_vals))
    lanes, spec = _pack_columns_u32({k: batch.columns[k] for k in needed})
    if dense_fast:
        key_lane = _dense_key_lane(kcol0)
        skey, slanes, is_start, is_end, num_groups = _sort_segments_dense(
            key_lane, valid, n_valid, lanes)
    else:
        hi, lo = hash_batch_keys(batch, key_names)
        skey = None
        slanes, is_start, is_end, num_groups = _sort_segments_carry(
            hi, lo, valid, n_valid, lanes, stable=False)
    scols = _unpack_columns_u32(slanes, spec)
    if dense_fast and key_names[0] in (v for _, v in aggs.values() if v):
        # the key column doubles as an agg value (e.g. count over key):
        # rebuild its sorted version from the key lane
        scols[key_names[0]] = _dense_lanes_invert([skey], kcol0.dtype,
                                                  False)

    # every aggregate's running reduce rides ONE fused segmented scan
    # (shared log(cap) passes + boundary carry — the scans dominate this
    # kernel's device time at millions of rows, measured ~2 ms per extra
    # scan at 2M)
    scan_in: List[Tuple[jax.Array, Any]] = [
        ((idx < n_valid).astype(jnp.int32), jnp.add)]   # run_cnt
    slots: Dict[Tuple[str, str | None], int] = {}

    def _slot(kind, vname, arr, op):
        k = (kind, vname)
        if k not in slots:
            slots[k] = len(scan_in)
            scan_in.append((arr, op))
        return slots[k]

    for out_name, (kind, vname) in aggs.items():
        if kind == "count":
            continue
        if _is_wide_sum(kind, scols[vname]):
            _slot("sum64", vname, _wide_words(scols[vname]), _wide_add)
        elif kind in ("sum", "mean"):
            _slot("sum", vname, scols[vname], jnp.add)
        elif kind == "min":
            _slot("min", vname, scols[vname], jnp.minimum)
        elif kind == "max":
            _slot("max", vname, scols[vname], jnp.maximum)
        elif kind in ("any", "all"):
            _slot("isum", vname, scols[vname].astype(jnp.int32), jnp.add)
        else:
            raise ValueError(f"unknown aggregate kind {kind}")
    scanned = _seg_scan_multi(scan_in, is_start)
    run_cnt = scanned[0]

    dense_in: Dict[str, Any] = ({} if dense_fast
                                else {k: scols[k] for k in key_names})
    for out_name, (kind, vname) in aggs.items():
        if kind == "count":
            o = run_cnt
        elif _is_wide_sum(kind, scols[vname]):
            o = Int64Column(*scanned[slots[("sum64", vname)]])
        elif kind in ("sum", "mean"):
            s = scanned[slots[("sum", vname)]]
            if kind == "sum":
                o = s
            else:
                c = jnp.maximum(run_cnt, 1).reshape(
                    (cap,) + (1,) * (s.ndim - 1))
                o = s / c.astype(s.dtype) \
                    if jnp.issubdtype(s.dtype, jnp.floating) \
                    else s.astype(jnp.float32) / c
        elif kind == "min":
            o = scanned[slots[("min", vname)]]
        elif kind == "max":
            o = scanned[slots[("max", vname)]]
        elif kind == "any":
            o = scanned[slots[("isum", vname)]] > 0
        elif kind == "all":
            o = scanned[slots[("isum", vname)]] == run_cnt
        else:
            raise ValueError(f"unknown aggregate kind {kind}")
        dense_in[out_name] = o

    lanes2, spec2 = _pack_columns_u32(dense_in)
    if dense_fast:
        lanes2 = [skey] + lanes2
    _, svals2 = _sort_carrying([(~is_end).astype(jnp.uint32)], lanes2, cap,
                               live=num_groups)
    if dense_fast:
        skey2, svals2 = svals2[0], svals2[1:]
    dcols = _unpack_columns_u32(svals2, spec2)
    gmask = idx < num_groups
    out_cols = {name: _mask_rows(v, gmask) for name, v in dcols.items()}
    if dense_fast:
        out_cols[key_names[0]] = _mask_rows(
            _dense_lanes_invert([skey2], kcol0.dtype, False), gmask)
    return Batch(out_cols, num_groups)


def _mask_rows(col, keep: jax.Array):
    """Zero rows where ``keep`` is False (strings get zero data+length)."""
    if isinstance(col, StringColumn):
        m2 = keep.reshape(-1, 1)
        return StringColumn(jnp.where(m2, col.data, 0),
                            jnp.where(keep, col.lengths, 0))
    if isinstance(col, Int64Column):
        return Int64Column(jnp.where(keep, col.hi, 0),
                           jnp.where(keep, col.lo, 0))
    m = keep.reshape(keep.shape + (1,) * (col.ndim - 1))
    return jnp.where(m, col, 0)


# ---------------------------------------------------------------------------
# user-defined decomposable aggregation (IDecomposable parity)


def _segmented_merge(seg: jax.Array, states, merge_fn):
    """Reduce an arbitrary associative ``merge_fn`` over each segment.

    TPU-idiomatic segmented reduction: a single ``associative_scan`` over
    rows carrying (segment id, state); the combine keeps the right operand
    where segments differ, so each segment's LAST row ends up holding the
    full segment reduction.  This is what lets *user-defined* aggregations
    (reference IDecomposable.cs:34 Accumulate/RecursiveAccumulate) run as
    one fused XLA op instead of a per-group loop.
    """

    def combine(a, b):
        sa, va = a
        sb, vb = b
        same = sa == sb

        def pick(x, y):
            m = same.reshape(same.shape + (1,) * (x.ndim - 1))
            return jnp.where(m, x, y)

        merged = merge_fn(va, vb)
        out = jax.tree.map(pick, merged, vb)
        return sb, out

    _, scanned = jax.lax.associative_scan(combine, (seg, states))
    return scanned


def _last_row_per_segment(is_start: jax.Array, num_groups: jax.Array,
                          n_valid: jax.Array) -> jax.Array:
    """Index of the last (sorted) row of each segment; 0 past num_groups.
    Scatter-free via _segment_bounds (XLA CSE merges the bool argsort
    with _first_row_per_segment's when both are used)."""
    cap = is_start.shape[0]
    _, end_excl = _segment_bounds(is_start, num_groups, n_valid)
    return jnp.where(jnp.arange(cap) < num_groups,
                     jnp.maximum(end_excl - 1, 0), 0)


def _seg_scan_multi(vals_ops, is_start: jax.Array):
    """Running segment reduces for SEVERAL (value, op) pairs in ONE
    associative scan: the log(cap) passes and the boundary-flag carry are
    shared instead of paid per aggregate (measured: the scans, not the
    sorts, dominate group_aggregate at millions of rows — five separate
    scans re-stream the array five times)."""

    def comb(a, b):
        fa, va = a[0], a[1:]
        fb, vb = b[0], b[1:]
        out = []
        for (xa, xb, (_, op)) in zip(va, vb, vals_ops):
            # a value may be a tuple of arrays (a 64-bit sum's words)
            out.append(jax.tree.map(
                lambda y, z: jnp.where(
                    fb.reshape(fb.shape + (1,) * (y.ndim - 1)), y, z),
                xb, op(xa, xb)))
        return (fa | fb,) + tuple(out)

    res = jax.lax.associative_scan(
        comb, (is_start,) + tuple(v for v, _ in vals_ops))
    return list(res[1:])


def _seg_scan_reduce(v: jax.Array, is_start: jax.Array, op,
                     reverse: bool = False) -> jax.Array:
    """Per-row running ``op``-reduce within each segment (rows in sorted
    segment order, ``is_start`` marking segment firsts).  One segmented
    associative_scan — log(cap) vectorized passes, NO scatter (TPU
    scatters serialize; measured ~25 ms per 4M rows vs ~1 ms for scans).
    The per-segment total sits at the segment's last row (first row with
    ``reverse=True``, whose boundary flags must mark segment ENDS).  Float
    accumulation order is the scan's balanced tree — no cross-segment
    cancellation (unlike a global-prefix difference), bounded rounding
    like numpy's pairwise sums."""

    def comb(a, b):
        va, fa = a
        vb, fb = b
        m = fb.reshape(fb.shape + (1,) * (va.ndim - 1))
        return jnp.where(m, vb, op(va, vb)), fa | fb

    out, _ = jax.lax.associative_scan(comb, (v, is_start), reverse=reverse)
    return out


def _hash_membership(hi: jax.Array, lo: jax.Array, flag: jax.Array,
                     valid: jax.Array) -> jax.Array:
    """bool [n] in ORIGINAL row order: does the row's 64-bit-hash segment
    contain a flagged row?  Scatter- and gather-free: one value-carry sort
    groups hashes (carrying the flag and the original position), forward +
    reverse segmented max-scans spread each segment's answer to every row,
    and a second 1-key sort on the carried position restores original
    order (the inverse-permutation-as-sort trick — TPU scatters
    serialize)."""
    n = hi.shape[0]
    iota = jnp.arange(n, dtype=jnp.uint32)
    # NOTE: valid rows sort as a prefix ONLY when valid is itself a
    # prefix mask; callers concatenate whole-batch valid prefixes, and
    # _sort_segments_carry's sentinel fold sorts the invalid rows last
    # regardless, so is_start/is_end stay correct
    # unbounded: the restore sort below needs every row's carried
    # position, the padding rows' too, to be a permutation
    (sflag, siota), is_start, is_end, _ng = _sort_segments_carry(
        hi, lo, valid, valid.sum(dtype=jnp.int32),
        (flag.astype(jnp.uint32), iota), stable=False, bounded=False)
    fwd = _seg_scan_reduce(sflag, is_start, jnp.maximum)
    bwd = _seg_scan_reduce(sflag, is_end, jnp.maximum, reverse=True)
    tot = jnp.maximum(fwd, bwd)
    # both sorts run unstable: the carried iota is a total key, so the
    # restore sort is deterministic regardless, and the first sort's
    # in-segment order is erased by the max-scans
    _, member = jax.lax.sort((siota, tot), num_keys=1, is_stable=False)
    return member > 0


def _group_states(batch: Batch, key_names: Sequence[str],
                  decs: Dict[str, Tuple], state_box: Dict):
    """Shared seed+segmented-merge machinery: returns (key out_cols,
    out -> per-group merged state pytree, num_groups, valid_rows mask)."""
    sb, seg, is_start, num_groups = _group_segments(batch, key_names)
    cap = batch.capacity

    out_cols = {}
    rep = sb.gather(_first_row_per_segment(is_start, num_groups))
    for k in key_names:
        out_cols[k] = rep.columns[k]

    last = _last_row_per_segment(is_start, num_groups, batch.count)
    valid_rows = jnp.arange(cap) < num_groups
    merged_states = {}
    for out_name, (seed, merge_fn, _fin) in decs.items():
        states = seed(dict(sb.columns))
        state_box[out_name] = jax.tree.structure(states)
        scanned = _segmented_merge(seg, states, merge_fn)
        merged_states[out_name] = jax.tree.map(
            lambda l: jnp.take(l, last, axis=0), scanned)
    return out_cols, merged_states, num_groups, valid_rows


def _emit_finalized(out_cols, out_name, fin, merged, valid_rows):
    val = fin(merged) if fin is not None else merged
    named = val if isinstance(val, dict) else {out_name: val}
    for cname, v in named.items():
        m = valid_rows.reshape(valid_rows.shape + (1,) * (v.ndim - 1))
        out_cols[cname] = jnp.where(m, v, 0)


def resolve_dec_spec(spec):
    """Dec spec -> (seed, merge, finalize) callables.  Specs are either a
    plan.expr.Decomposable (user-defined; ships by fn_table registration)
    or a ("__builtin__", kind, col) tag rebuilt here on the executing side
    (keeps plans serializable — runtime/shiplan.py)."""
    if isinstance(spec, tuple) and len(spec) == 3 and \
            spec[0] == "__builtin__":
        from dryad_tpu.plan.planner import _builtin_as_decomposable
        d = _builtin_as_decomposable(spec[1], spec[2])
        return (d.seed, d.merge, d.finalize)
    if hasattr(spec, "seed"):
        return (spec.seed, spec.merge, spec.finalize)
    return spec  # already a triple (direct kernel callers)


def _resolve_decs(decs):
    return {k: resolve_dec_spec(v) for k, v in decs.items()}


def group_decompose_partial(batch: Batch, key_names: Sequence[str],
                            decs: Dict[str, Tuple], state_box: Dict
                            ) -> Batch:
    """Map-side combine for user-defined decomposable aggregates.

    decs: out_name -> dec spec (see resolve_dec_spec).  ``seed(columns)``
    maps the row columns to a state pytree (vectorized over rows);
    ``merge(a, b)`` is the associative combine.  Output: key columns + the
    flattened state leaves as columns ``{out}@{i}``; the treedefs are
    published into ``state_box`` for the merge/finalize stage
    (reference IDecomposable.cs:34 Initialize/Seed/Accumulate).
    """
    decs = _resolve_decs(decs)
    out_cols, merged_states, num_groups, valid_rows = _group_states(
        batch, key_names, decs, state_box)
    for out_name, merged in merged_states.items():
        for i, leaf in enumerate(jax.tree.leaves(merged)):
            m = valid_rows.reshape(valid_rows.shape + (1,) * (leaf.ndim - 1))
            out_cols[f"{out_name}@{i}"] = jnp.where(m, leaf, 0)
    return Batch(out_cols, num_groups)


def group_decompose_local(batch: Batch, key_names: Sequence[str],
                          decs: Dict[str, Tuple], state_box: Dict) -> Batch:
    """Single-pass decomposable GroupBy (co-located input): seed + merge +
    FinalReduce in one fused kernel."""
    decs = _resolve_decs(decs)
    out_cols, merged_states, num_groups, valid_rows = _group_states(
        batch, key_names, decs, state_box)
    for out_name, merged in merged_states.items():
        fin = decs[out_name][2]
        _emit_finalized(out_cols, out_name, fin, merged, valid_rows)
    return Batch(out_cols, num_groups)


def group_decompose_merge(batch: Batch, key_names: Sequence[str],
                          decs: Dict[str, Tuple], state_box: Dict,
                          finalize: bool) -> Batch:
    """Reduce-side merge of partial states (columns ``{out}@{i}``), plus
    FinalReduce when ``finalize`` (reference IDecomposable.cs:34
    RecursiveAccumulate/FinalReduce)."""
    decs = _resolve_decs(decs)
    sb, seg, is_start, num_groups = _group_segments(batch, key_names)
    cap = batch.capacity

    out_cols = {}
    rep = sb.gather(_first_row_per_segment(is_start, num_groups))
    for k in key_names:
        out_cols[k] = rep.columns[k]

    last = _last_row_per_segment(is_start, num_groups, batch.count)
    valid_rows = jnp.arange(cap) < num_groups
    for out_name, (_seed, merge_fn, fin) in decs.items():
        treedef = state_box[out_name]
        n_leaves = treedef.num_leaves
        leaves = [sb.columns[f"{out_name}@{i}"] for i in range(n_leaves)]
        states = jax.tree.unflatten(treedef, leaves)
        scanned = _segmented_merge(seg, states, merge_fn)
        merged = jax.tree.map(
            lambda l: jnp.take(l, last, axis=0), scanned)
        if finalize:
            _emit_finalized(out_cols, out_name, fin, merged, valid_rows)
        else:
            for i, leaf in enumerate(jax.tree.leaves(merged)):
                m = valid_rows.reshape(
                    valid_rows.shape + (1,) * (leaf.ndim - 1))
                out_cols[f"{out_name}@{i}"] = jnp.where(m, leaf, 0)
    return Batch(out_cols, num_groups)


# ---------------------------------------------------------------------------
# group CONTENTS (per-group apply / top-k / rank select)
#
# The reference's GroupBy materializes each key's element sequence and runs
# ANY result selector over it (DryadLinqVertex.cs:510-753 — hash/sort
# GroupBy yielding IGrouping to user code).  The TPU-native forms below keep
# everything shape-static: rows are sorted into key segments and either
# (a) trimmed per segment by rank (top-k / rank select — O(cap) memory), or
# (b) regrouped into a dense [max_groups, group_capacity] layout and handed
# to a user fn vmapped over groups (the general result-selector path).


def _segments_by_keys_and_lanes(batch: Batch, key_names: Sequence[str],
                                extra_lanes: Tuple[jax.Array, ...]):
    """Sort rows by (key hash, extra ordering lanes), label equal-hash runs
    as segments — _hash_sort_segments with within-segment value order."""
    hi, lo = hash_batch_keys(batch, key_names)
    return _hash_sort_segments(hi, lo, batch.valid_mask(), extra_lanes)


def group_top_k(batch: Batch, key_names: Sequence[str], k: int, by: str,
                descending: bool = True) -> Batch:
    """Per-group top-k rows by the ``by`` column (all columns kept).

    O(cap) memory: rows are sorted by (key hash, by-value), and each
    segment keeps its first k rows — no dense regrouping.  Ties keep
    original row order (both sorts are stable).  Output fits the input
    capacity by construction (no overflow channel needed).
    Reference: a per-group result selector taking the k largest
    (DryadLinqVertex.cs:510-753 GroupBy family)."""
    lanes = sort_lanes_for(batch.columns[by], descending)
    order, seg, is_start, num_groups = _segments_by_keys_and_lanes(
        batch, key_names, tuple(reversed(lanes)))
    cap = batch.capacity
    sb = batch.gather(order)
    start_pos, _ = _segment_bounds(is_start, num_groups, batch.count)
    idx = jnp.arange(cap, dtype=jnp.int32)
    rel = idx - jnp.take(start_pos, jnp.clip(seg, 0, cap - 1))
    keep = (idx < batch.count) & (rel < k)
    return compact(sb, keep)


def group_rank_select(batch: Batch, key_names: Sequence[str], by: str,
                      rank: str = "median", out: str | None = None) -> Batch:
    """One row per group: the group's element at a sorted rank of ``by``.

    rank="median" picks the LOWER median (element (n-1)//2 of the
    ascending ``by`` order — exact an element of the group, unlike
    numpy's interpolated even-size median); "min"/"max" pick the ends.
    Output columns: the key columns + ``out`` (default: the ``by`` name)
    holding the selected value."""
    lanes = sort_lanes_for(batch.columns[by], False)
    order, seg, is_start, num_groups = _segments_by_keys_and_lanes(
        batch, key_names, tuple(reversed(lanes)))
    cap = batch.capacity
    sb = batch.gather(order)
    start_pos, end_excl = _segment_bounds(is_start, num_groups, batch.count)
    sizes = end_excl - start_pos
    if rank == "median":
        pos = start_pos + (sizes - 1) // 2
    elif rank == "min":
        pos = start_pos
    elif rank == "max":
        pos = end_excl - 1
    else:
        raise ValueError(f"unknown rank {rank!r}")
    gvalid = jnp.arange(cap, dtype=jnp.int32) < num_groups
    sel = jnp.where(gvalid, jnp.clip(pos, 0, cap - 1), 0)
    rep = sb.gather(jnp.where(gvalid, start_pos, 0))
    out_cols: Dict[str, Any] = {}
    for kname in key_names:
        out_cols[kname] = rep.columns[kname]
    v = sb.columns[by]
    oname = out or by
    if isinstance(v, StringColumn):
        out_cols[oname] = v.gather(sel)
    else:
        out_cols[oname] = jnp.take(v, sel, axis=0)
    return Batch(out_cols, num_groups)


def group_regroup_apply(batch: Batch, key_names: Sequence[str], fn,
                        max_groups: int, group_capacity: int,
                        out_rows: int, out_capacity: int):
    """The general per-group result selector: regroup rows into a dense
    [max_groups, group_capacity] layout and vmap ``fn`` over groups.

    ``fn(cols, count) -> (out_cols, mask)``: cols are ONE group's columns
    ([group_capacity, ...] arrays / StringColumns; rows >= count are
    unspecified), out_cols are [out_rows, ...], mask is [out_rows] bool.
    Group key columns are attached to the output automatically (one value
    per group, broadcast over its emitted rows) unless fn emits a column
    of the same name.  Outputs of all groups are flattened and compacted
    into ``out_capacity`` rows.

    Returns (batch, num_groups, max_group_size, total_out_rows) — the
    three measured requirements; the executor converts any that exceed
    its static bound into a right-sized retry (measured-need feedback,
    DrDynamicDistributor.cpp:388 role).

    Memory note: the dense regroup materializes
    max_groups x group_capacity cells per column — size the two knobs for
    the workload (the price of giving user code a whole materialized
    group on a tensor machine; reference streams IGroupings instead,
    DryadLinqVertex.cs:510)."""
    sb, seg, is_start, num_groups = _group_segments(batch, key_names)
    cap = batch.capacity
    start_pos, end_excl = _segment_bounds(is_start, num_groups, batch.count)
    idx = jnp.arange(cap, dtype=jnp.int32)
    sizes = jnp.where(idx < num_groups, end_excl - start_pos, 0)
    max_size = jnp.max(sizes).astype(jnp.int32)

    # a partition cannot hold more groups (or a larger group) than rows
    G, C, R = min(max_groups, cap), min(group_capacity, cap), out_rows
    gstart = start_pos[:G]
    gsizes = jnp.minimum(sizes[:G], C)  # clamp: oversize triggers retry
    gvalid = jnp.arange(G, dtype=jnp.int32) < num_groups
    gidx = jnp.clip(gstart[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :],
                    0, cap - 1)  # [G, C]
    group_cols: Dict[str, Any] = {}
    for kname, v in sb.columns.items():
        if isinstance(v, StringColumn):
            group_cols[kname] = StringColumn(
                jnp.take(v.data, gidx, axis=0),
                jnp.take(v.lengths, gidx, axis=0))
        else:
            group_cols[kname] = jnp.take(v, gidx, axis=0)

    out_cols, mask = jax.vmap(fn)(group_cols, gsizes)  # [G, R, ...], [G, R]
    mask = mask & gvalid[:, None]

    rep = sb.gather(jnp.where(gvalid, gstart, 0))  # [G] key rows
    full: Dict[str, Any] = {}
    for kname in key_names:
        if kname in out_cols:
            continue
        v = rep.columns[kname]
        if isinstance(v, StringColumn):
            full[kname] = StringColumn(
                jnp.broadcast_to(v.data[:, None, :], (G, R, v.max_len)),
                jnp.broadcast_to(v.lengths[:, None], (G, R)))
        else:
            full[kname] = jnp.broadcast_to(
                v[:, None], (G, R) + v.shape[1:])
    full.update(out_cols)

    flat_mask = mask.reshape(-1)
    total = flat_mask.sum(dtype=jnp.int32)
    perm = jnp.argsort(~flat_mask, stable=True)[:out_capacity]
    cols: Dict[str, Any] = {}
    for kname, v in full.items():
        if isinstance(v, StringColumn):
            data = v.data.reshape((G * R,) + v.data.shape[2:])
            lens = v.lengths.reshape(-1)
            cols[kname] = StringColumn(jnp.take(data, perm, axis=0),
                                       jnp.take(lens, perm))
        else:
            flat = v.reshape((G * R,) + v.shape[2:])
            cols[kname] = jnp.take(flat, perm, axis=0)
    out = Batch(cols, jnp.minimum(total, out_capacity))
    return out, num_groups, max_size, total


def distinct(batch: Batch, key_names: Sequence[str] | None = None) -> Batch:
    """One representative row per distinct key (all columns kept).

    Gather-free: value-carry sort by hash, then a second value-carry sort
    on the is_start flag densifies each segment's first row to the front
    in group order (see the packed-row transport note above)."""
    keys = list(key_names) if key_names else sorted(batch.names)
    hi, lo = hash_batch_keys(batch, keys)
    cap = batch.capacity
    idx = jnp.arange(cap, dtype=jnp.int32)
    lanes, spec = _pack_columns_u32(dict(batch.columns))
    slanes, is_start, _is_end, num_groups = _sort_segments_carry(
        hi, lo, batch.valid_mask(), batch.count, lanes)
    _, svals2 = _sort_carrying([(~is_start).astype(jnp.uint32)], slanes,
                               cap, live=num_groups)
    cols = _unpack_columns_u32(svals2, spec)
    gmask = idx < num_groups
    return Batch({k: _mask_rows(v, gmask) for k, v in cols.items()},
                 num_groups)


# ---------------------------------------------------------------------------
# whole-batch (scalar) aggregation


@jax.named_scope("group_aggregate")
def scalar_aggregate(batch: Batch,
                     aggs: Dict[str, Tuple[str, str | None]]) -> Dict[str, jax.Array]:
    """Masked full-batch reductions: out_name -> (kind, value_column|None)."""
    valid = batch.valid_mask()
    out = {}
    for out_name, (kind, vname) in aggs.items():
        if kind == "count":
            out[out_name] = batch.count
            continue
        v = batch.columns[vname]
        if kind in ("sum", "mean"):
            vm = jnp.where(valid, v, 0)
            s = vm.sum(axis=0)
            if kind == "sum":
                out[out_name] = s
            else:
                c = jnp.maximum(batch.count, 1)
                out[out_name] = s / c if jnp.issubdtype(s.dtype, jnp.floating) \
                    else s.astype(jnp.float32) / c
        elif kind == "min":
            out[out_name] = jnp.where(valid, v, _neutral_for("min", v.dtype)).min(axis=0)
        elif kind == "max":
            out[out_name] = jnp.where(valid, v, _neutral_for("max", v.dtype)).max(axis=0)
        elif kind == "any":
            out[out_name] = (jnp.where(valid, v, False)).any(axis=0)
        elif kind == "all":
            out[out_name] = (jnp.where(valid, v, True)).all(axis=0)
        else:
            raise ValueError(kind)
    return out


# ---------------------------------------------------------------------------
# join


def _keys_equal(a: Batch, a_idx, a_names, b: Batch, b_idx, b_names,
                live=None) -> jax.Array:
    """Row-wise equality of the key columns at the two index vectors; of
    the first ``live`` pairs alone where given (the rest compare zeros
    and the caller masks them)."""
    eq = jnp.ones(a_idx.shape, jnp.bool_)
    for an, bn in zip(a_names, b_names):
        ca, cb = a.columns[an], b.columns[bn]
        if isinstance(ca, StringColumn):
            la = _gather_live(ca.lengths, a_idx, live)
            lb = _gather_live(cb.lengths, b_idx, live)
            da = _gather_live(ca.data, a_idx, live)
            db = _gather_live(cb.data, b_idx, live)
            L = min(ca.max_len, cb.max_len)
            pos = jnp.arange(L, dtype=jnp.int32)[None, :]
            m = pos < la[:, None]
            beq = jnp.where(m, da[:, :L] == db[:, :L], True).all(axis=1)
            # if max_lens differ, longer-side extra bytes imply inequality via length
            eq = eq & (la == lb) & beq
        else:
            eq = eq & (_gather_live(ca, a_idx, live)
                       == _gather_live(cb, b_idx, live))
    return eq


@jax.named_scope("row_gather")
def _packed_gather(cols: Dict[str, Any], idx: jax.Array,
                   live=None) -> Dict[str, Any]:
    """Gather rows of several columns with ONE fused word-matrix gather:
    pack the columns to u32 lanes, take the stacked [cap, W] matrix
    once, unpack.  TPU random gathers pay a per-ROW cost (~10.7 ns
    measured, benchmarks/pallas_probe), so fetching each output row's
    whole packed payload in one gather beats one gather per column —
    the join probe's dominant cost (probe + verify + gather fuse into
    one program around this).  The per-row-cost model is TPU-specific:
    on cpu the stack/unpack copies made the packed form ~2x SLOWER
    (BENCH_kernels r06 join_gather at 262k rows), so other backends
    keep one take per column — the same backend tier gating the pallas
    kernels (force_interpret() routes tests through the packed form).
    ``live``: only the first ``live`` output rows are read — either form
    fetches those alone and leaves zeros past them (_gather_live)."""
    from dryad_tpu.ops.pallas_kernels import pallas_active
    if pallas_active() is None:
        return {k: jax.tree.map(lambda x: _gather_live(x, idx, live), v)
                for k, v in cols.items()}
    lanes, spec = _pack_columns_u32(cols)
    if not lanes:
        return {}
    return _unpack_columns_u32(_gather_lanes(lanes, idx, live), spec)


def _join_out_names(left: Batch, right: Batch, right_keys, suffix: str):
    """Output column name plan shared by both join lowerings (the
    lax.cond pair must produce identical pytrees)."""
    names = list(left.names)
    rkeyset = set(right_keys)
    rmap = []
    for k in right.names:
        if k in rkeyset:
            continue
        name = k if k not in names else k + suffix
        rmap.append((k, name))
        names.append(name)
    return rmap


@jax.named_scope("lookup_join")     # a trace tells its sorts from a group-by's
def _lookup_join(left: Batch, right: Batch, left_keys: Sequence[str],
                 right_keys: Sequence[str], out_capacity: int,
                 suffix: str, how: str) -> Tuple[Batch, jax.Array]:
    """Gather-free join for a UNIQUE-keyed right side (lookup/dimension
    table — the PageRank ranks join, the star-schema shape).

    The general hash_join materializes every output column by random
    gather (~10.7 ns/row x columns x out_capacity, measured — the
    dominant join cost).  With at most ONE right row per key, each left
    row is its own output row, so the join is a merge: sort the union of
    both sides by 64-bit key hash with rights first in each run, hand
    each left row the payload of the latest right row before it, and
    compact the left rows.

    The hand-down is a prefix SUM, not a scan over segments: the right
    side alone is put in key-hash order first (it is the small side), and
    each of its rows carries its payload lanes LESS the previous right
    row's (uint32, wrapping).  Valid rights keep that order in the union
    (their hashes are distinct), so the wrapping prefix sum of a lane
    over the union telescopes to the latest right row's value at every
    position — one streamed pass a lane (pallas_kernels.prefix_sum) where
    a segmented associative scan is log-depth in passes and, unrolled by
    the compiler, hundreds of MB of program at millions of rows.

    Match verification: a left row matches when a right row precedes it
    and that row's key is its own.  When the two sides' key columns pack
    to the SAME u32 lane layout (same dtype / string max_len — the common
    case), the right row's packed key lanes are handed down with the
    payload and each left row byte-compares them against its own carried
    key lanes, so a 64-bit hash collision is caught exactly like the
    general kernel's _keys_equal.  When the layouts differ (e.g. joining
    an i32 key to an i64 key column), the right row's 64-bit hash pair is
    handed down and compared instead — the same ~n^2/2^64 budget every
    hash group documents.  The caller-facing ``right_unique=True`` path
    also RUNTIME-verifies right-side uniqueness and falls back to the
    general kernel on duplicates (covering hash-collision-induced
    apparent duplicates); on duplicates this kernel's result is
    undefined.
    """
    from dryad_tpu.ops.pallas_kernels import prefix_sum
    lhi, llo = hash_batch_keys(left, left_keys)
    rhi, rlo = hash_batch_keys(right, right_keys)
    lvalid = left.valid_mask()
    rvalid = right.valid_mask()
    lhi, llo = _sentinel_fold(lhi, llo, lvalid)
    rhi, rlo = _sentinel_fold(rhi, rlo, rvalid)
    cl, cr = left.capacity, right.capacity
    n = cl + cr

    lpack, lspec = _pack_columns_u32(dict(left.columns))
    rmap = _join_out_names(left, right, right_keys, suffix)
    rpack, rspec = _pack_columns_u32(
        {name: right.columns[k] for k, name in rmap})
    # byte verification (handed-down packed key lanes): only when both
    # sides' key columns pack identically — offsets of the left key
    # lanes within lpack, and the right keys packed under the left
    # names so the specs are directly comparable
    loff: Dict[str, Tuple[int, Tuple]] = {}
    off = 0
    for entry in lspec:
        loff[entry[0]] = (off, entry[1:])
        off += entry[3]
    vlanes, vspec = _pack_columns_u32(
        {ln: right.columns[rn]
         for ln, rn in zip(left_keys, right_keys)})
    verify = (len(set(left_keys)) == len(left_keys)
              and len(vspec) == len(left_keys)
              and all(ln in loff and loff[ln][1] == entry[1:]
                      for ln, entry in zip(left_keys, vspec)))
    if verify:
        own: List[int] = []        # lpack lanes the handed-down key meets
        for ln, entry in zip(left_keys, vspec):
            o = loff[ln][0]
            own.extend(range(o, o + entry[3]))
    else:
        vlanes = [rhi, rlo]
    nr = len(rpack)

    # the right side alone in key-hash order (an index sort and one
    # packed gather: it is the small side), each row less its predecessor
    _, _, rorder = jax.lax.sort(
        (rhi, rlo, jnp.arange(cr, dtype=jnp.int32)), num_keys=3,
        is_stable=False)
    hand = jnp.take(jnp.stack(rpack + vlanes, axis=1), rorder, axis=0)
    hand = hand - jnp.concatenate([jnp.zeros_like(hand[:1]), hand[:-1]])

    # rights sort BEFORE lefts within a key run, so the latest right row
    # before a left row of its key is its match
    zl = jnp.zeros((cr,), jnp.uint32)
    zr = jnp.zeros((cl,), jnp.uint32)
    lanes = [jnp.concatenate([l, zl]) for l in lpack]
    lanes += [jnp.concatenate([zr, hand[:, j]])
              for j in range(hand.shape[1])]
    srhi, srlo = jnp.take(rhi, rorder), jnp.take(rlo, rorder)
    n_valid = left.count + right.count
    # the sentinels sort last and a prefix sum reads no row after its
    # own: the first n_valid sorted rows are all that anything reads
    skeys, sl = _sort_carrying(
        [jnp.concatenate([lhi, srhi]), jnp.concatenate([llo, srlo]),
         jnp.concatenate([jnp.ones((cl,), jnp.uint32), zl])],
        lanes, n, stable=False, live=n_valid)
    shi, slo, sside = skeys
    idx = jnp.arange(n, dtype=jnp.int32)
    live = idx < n_valid            # valid rows sort before the sentinels
    is_left = (sside == 1) & live

    # hand the right rows' lanes down: the wrapping prefix sum of the
    # differences is the latest right row's value
    filled = [prefix_sum(sl[len(lpack) + j])
              for j in range(nr + len(vlanes))]
    present = prefix_sum(((sside == 0) & live).astype(jnp.int32)) > 0
    mine = [sl[li] for li in own] if verify else [shi, slo]
    for got, want in zip(filled[nr:], mine):
        present = present & (got == want)

    keep = is_left & present if how == "inner" else is_left
    total = keep.sum(dtype=jnp.int32)

    out_lanes = list(sl[:len(lpack)])
    for j in range(nr):
        # unmatched (or collision-rejected) left rows zero-fill the
        # right columns (how="left")
        out_lanes.append(jnp.where(present, filled[j], 0))
    _, dl = _sort_carrying([(~keep).astype(jnp.uint32)], out_lanes, n,
                           live=total)

    def _fit(a):
        return a[:out_capacity] if n >= out_capacity else jnp.concatenate(
            [a, jnp.zeros((out_capacity - n,), a.dtype)])

    dl = [_fit(a) for a in dl]
    cols = _unpack_columns_u32(dl[:len(lpack)], lspec)
    rcols = _unpack_columns_u32(dl[len(lpack):], rspec)
    cols.update(rcols)
    cnt = jnp.minimum(total, out_capacity)
    gmask = jnp.arange(out_capacity) < cnt
    cols = {k: _mask_rows(v, gmask) for k, v in cols.items()}
    need = jnp.where(total > out_capacity, total, 0).astype(jnp.int32)
    return Batch(cols, cnt), need


def search_sort_rows(n_left: int, n_right: int) -> int:
    """Elements the search phase of ``hash_join``'s general body sorts for
    ``n_left`` left and ``n_right`` right rows of capacity: the merge of
    both sides' hashes, then the sort back to left-row order — once,
    carrying both bounds, or past the operand budget
    (``_VALOPS_MAX_ELEMS``) once a bound."""
    n = n_left + n_right
    return n * (2 if _carry_fits(n, 1, 2) else 3)


@jax.named_scope("search")
def _candidate_ranges(rkey: jax.Array, lh: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """``start[i]`` = #{k : rkey[k] < lh[i]} and ``stop[i]`` = #{k :
    rkey[k] <= lh[i]} against the SORTED ``rkey`` (``np.searchsorted``'s
    left and right sides), with one merge sort of both sides and no
    scatter.

    The union ``rkey ++ lh`` is sorted on (hash, position): a right row's
    position is below every left row's, so in a run of equal hashes the
    right rows come first.  In that order ``stop`` is the running count of
    right rows, and ``start`` that count where the hash's run began — a
    running max of the counts at run starts, since the counts never fall.
    A sort keyed on the left rows' own positions brings both back."""
    from dryad_tpu.ops.pallas_kernels import prefix_max, prefix_sum
    n, m = rkey.shape[0], lh.shape[0]
    pos = jnp.arange(n + m, dtype=jnp.int32)
    skey, spos = jax.lax.sort((jnp.concatenate([rkey, lh]), pos),
                              num_keys=2, is_stable=False)
    is_right = (spos < n).astype(jnp.int32)
    stop = prefix_sum(is_right)
    run_start = (pos == 0) | (skey != jnp.roll(skey, 1))
    start = prefix_max(jnp.where(run_start, stop - is_right, 0))
    # left rows first, at their own positions; right rows past them
    back = jnp.where(spos < n, spos + m, spos - n)
    if _carry_fits(n + m, 1, 2):
        _, start, stop = jax.lax.sort((back, start, stop), num_keys=1,
                                      is_stable=False)
    else:
        _, start = jax.lax.sort((back, start), num_keys=1, is_stable=False)
        _, stop = jax.lax.sort((back, stop), num_keys=1, is_stable=False)
    return start[:m], stop[:m]


@jax.named_scope("search")
def _slot_owners(cum: jax.Array, mult: jax.Array,
                 out_capacity: int) -> jax.Array:
    """``lid[t]`` = #{i : cum[i] <= t}, the left row that owns output slot
    ``t``, for every slot ``t < cum[-1]``; ``cum`` is the inclusive prefix
    sum of the non-negative ``mult``.  Row ``i`` owns the slots ``[cum[i]
    - mult[i], cum[i])``: its index is written at the first of them and a
    running max over the slots hands it to the rest — one scatter of a
    mark a row and one pass over the slots, whatever share of them is
    live.  Slots at or past ``cum[-1]`` hold some row's index; every
    reader masks them or reads the first ``live`` alone."""
    from dryad_tpu.ops.pallas_kernels import prefix_max
    rows = jnp.arange(cum.shape[0], dtype=jnp.int32)
    first = cum - mult
    # a row whose first slot is not in range gets an index of its own past
    # the end: no two indices are equal, and those marks are dropped
    at = jnp.where((mult > 0) & (first < out_capacity), first,
                   out_capacity + rows)
    marks = jnp.zeros((out_capacity,), jnp.int32).at[at].set(
        rows, mode="drop", unique_indices=True)
    return prefix_max(marks)


@jax.named_scope("hash_join")
def hash_join(left: Batch, right: Batch, left_keys: Sequence[str],
              right_keys: Sequence[str], out_capacity: int,
              suffix: str = "_r", how: str = "inner",
              right_unique: bool | str = False
              ) -> Tuple[Batch, jax.Array]:
    """Equi-join; output columns = left columns + right non-key columns
    (right name suffixed on collision).  Returns ``(batch, overflow)``.

    ``how="left"``: left rows without a match emit ONE row with the right
    columns zero-filled (the GroupJoin empty-group case — reference
    DryadLinqQueryable GroupJoin; pair with a count aggregate to
    distinguish empty groups).  A left row whose only hash candidates are
    64-bit-collision false positives could be misclassified as matched-less
    output being dropped — probability ~2^-32 per pair, same collision
    budget documented on group_by.

    ``how="right"``: mirrored — right rows without a match emit ONE row
    with the LEFT non-key columns zero-filled and the left key columns
    taken from the right keys.  ``how="full"`` combines both.  Unmatched
    right rows are appended after the matched output (reference right/full
    outer join lowering, DryadLinqQueryable.cs:3639-area operator family).

    Output capacity is the static ``out_capacity``.  ``overflow`` is a
    conservative bool: True whenever the number of *candidate* pairs (hash
    matches before real-key verification) exceeds ``out_capacity`` — in that
    case true matches may have been dropped and the caller should re-run with
    a larger capacity.  It can be a false alarm when hash collisions inflate
    the candidate count, which is rare and only costs a re-plan.

    Reference semantics: DryadLinqVertex hash join (DryadLinqVertex.cs:942).

    ``right_unique=True`` (inner/left only) declares the right side a
    lookup table: after a cheap runtime duplicate check on the right's
    64-bit hashes, the gather-free merge-fill path (_lookup_join) runs;
    duplicates (or hash collisions that look like them) fall back to this
    general kernel inside the same compiled program (lax.cond).

    ``right_unique="verified"`` (inner/left only): the right side's key
    was held to that very check where its rows were written
    (io/store.check_unique, 64-bit hash collisions included), so the
    program is _lookup_join alone — no check, no ``cond``, no second
    kernel.
    """
    if right_unique == "verified" and how in ("inner", "left"):
        return _lookup_join(left, right, left_keys, right_keys,
                            out_capacity, suffix, how)
    if right_unique and how in ("inner", "left"):
        rhi0, rlo0 = hash_batch_keys(right, right_keys)
        rv = right.valid_mask()
        rhi0, rlo0 = _sentinel_fold(rhi0, rlo0, rv)
        shi0, slo0 = jax.lax.sort((rhi0, rlo0), num_keys=2,
                                  is_stable=False)
        dup = jnp.any((shi0[1:] == shi0[:-1]) & (slo0[1:] == slo0[:-1])
                      & (jnp.arange(1, right.capacity) < right.count))
        return jax.lax.cond(
            ~dup,
            lambda lr: _lookup_join(lr[0], lr[1], left_keys, right_keys,
                                    out_capacity, suffix, how),
            lambda lr: hash_join(lr[0], lr[1], left_keys, right_keys,
                                 out_capacity, suffix, how),
            (left, right))
    # TPUs have no fast uint64, so candidate ranges are found on a single
    # 32-bit hash lane; real-key verification below removes the (rare)
    # collision-induced false candidates.  (A collision only widens a
    # candidate range, never loses a match.)
    lhi, llo = hash_batch_keys(left, left_keys)
    rhi, rlo = hash_batch_keys(right, right_keys)
    lh = lhi ^ (llo * jnp.uint32(0x9E3779B9))
    rh = rhi ^ (rlo * jnp.uint32(0x9E3779B9))
    rvalid = right.valid_mask()
    lvalid = left.valid_mask()

    # sort right by hash, invalid last.  The sorted batch is never
    # materialized: every sorted-row access composes the permutation
    # (order) with its index — one full-batch gather saved per join.
    # (invalid, rh, iota) rides ONE unstable 3-key sort: the iota is
    # both the tiebreak (deterministic candidate order) and the
    # permutation payload — the same operand set lexsort's stable
    # machinery pays for, without the stability passes.
    _, _, order = jax.lax.sort(
        ((~rvalid).astype(jnp.uint32), rh,
         jnp.arange(right.capacity, dtype=jnp.int32)),
        num_keys=3, is_stable=False)
    rkey = jnp.take(rh, order)
    # invalid rows take the sentinel max key, so they sort last; a left
    # row hashing to the sentinel counts them as candidates, and
    # ``rid < right.count`` below drops them
    pos = jnp.arange(right.capacity)
    rkey = jnp.where(pos < right.count, rkey, jnp.uint32(0xFFFFFFFF))

    # each left row's candidates are the sorted right rows [start, stop)
    start, stop = _candidate_ranges(rkey, lh)
    mult = jnp.where(lvalid, stop - start, 0)
    if how not in ("inner", "left", "right", "full"):
        raise ValueError(f"unknown join how={how!r}")
    left_synth = how in ("left", "full")
    if left_synth:
        # unmatched left rows still occupy one output slot (synthetic)
        synth_row = lvalid & (mult == 0)
        mult = jnp.where(synth_row, 1, mult)

    # output slot -> (left row, right row) via prefix sums
    cum = jnp.cumsum(mult)
    total = cum[-1]
    t = jnp.arange(out_capacity, dtype=jnp.int32)
    lid_c = _slot_owners(cum, mult, out_capacity)
    # slots at or past ``total`` hold nothing (slot_valid): every gather
    # over the slots fetches the first ``live`` alone and leaves zeros
    # behind them, which index row 0 and are masked like what was there
    live = jnp.minimum(total, out_capacity)
    base = _gather_live(cum, lid_c, live) - _gather_live(mult, lid_c, live)
    rid = (_gather_live(start, lid_c, live) + (t - base)).astype(jnp.int32)
    rid = jnp.clip(rid, 0, right.capacity - 1)
    slot_valid = t < total

    # verify true key equality (hash collisions) then compact; also exclude
    # candidates that landed in the right-side padding region, whose contents
    # are unspecified and may hold stale real keys
    rid_abs = _gather_live(order, rid, live)  # sorted position -> own row
    eq = _keys_equal(left, lid_c, left_keys, right, rid_abs, right_keys,
                     live)
    keep_match = slot_valid & eq & (rid < right.count)
    keep = keep_match
    if left_synth:
        synth_slot = slot_valid & _gather_live(synth_row, lid_c, live)
        keep = keep | synth_slot

    # one packed gather per side (probe + verify + gather fused around
    # it — see _packed_gather) instead of one random gather per column
    out_cols = _packed_gather(dict(left.columns), lid_c, live)
    rkeyset = set(right_keys)
    rpayload = {}
    for k, v in right.columns.items():
        if k in rkeyset:
            continue
        name = k if k not in out_cols else k + suffix
        rpayload[name] = v
    for name, g in _packed_gather(rpayload, rid_abs, live).items():
        if left_synth:
            # unmatched left rows zero-fill the right columns
            g = _mask_rows(g, ~synth_slot)
        out_cols[name] = g
    # compaction by value-carry sort, not argsort+gather: the full-batch
    # gather alone measured ~22 ms at 400k rows x 5 columns
    joined = Batch(out_cols, jnp.asarray(out_capacity, jnp.int32))
    out = compact(joined, keep)
    # conservative: candidate pairs dropped for capacity might have been real.
    # NEED channel: 0 = fits, else actual candidate-pair count so the
    # executor can right-size the retry in one shot
    need = jnp.where(total > out_capacity, total, 0).astype(jnp.int32)
    if how in ("right", "full"):
        # right rows whose segment produced no VERIFIED match get one
        # synthetic output row each, appended after the matched rows.  A
        # match dropped only by capacity overflow marks its right row
        # matched=False, inflating u — harmless: need already forces a
        # right-sized retry in that case.
        matched = jnp.zeros((right.capacity,), jnp.int32).at[rid_abs].max(
            keep_match.astype(jnp.int32))
        unmatched = right.valid_mask() & (matched == 0)
        ru = compact(right, unmatched)
        u = ru.count
        key_map = dict(zip(left_keys, right_keys))
        synth_cols: Dict[str, Any] = {}
        for k, v in left.columns.items():
            if k in key_map:
                rv = ru.columns[key_map[k]]
                if isinstance(v, StringColumn):
                    # keep the right key's full width — concat2 pads
                    # mismatched string widths (truncating here would
                    # corrupt unmatched right keys longer than the left
                    # column's max_len)
                    synth_cols[k] = rv
                else:
                    synth_cols[k] = rv.astype(v.dtype)
            elif isinstance(v, StringColumn):
                synth_cols[k] = StringColumn(
                    jnp.zeros((right.capacity, v.max_len), jnp.uint8),
                    jnp.zeros((right.capacity,), jnp.int32))
            else:
                synth_cols[k] = jnp.zeros((right.capacity,) + v.shape[1:],
                                          v.dtype)
        for k, v in ru.columns.items():
            if k in rkeyset:
                continue
            name = k if k not in synth_cols else k + suffix
            synth_cols[name] = v
        merged = concat2(out, Batch(synth_cols, u))
        out = merged.gather(
            jnp.arange(out_capacity, dtype=jnp.int32),
            count=jnp.minimum(merged.count, out_capacity))
        need = jnp.where(total + u > out_capacity, total + u,
                         need).astype(jnp.int32)
    return out, need


def flat_map_expand(batch: Batch, fn, out_capacity: int
                    ) -> Tuple[Batch, jax.Array]:
    """Generic SelectMany: ``fn(cols) -> (out_cols, mask)`` where each output
    column is [cap, m, ...] and mask is [cap, m]; flattens row-major and
    compacts into ``out_capacity`` rows.  Returns (batch, overflow)."""
    out_cols, mask = fn(dict(batch.columns))
    mask = mask & batch.valid_mask()[:, None]
    cap, m = mask.shape
    flat_mask = mask.reshape(-1)
    total = flat_mask.sum(dtype=jnp.int32)
    perm = jnp.argsort(~flat_mask, stable=True)[:out_capacity]
    cols = {}
    for k, v in out_cols.items():
        if isinstance(v, StringColumn):
            data = v.data.reshape((cap * m,) + v.data.shape[2:])
            lens = v.lengths.reshape(-1)
            cols[k] = StringColumn(jnp.take(data, perm, axis=0),
                                   jnp.take(lens, perm))
        else:
            flat = v.reshape((cap * m,) + v.shape[2:])
            cols[k] = jnp.take(flat, perm, axis=0)
    out = Batch(cols, jnp.minimum(total, out_capacity))
    need = jnp.where(total > out_capacity, total, 0)
    return out, need.astype(jnp.int32)


def zip2(a: Batch, b: Batch, suffix: str = "_r") -> Batch:
    """Positional pairing within a partition; shorter-side count (LINQ Zip).
    Capacity = min of the two capacities."""
    cap = min(a.capacity, b.capacity)

    def trim(v):
        return jax.tree.map(lambda x: x[:cap] if x.ndim else x, v)

    cols = {}
    for k, v in a.columns.items():
        cols[k] = trim(v)
    for k, v in b.columns.items():
        name = k if k not in cols else k + suffix
        cols[name] = trim(v)
    return Batch(cols, jnp.minimum(a.count, b.count))


def right_match_mask(left: Batch, right: Batch, left_keys: Sequence[str],
                     right_keys: Sequence[str]) -> jax.Array:
    """bool [right.capacity]: right rows whose 64-bit key hash appears
    among left's VALID rows (the cross-chunk matched-right tracking that
    streamed right/full outer joins need; same hash-membership collision
    budget as semi_anti_join)."""
    lhi, llo = hash_batch_keys(left, left_keys)
    rhi, rlo = hash_batch_keys(right, right_keys)
    lvalid = left.valid_mask()
    rvalid = right.valid_mask()
    hi = jnp.concatenate([rhi, lhi])
    lo = jnp.concatenate([rlo, llo])
    is_left = jnp.concatenate([jnp.zeros(right.capacity, jnp.int32),
                               lvalid.astype(jnp.int32)])
    valid = jnp.concatenate([rvalid, lvalid])
    member = _hash_membership(hi, lo, is_left, valid)
    return member[:right.capacity] & rvalid


def semi_anti_join(left: Batch, right: Batch, left_keys: Sequence[str],
                   right_keys: Sequence[str], anti: bool = False) -> Batch:
    """Keep left rows whose key does (semi) / does not (anti) appear in right.

    Exact membership on the full 64-bit hash pair via a merged sort: right
    hashes are flagged, the union is sorted, and a per-segment max of the
    flag tells each left row whether its segment contains a right row.
    Reference semantics: Intersect/Except building blocks
    (DryadLinqVertex set ops)."""
    lhi, llo = hash_batch_keys(left, left_keys)
    rhi, rlo = hash_batch_keys(right, right_keys)
    lvalid = left.valid_mask()
    rvalid = right.valid_mask()
    hi = jnp.concatenate([lhi, rhi])
    lo = jnp.concatenate([llo, rlo])
    is_right = jnp.concatenate([jnp.zeros(left.capacity, jnp.int32),
                                rvalid.astype(jnp.int32)])
    valid = jnp.concatenate([lvalid, rvalid])
    member = _hash_membership(hi, lo, is_right, valid)
    lmember = member[:left.capacity]
    keep = lvalid & (~lmember if anti else lmember)
    return compact(left, keep)


# ---------------------------------------------------------------------------
# concat


def concat2(a: Batch, b: Batch) -> Batch:
    """Device-side concat: valid rows of ``a`` then valid rows of ``b``."""
    ca, cb = a.capacity, b.capacity
    out_cap = ca + cb
    i = jnp.arange(out_cap, dtype=jnp.int32)
    from_a = i < a.count
    src = jnp.where(from_a, jnp.minimum(i, ca - 1),
                    jnp.minimum(ca + (i - a.count), out_cap - 1))
    cols = {}
    for k in a.names:
        va, vb = a.columns[k], b.columns[k]
        if isinstance(va, StringColumn):
            L = max(va.max_len, vb.max_len)
            da = jnp.pad(va.data, ((0, 0), (0, L - va.max_len)))
            db = jnp.pad(vb.data, ((0, 0), (0, L - vb.max_len)))
            data = jnp.concatenate([da, db], axis=0)
            lens = jnp.concatenate([va.lengths, vb.lengths])
            cols[k] = StringColumn(jnp.take(data, src, axis=0),
                                   jnp.take(lens, src))
        else:
            cols[k] = jnp.take(jnp.concatenate([va, vb], axis=0), src, axis=0)
    return Batch(cols, a.count + b.count)


def mean_finalize_columns(cols: dict, mean_cols: Sequence[str]) -> dict:
    """Finalize decomposed means: replace {m}__sum/{m}__cnt partial columns
    with their quotient (the FinalReduce step of the builtin Average
    decomposition, IDecomposable.cs:34 / _decompose_aggs)."""
    out = dict(cols)
    for m in mean_cols:
        s = out.pop(m + "__sum")
        c = out.pop(m + "__cnt")
        cf = jnp.maximum(c, 1).reshape(c.shape + (1,) * (s.ndim - 1))
        out[m] = s / cf.astype(s.dtype) \
            if jnp.issubdtype(s.dtype, jnp.floating) \
            else s.astype(jnp.float32) / cf
    return out
