"""Stage-graph executor over the device mesh.

The counterpart of the reference's Graph Manager engine (SURVEY.md §2.2):
runs stages in topo order, each stage as ONE jit(shard_map(...)) program over
the partition axis; materializes stage outputs in device HBM (the replay
anchors); checks overflow flags host-side and re-runs a stage with scaled
capacities (the dynamic-repartition role of DrDynamicDistributionManager);
computes range-partition bounds from samples between stages (the
DrDynamicRangeDistributionManager / DryadLinqSampler.cs:42 pattern — a cheap
host step here instead of a sampling vertex stage).

Where the reference's GM is an actor message pump driving thousands of
vertex processes (DrMessagePump.h:116), our control plane is a host loop:
XLA's SPMD model means one launched program IS the whole stage across all
partitions, so per-vertex state machines collapse into per-stage calls.
Failure handling (replay from materialized inputs) lives in
exec/recovery.py.
"""

from __future__ import annotations

import re
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dryad_tpu.data.columnar import Batch, StringColumn
from dryad_tpu.exec.data import PData
from dryad_tpu.obs import trace
from dryad_tpu.ops import kernels
from dryad_tpu.ops.text import (lower_ascii, split_tokens,
                                tokenize_group_count)
from dryad_tpu.parallel import shuffle
from dryad_tpu.parallel.mesh import PARTITION_AXIS
from dryad_tpu.plan.stages import Exchange, Stage, StageGraph, StageOp
from jax.sharding import PartitionSpec as P

__all__ = ["Executor", "CapacityError", "stage_program_name"]

_MAX_CAPACITY_RETRIES = 3
_SAMPLES_PER_PART = 4096
# exchange slot feedback: how many leading legs report their measured
# send-slot rows through the stage info vector (fixed width so the
# deferred settle can stack infos across stages; stages with more
# exchange legs simply don't get feedback for the extras)
_SLOT_FEEDBACK_LEGS = 4
# lane of the stage info vector that carries a range exchange's tie_rows
_INFO_TIE = 4 + _SLOT_FEEDBACK_LEGS
# the two lanes after it: the rows the program's bounded gathers fetched,
# and the rows they would have fetched unbounded (kernels.gather_tally)
_INFO_GATHER = _INFO_TIE + 1
# and the lane after those: the group-bys whose one-hot branch ran
# (kernels.onehot_tally)
_INFO_ONEHOT = _INFO_GATHER + 2
# a program's name with jit's own "jit_" before it stays within 64
_PROGRAM_NAME_MAX = 60


def _quantize_slot_rows(slot: int) -> int:
    """Round a measured slot need UP to a ~1/16-relative grid so the
    per-exchange compile-cache variants stay bounded while supersteps'
    slot drift keeps hitting the same compiled program."""
    g = max(16, 1 << max(int(slot).bit_length() - 4, 0))
    return -(-int(slot) // g) * g

# stage-loop metrics, resolved ONCE (Counter handles are stable
# get-or-create objects; per-stage registry lookups would put a lock +
# key construction on the superstep hot path).  Family names/help come
# from the canonical obs.metrics.FAMILIES table, shared with the
# event-derived mirror (metrics_from_events) so the two cannot drift.
from dryad_tpu.obs.metrics import REGISTRY as _METRICS
from dryad_tpu.obs.metrics import family_counter as _family

_M_CACHE_HITS = _family(_METRICS, "cache_hits")
_M_CACHE_MISSES = _family(_METRICS, "cache_misses")
_M_COMPILE_S = _family(_METRICS, "compile_seconds")
_M_STAGE_RUNS = _family(_METRICS, "stage_runs")
_M_RUN_S = _family(_METRICS, "run_seconds")
_M_SHUFFLE_B = _family(_METRICS, "shuffle_bytes")
_M_CAP_RETRIES = _family(_METRICS, "cap_retries")


def _no_event(e) -> None:
    """Default event sink: drops everything.  The explicit ``level = 0``
    tells the span gate (obs/trace._sink_level) not to build spans that
    nothing will ever read — an executor without an EventLog pays zero
    tracing work."""


_no_event.level = 0


class CapacityError(RuntimeError):
    pass


# Op kinds whose overflow is fixed by doubling out_capacity on retry.
_SCALABLE_OVERFLOW_KINDS = {"flat_tokens", "flat_map", "join", "zip",
                            "group_apply"}
# Op kinds whose overflow CANNOT be fixed by scaling: `recap` truncates to a
# user-fixed capacity, `sliding_window` overflows when a neighbor partition
# lacks halo rows — retrying at a bigger scale just re-runs the same failure.
_FIXED_OVERFLOW_KINDS = {"recap", "sliding_window"}


def _stage_kinds(stage: Stage) -> set:
    return ({op.kind for leg in stage.legs for op in leg.ops}
            | {op.kind for op in stage.body})


def stage_program_name(stage: Stage) -> str:
    """Name of a stage's device program: ``stage_<label>_<op kinds>`` in
    plan order (each leg's ops, then its exchange, then the body), so a
    profiler's module line reads ``jit_stage_orderby_range_sort``.  A
    pure function of the plan — no stage id, fingerprint or counter —
    so one program has one name in every process and the persistent
    compile cache keeps hitting."""
    kinds = []
    for leg in stage.legs:
        kinds += [op.kind for op in leg.ops]
        if leg.exchange is not None:
            kinds.append(leg.exchange.kind)
    kinds += [op.kind for op in stage.body]
    words = [stage.label] + [k for i, k in enumerate(kinds)
                             if i == 0 or k != kinds[i - 1]]
    name = re.sub(r"[^a-z0-9]+", "_", "_".join(words).lower()).strip("_")
    return ("stage_" + name)[:_PROGRAM_NAME_MAX].rstrip("_")


def _stage_overflow_scalable(stage: Stage) -> bool:
    """True if any overflow source in the stage responds to capacity
    scaling (any exchange, or a scalable op kind)."""
    if _stage_kinds(stage) & _SCALABLE_OVERFLOW_KINDS:
        return True
    return any(leg.exchange is not None for leg in stage.legs)


from functools import partial


@partial(jax.jit, static_argnums=(2, 3))
def _sample_lanes(cols, counts, keys, S: int = _SAMPLES_PER_PART):
    """[P, S, L+1] u32 sample tuples: each partition's first
    min(count, S) entries evenly spread over its valid rows, each the
    L range lanes of ``keys`` (shuffle.range_key_lanes) and the row's
    global input position (shuffle.global_position's number, from the
    counts).  Module-level jit: one compile per (column shapes, keys,
    S), reused across queries."""
    starts = (jnp.cumsum(counts) - counts).astype(jnp.uint32)

    def one(cols_p, cnt, start):
        lanes = shuffle.range_key_lanes(Batch(cols_p, cnt), keys)
        cap = lanes[0].shape[0]
        take = jnp.maximum(jnp.minimum(cnt, S), 1)
        # float64-free overflow-safe spread: i * cnt can exceed int32 for
        # partitions > ~524k rows, so compute the stride first
        i = jnp.arange(S, dtype=jnp.int32)
        idx = jnp.clip((i * (cnt // take)) + (i * (cnt % take)) // take,
                       0, cap - 1)
        return jnp.stack([jnp.take(lane, idx) for lane in lanes]
                         + [start + idx.astype(jnp.uint32)], axis=1)

    return jax.vmap(one)(cols, counts, starts)


@jax.jit
def _splitters(lanes, counts):
    """[P-1, L+1] u32: the P-quantiles of the [P, S, L+1] sample tuples
    of ``_sample_lanes``, in shuffle.range_dest's own (lexicographic)
    order.  A key that fills several quantiles yields splitters that
    differ in the position lane alone, and those cut its run evenly.
    Invalid sample slots fold to the all-ones sentinel in every lane and
    sort last; a valid tuple equal to the sentinel only nudges a
    HEURISTIC split point."""
    P_, S, n_lanes = lanes.shape
    take = jnp.minimum(counts.astype(jnp.int32), S)  # [P]
    valid = jnp.arange(S, dtype=jnp.int32)[None, :] < take[:, None]
    flat = jnp.where(valid[:, :, None], lanes, jnp.uint32(0xFFFFFFFF)
                     ).reshape(P_ * S, n_lanes)
    srt = jax.lax.sort([flat[:, k] for k in range(n_lanes)],
                       num_keys=n_lanes)
    n_tot = take.sum()
    qs = jnp.clip((n_tot * jnp.arange(1, P_, dtype=jnp.int32)) // P_,
                  0, P_ * S - 1)
    bounds = jnp.stack([jnp.take(lane, qs) for lane in srt], axis=1)
    return jnp.where(n_tot > 0, bounds, 0).astype(jnp.uint32)


def _squeeze(b: Batch) -> Batch:
    return jax.tree.map(lambda x: x[0], b)


def _expand(b: Batch) -> Batch:
    return jax.tree.map(lambda x: x[None], b)


# sentinel "need" value: the overflow source cannot be fixed by scaling
_UNSCALABLE = 1 << 30


def _needs(ns, nsl=None):
    """Pack a (need_scale, need_slack) int32[2] needs vector."""
    z = jnp.zeros((), jnp.int32)
    ns = jnp.asarray(ns, jnp.int32) if ns is not None else z
    nsl = jnp.asarray(nsl, jnp.int32) if nsl is not None else z
    return jnp.stack([ns, nsl])


def _scale_need(need_rows, base_capacity: int):
    """Rows needed -> capacity scale needed (0 stays 0)."""
    return (-(-need_rows // jnp.int32(max(base_capacity, 1)))).astype(
        jnp.int32)


def _apply_op(b, op: StageOp, scale: int, others: List[Batch],
              axes: tuple = (PARTITION_AXIS,), slack: int = 2):
    """Apply one StageOp to batch ``b``; returns ``(batch, needs)`` where
    needs = int32[2] (need_scale, need_slack): 0 = fits, >0 = the measured
    requirement for a right-sized retry, _UNSCALABLE = retrying can't help."""
    no = jnp.zeros((2,), jnp.int32)
    k = op.kind
    p = op.params
    if k == "fn":
        new = p["fn"](dict(b.columns))
        return Batch(dict(new), b.count), no
    if k == "mean_fin":
        # structured mean finalization (sum/cnt -> mean) so the op
        # serializes for cluster shipping (runtime/shiplan.py)
        return Batch(kernels.mean_finalize_columns(dict(b.columns),
                                                   p["cols"]), b.count), no
    if k == "filter":
        return kernels.compact(b, p["fn"](dict(b.columns))), no
    if k == "flat_tokens":
        mtr = p.get("max_tokens_per_row")
        out, need_rows = split_tokens(b, p["column"],
                                      out_capacity=p["out_capacity"] * scale,
                                      max_token_len=p["max_token_len"],
                                      delims=p["delims"],
                                      max_tokens_per_row=(
                                          mtr * scale if mtr else None))
        if p["lower"]:
            col = out.columns[p["column"]]
            out = Batch({p["column"]: lower_ascii(col)}, out.count)
        return out, _needs(_scale_need(need_rows, p["out_capacity"]))
    if k == "tokens_group_count":
        mtr = p.get("max_tokens_per_row")
        out, need_rows = tokenize_group_count(
            b, p["column"], out_capacity=p["out_capacity"] * scale,
            vocab_capacity=p["vocab_capacity"] * scale,
            count_name=p["count_name"], max_token_len=p["max_token_len"],
            delims=p["delims"], lower=p["lower"],
            max_tokens_per_row=(mtr * scale if mtr else None))
        return out, _needs(_scale_need(need_rows, p["out_capacity"]))
    if k in ("dgroup_local", "dgroup_partial", "dgroup_merge"):
        keys = list(p["keys"])
        if k == "dgroup_local":
            return kernels.group_decompose_local(b, keys, p["decs"],
                                                 p["box"]), no
        if k == "dgroup_partial":
            return kernels.group_decompose_partial(b, keys, p["decs"],
                                                   p["box"]), no
        return kernels.group_decompose_merge(b, keys, p["decs"], p["box"],
                                             p["finalize"]), no
    if k == "group":
        keys = list(p["keys"])
        return kernels.group_aggregate(b, keys, dict(p["aggs"])), no
    if k == "where_group":
        # _fuse_stage_ops: the filters in front of a group-by are the
        # group-by's row mask, each predicate evaluated where the plan
        # has it (a later projection may drop the column it reads)
        cols, keep = dict(b.columns), True
        for step in p["steps"]:
            if step.kind == "filter":
                keep = keep & step.params["fn"](dict(cols))
            else:
                cols = dict(step.params["fn"](dict(cols)))
        g = p["group"].params
        return kernels.group_aggregate(Batch(cols, b.count), list(g["keys"]),
                                       dict(g["aggs"]), where=keep), no
    if k == "group_apply":
        G0, C0, O0 = p["max_groups"], p["group_capacity"], p["out_capacity"]
        out, ng, ms, tot = kernels.group_regroup_apply(
            b, list(p["keys"]), p["fn"], G0 * scale, C0 * scale,
            p["out_rows"], O0 * scale)
        ns = jnp.maximum(jnp.maximum(
            jnp.where(ng > G0 * scale, _scale_need(ng, G0), 0),
            jnp.where(ms > C0 * scale, _scale_need(ms, C0), 0)),
            jnp.where(tot > O0 * scale, _scale_need(tot, O0), 0))
        return out, _needs(ns)
    if k == "group_top_k":
        return kernels.group_top_k(b, list(p["keys"]), p["k"], p["by"],
                                   p["descending"]), no
    if k == "group_rank":
        return kernels.group_rank_select(b, list(p["keys"]), p["by"],
                                         p["rank"], p["out"]), no
    if k == "distinct":
        keys = list(p["keys"]) or None
        return kernels.distinct(b, keys), no
    if k == "sort":
        return kernels.sort_by_columns(b, list(p["keys"])), no
    if k == "take":
        n = p["n"]
        local = kernels.take(b, n)
        if p.get("global", True):
            counts = jax.lax.all_gather(local.count, axes)
            me = jax.lax.axis_index(axes)
            nparts = counts.shape[0]
            before = jnp.sum(
                jnp.where(jnp.arange(nparts) < me, counts, 0))
            keep = jnp.clip(n - before, 0, local.count)
            local = local.with_count(keep)
        return local, no
    if k == "apply":
        if p.get("with_index"):
            return p["fn"](b, jax.lax.axis_index(axes)), no
        return p["fn"](b), no
    if k == "flat_map":
        out, need_rows = kernels.flat_map_expand(b, p["fn"],
                                                 p["out_capacity"] * scale)
        return out, _needs(_scale_need(need_rows, p["out_capacity"]))
    if k == "zip":
        out, need_recv, need_slack = shuffle.zip_exchange(
            b, others[0], suffix=p.get("suffix", "_r"),
            send_slack=slack, axes=axes)
        # recv fits by construction (dest partition holds <= its left rows);
        # only send slots can fall short under skewed right-side counts
        return out, _needs(jnp.where(need_recv > 0, _UNSCALABLE, 0),
                           need_slack)
    if k == "row_index":
        counts = jax.lax.all_gather(b.count, axes)
        me = jax.lax.axis_index(axes)
        start = jnp.sum(jnp.where(jnp.arange(counts.shape[0]) < me,
                                  counts, 0))
        idx = start + jnp.arange(b.capacity, dtype=jnp.int32)
        return b.with_columns({p["column"]: idx}), no
    if k == "skip":
        n = p["n"]
        counts = jax.lax.all_gather(b.count, axes)
        me = jax.lax.axis_index(axes)
        start = jnp.sum(jnp.where(jnp.arange(counts.shape[0]) < me,
                                  counts, 0))
        # drop the first max(0, n - start) local rows
        drop = jnp.clip(n - start, 0, b.count)
        keep = jnp.arange(b.capacity, dtype=jnp.int32) >= drop
        return kernels.compact(b, keep), no
    if k == "take_while" or k == "skip_while":
        pred = p["fn"](dict(b.columns)) & b.valid_mask()
        # local index of first failing row; capacity if none fail
        fail = ~pred & b.valid_mask()
        first_fail = jnp.min(jnp.where(
            fail, jnp.arange(b.capacity, dtype=jnp.int32), b.capacity))
        first_fail = jnp.minimum(first_fail, b.count)
        # a partition's prefix counts only if all earlier partitions were
        # fully clean (no failing row)
        clean = first_fail >= b.count
        cleans = jax.lax.all_gather(clean, axes)
        me = jax.lax.axis_index(axes)
        nparts = cleans.shape[0]
        all_before_clean = jnp.all(
            jnp.where(jnp.arange(nparts) < me, cleans, True))
        prefix_len = jnp.where(all_before_clean, first_fail, 0)
        if k == "take_while":
            return b.with_count(prefix_len), no
        keep = jnp.arange(b.capacity, dtype=jnp.int32) >= prefix_len
        return kernels.compact(b, keep), no
    if k == "sliding_window":
        w = p["w"]
        D = jax.lax.axis_size(axes)
        halo = w - 1
        if halo == 0:
            cols = {kk: (StringColumn(v.data[:, None], v.lengths[:, None])
                         if isinstance(v, StringColumn) else v[:, None])
                    for kk, v in b.columns.items()}
            return Batch(cols, b.count), no
        # every partition sends its first (w-1) rows to the PREVIOUS one;
        # windows needing rows beyond the halo (tiny next partition) or past
        # the dataset end are dropped.  Requires halo <= next partition's
        # count (flagged as overflow -> capacity retries won't fix, which
        # surfaces a clear error).
        perm = [(i, (i - 1) % D) for i in range(D)]

        def send(x):
            return jax.lax.ppermute(x[:halo], axes, perm)

        next_count = jax.lax.ppermute(b.count, axes, perm)
        me = jax.lax.axis_index(axes)
        is_last = me == D - 1
        halo_avail = jnp.where(is_last, 0, jnp.minimum(next_count, halo))
        bad = (~is_last) & (next_count < halo)
        cap = b.capacity
        bad = jnp.where(bad, jnp.int32(_UNSCALABLE), 0)
        # splice the halo at position `count` (local rows past count are
        # padding and must not appear inside windows)
        idx_ext = jnp.arange(cap + halo, dtype=jnp.int32)
        src = jnp.where(idx_ext < b.count,
                        jnp.minimum(idx_ext, cap - 1),
                        jnp.minimum(cap + (idx_ext - b.count),
                                    cap + halo - 1))
        widx0 = jnp.arange(cap, dtype=jnp.int32)[:, None] + \
            jnp.arange(w, dtype=jnp.int32)[None, :]
        widx = jnp.take(src, widx0)  # [cap, w] -> indices into concat array
        cols = {}
        for kk, v in b.columns.items():
            if isinstance(v, StringColumn):
                data = jnp.concatenate([v.data, send(v.data)], axis=0)
                lens = jnp.concatenate([v.lengths, send(v.lengths)], axis=0)
                cols[kk] = StringColumn(jnp.take(data, widx, axis=0),
                                        jnp.take(lens, widx, axis=0))
            else:
                ext = jnp.concatenate([v, send(v)], axis=0)
                cols[kk] = jnp.take(ext, widx, axis=0)
        # valid window starts: i + w <= count + halo_avail
        n_out = jnp.clip(b.count + halo_avail - halo, 0, cap)
        return Batch(cols, n_out), _needs(bad)
    if k == "recap":
        cap = p["capacity"]
        if cap >= b.capacity:
            return b.pad_to(cap), no
        trunc = jax.tree.map(
            lambda x: x[:cap] if x.ndim else x, b)
        return (trunc.with_count(jnp.minimum(b.count, cap)),
                _needs(jnp.where(b.count > cap, _UNSCALABLE, 0)))
    if k == "apply2":
        return p["fn"](b, others[0]), no
    if k == "join":
        right = others[0]
        out, need_rows = kernels.hash_join(
            b, right, list(p["left_keys"]), list(p["right_keys"]),
            out_capacity=p["out_capacity"] * scale,
            how=p.get("how", "inner"),
            right_unique=p.get("right_unique", False))
        return out, _needs(_scale_need(need_rows, p["out_capacity"]))
    if k == "semi_anti":
        # canonical (sorted) column order on BOTH sides: the two legs may
        # have different column insertion orders for the same column set
        right = others[0]
        return kernels.semi_anti_join(
            b, right, sorted(b.names), sorted(right.names),
            anti=p["anti"]), no
    if k == "concat":
        return kernels.concat2(b, others[0]), no
    raise ValueError(f"unknown op kind {k}")


def _rowwise(op: StageOp) -> bool:
    """True for an op whose function is row-wise BY CONSTRUCTION: a SQL
    row-expression program (sql/rowexpr: column references, literals,
    elementwise operators — row i of the result reads row i of the
    input and nothing else), so neither a dropped row nor a row's
    position can show in another row.  An opaque callable may look at
    positions (a running sum over the compacted prefix) and is not."""
    from dryad_tpu.sql.rowexpr import Predicate, Projector
    return ((op.kind == "fn" and isinstance(op.params["fn"], Projector))
            or (op.kind == "filter"
                and isinstance(op.params["fn"], Predicate)))


def _where_group_end(ops, i: int) -> Optional[int]:
    """Index of the ``group`` op that the filter ``ops[i]`` feeds through
    row-wise ops alone (``filter, (fn | filter)*, group``), else None:
    an exchange, a join, a sort, a take, the end of the leg or an opaque
    function behind the filter still needs the compacted batch."""
    j = i + 1
    while j < len(ops) and _rowwise(ops[j]):
        j += 1
    return j if j < len(ops) and ops[j].kind == "group" else None


def _fuse_stage_ops(ops):
    """Executor-side peephole.  Plans ship unfused; fusion is a
    per-execution rewrite, so workers and driver fuse identically (and a
    stage's program name and fingerprint stay the plan's).

    * flat_tokens immediately followed by a count-only group over the
      token column becomes ONE fused op — the windowed byte extraction
      (the tokenizer's dominant cost, ~10 ns per gathered word) then
      runs only for group representatives (ops/text.tokenize_group_count).
    * a filter that feeds a group through row-wise ops alone
      (``filter, (fn | filter)*, group``, _where_group_end) becomes ONE
      ``where_group`` op: the predicates make the group-by's row mask
      (kernels.group_aggregate ``where=``) where kernels.compact would
      sort every row of every column to the front first — the group-by
      reads validity from a mask and never from position.  The first
      filter's predicate may be any callable (it sees the batch the plan
      gives it); what follows it must be row-wise by construction
      (_rowwise), since it now sees the dropped rows in place."""
    out = []
    i = 0
    while i < len(ops):
        op = ops[i]
        if op.kind == "filter":
            j = _where_group_end(ops, i)
            if j is not None:
                out.append(StageOp("where_group", {"steps": list(ops[i:j]),
                                                   "group": ops[j]}))
                i = j + 1
                continue
        if (op.kind == "flat_tokens" and i + 1 < len(ops)
                and ops[i + 1].kind == "group"):
            g = ops[i + 1]
            aggs = dict(g.params["aggs"])
            if (list(g.params["keys"]) == [op.params["column"]]
                    and len(aggs) == 1
                    and all(kind == "count" and v is None
                            for kind, v in aggs.values())):
                p = dict(op.params)
                p["count_name"] = next(iter(aggs))
                p["vocab_capacity"] = max(
                    1 << 16, p["out_capacity"] // 32)
                out.append(StageOp("tokens_group_count", p))
                i += 2
                continue
        out.append(op)
        i += 1
    return out


def _filter_counts(stage: Stage) -> Dict[str, int]:
    """What a stage's program does with the plan's filter ops:
    ``filters_masked`` became a group-by's row mask (_fuse_stage_ops),
    ``filters_compacted`` still sort the batch (kernels.compact).  Known
    from the plan; {} for a stage without a filter."""
    masked = compacted = 0
    for ops in [leg.ops for leg in stage.legs] + [stage.body]:
        for op in _fuse_stage_ops(ops):
            if op.kind == "where_group":
                masked += sum(st.kind == "filter"
                              for st in op.params["steps"])
            elif op.kind == "filter":
                compacted += 1
    if not masked and not compacted:
        return {}
    return {"filters_masked": masked, "filters_compacted": compacted}


def _wide_sum_count(stage: Stage) -> Dict[str, int]:
    """``int64_sums``: the integer sums a stage's group-bys accumulate in
    64 bits (aggregate kind ``sum64``, kernels.group_aggregate).  Known
    from the plan; {} for a stage without a group-by."""
    groups = [op for ops in [leg.ops for leg in stage.legs] + [stage.body]
              for op in ops if op.kind == "group"]
    if not groups:
        return {}
    return {"int64_sums": sum(spec[0] == "sum64" for g in groups
                              for spec in g.params["aggs"].values()
                              if isinstance(spec, tuple))}


def stage_info_attrs(info) -> Dict[str, int]:
    """``gather_rows`` / ``gather_rows_cap`` / ``onehot_groups`` of a
    fetched stage info ``[P, lanes]``: the rows the program's bounded
    gathers fetched, over all shards, the rows the same gathers would
    have fetched unbounded (their capacities), and the group-bys whose
    one-hot branch ran (the most on any one shard)."""
    return {"gather_rows": int(info[:, _INFO_GATHER].sum()),
            "gather_rows_cap": int(info[:, _INFO_GATHER + 1].sum()),
            "onehot_groups": int(info[:, _INFO_ONEHOT].max())}


def _join_kernel(params) -> str:
    """Which join a stage's program holds, from the plan: ``lookup``
    (kernels._lookup_join alone: the right side's key was verified where
    it was written), ``hash`` (kernels.hash_join's general body alone),
    ``checked`` (both, and a run-time duplicate check that picks)."""
    ru = params.get("right_unique", False)
    if not ru or params.get("how", "inner") not in ("inner", "left"):
        return "hash"
    return "lookup" if ru == "verified" else "checked"


def _apply_exchange(b: Batch, ex: Exchange, scale: int, slack: int, bounds,
                    axes: tuple = (PARTITION_AXIS,),
                    slot_rows: int | None = None
                    ) -> Tuple[Batch, jax.Array, jax.Array, jax.Array]:
    """Returns (batch, needs[2], slot_used, tie_rows) — see _apply_op.
    slot_used is the exchange's own measured max send-slot rows (pmax'd;
    0 for broadcast), fed back through the stage info vector so LATER
    runs of the same stage ship measured exact slots instead of the
    structural slack (Executor._note_slot_feedback).  tie_rows is this
    shard's rows that a range exchange placed by the tiebreak (0 for
    the other kinds); it rides the info vector into the trace."""
    cap = ex.out_capacity * scale
    slot = ties = jnp.zeros((), jnp.int32)
    if ex.kind == "hash":
        # empty keys = whole row; sorted so both legs of a set op agree
        keys = list(ex.keys) or sorted(b.names)
        out, nr, nsl, slot = shuffle.hash_exchange(
            b, keys, cap, send_slack=slack, axes=axes, axis=ex.axis,
            slot_rows=slot_rows)
    elif ex.kind == "range":
        keys = ex.sort_keys()
        ties = shuffle.range_tie_rows(b, keys, bounds)
        out, nr, nsl, slot = shuffle.range_exchange(
            b, keys, bounds, cap, send_slack=slack, axes=axes,
            slot_rows=slot_rows)
    elif ex.kind == "broadcast":
        out, nr, nsl = shuffle.broadcast_gather(b, cap, axes=axes)
    else:
        raise ValueError(ex.kind)
    return (out, _needs(_scale_need(nr, ex.out_capacity), nsl),
            slot.astype(jnp.int32), ties)


class Executor:
    """Executes StageGraphs; owns the mesh and the per-stage compile cache."""

    def __init__(self, mesh,
                 event_log: Optional[Callable[[dict], None]] = None,
                 config=None):
        from dryad_tpu.utils.config import JobConfig
        self.mesh = mesh
        self.axes = tuple(mesh.axis_names)
        self.nparts = mesh.devices.size
        self.config = config or JobConfig()
        from dryad_tpu.utils.compile_cache import enable_persistent_cache
        enable_persistent_cache(self.config.compilation_cache_dir)
        self._event = event_log or _no_event
        # Multi-process (runtime-cluster) mode: host-side reads of sharded
        # values (overflow flags, sample lanes, counts) must first replicate
        # over the mesh — every process executes the same replication
        # collective, then reads its local copy.
        from dryad_tpu.exec.data import mesh_is_multiprocess
        self._multiproc = mesh_is_multiprocess(mesh)
        # bounded LRU keyed by stage structure + input shapes, so identical
        # re-plans (same Dataset collected twice, do_while bodies) reuse
        # compiled programs instead of growing without bound
        from collections import OrderedDict
        self._compile_cache: "OrderedDict[Any, Callable]" = OrderedDict()
        self._compile_cache_max = self.config.compile_cache_size
        # what tracing a cached join stage's program told of its shapes
        # (_build_stage_fn's ``facts``), under the program's cache key
        self._stage_facts: Dict[Any, Dict[str, int]] = {}
        # measured slot-probe RESULTS keyed by (keys, slack, schema, the
        # input's device buffer identities): an iterative job re-running
        # the same stage over the SAME buffers (do_while loop state that
        # a body leg reads unchanged) skips the probe's blocking
        # device->host scalar fetch on every superstep.  Entries carry
        # WEAKREFS to the probed buffers: an id() is only recycled after
        # its original object died, so "all referents alive" proves the
        # keyed ids still name the probed arrays — a dead ref evicts the
        # entry instead of replaying a stale hint for different data.
        self._slot_probe_cache: "OrderedDict[Any, tuple]" = OrderedDict()
        # measured send-slot FEEDBACK keyed by (stage fingerprint, leg):
        # every info fetch (sync attempt or deferred settle) records the
        # exchanges' own pmax'd slot_used, so the NEXT run of the same
        # stage — the steady state of iterative jobs and re-collected
        # queries, and EVERY leg kind including multi-exchange stages
        # whose legs carry ops — ships measured exact slots with ZERO
        # extra host syncs (the streamed path's right-sizing,
        # runtime/stream_plan.py, brought to the in-memory executor;
        # closes ARCHITECTURE Known-limit #5)
        self._slot_feedback: "OrderedDict[Any, int]" = OrderedDict()
        # last synchronous stage's observed stats (adapt/stats.StageStats)
        # — consumed by exec/recovery.Run's adaptive boundary hook
        self._last_stage_stats = None
        # the job-service daemon (dryad_tpu/service) runs CONCURRENT
        # jobs over one shared executor so they share the compiled-stage
        # cache; the shared caches get a lock (compiles run outside it —
        # two jobs racing the same cold stage at worst both compile)
        import threading
        self._cache_lock = threading.RLock()

    def apply_config(self, config) -> None:
        """Re-point a persistent executor at a new job's JobConfig (worker
        processes keep one executor per mesh across submitted jobs)."""
        from dryad_tpu.utils.config import JobConfig
        self.config = config or JobConfig()
        from dryad_tpu.utils.compile_cache import enable_persistent_cache
        enable_persistent_cache(self.config.compilation_cache_dir)
        self._compile_cache_max = self.config.compile_cache_size
        while len(self._compile_cache) > self._compile_cache_max:
            self._evict_oldest_program()

    def _evict_oldest_program(self) -> None:
        key, _fn = self._compile_cache.popitem(last=False)
        self._stage_facts.pop(key, None)

    # -- stage program construction ---------------------------------------

    def _build_stage_fn(self, stage: Stage, scale: int, slack: int,
                        n_legs: int, has_bounds: bool,
                        salted: bool = False,
                        slot_hints: tuple = (),
                        facts: Optional[Dict[str, int]] = None):
        """``facts`` (optional) is filled while the program is traced
        with what only its shapes tell: ``join_in_bytes``, the bytes of
        a join's two inputs as the program holds them (every leg's ops
        and exchange applied), capacity x row bytes over all shards,
        ``build_rows``, the rows of capacity of its right input, and, where
        the program holds hash_join's general body, ``search_sort_rows``,
        the elements its search phase sorts (kernels.search_sort_rows)."""
        def per_shard(*args):
            with kernels.gather_tally() as gathers, \
                    kernels.onehot_tally() as onehots:
                return ops_and_info(gathers, onehots, *args)

        def ops_and_info(gathers, onehots, *args):
            leg_batches = [
                _squeeze(b) for b in args[:n_legs]]
            bounds = args[n_legs] if has_bounds else None
            needs = jnp.zeros((2,), jnp.int32)
            # exchange-attributed capacity need, tracked SEPARATELY so the
            # salting trigger reacts to exchange skew only — a uniform
            # flat_map shortfall must scale capacity, not salt the join
            exch_need = jnp.zeros((), jnp.int32)
            # per-leg measured send-slot rows (exchange feedback channel;
            # fixed width so _settle can stack infos across stages)
            slots = jnp.zeros((_SLOT_FEEDBACK_LEGS,), jnp.int32)
            ties = jnp.zeros((), jnp.int32)
            outs = []
            if salted:
                # hot-key-salted join repartition: both legs' hash
                # exchanges are rewritten jointly (left spreads hot keys,
                # right replicates its hot rows) — the runtime skew escape
                # (DrDynamicDistributor.h:79; see shuffle.skew_join_exchange)
                lb, rb = leg_batches
                for op in _fuse_stage_ops(stage.legs[0].ops):
                    lb, nd = _apply_op(lb, op, scale, [], self.axes, slack)
                    needs = jnp.maximum(needs, nd)
                for op in _fuse_stage_ops(stage.legs[1].ops):
                    rb, nd = _apply_op(rb, op, scale, [], self.axes, slack)
                    needs = jnp.maximum(needs, nd)
                lex, rex = stage.legs[0].exchange, stage.legs[1].exchange
                lcap = lex.out_capacity * scale
                rcap = rex.out_capacity * scale
                lout, rout, lnr, rnr, nsl = shuffle.skew_join_exchange(
                    lb, rb, lex.keys, rex.keys, lcap, rcap,
                    hot_factor=self.config.salt_hot_factor,
                    topk=self.config.salt_topk, send_slack=slack,
                    axes=self.axes)
                nd = _needs(jnp.maximum(
                    _scale_need(lnr, lex.out_capacity),
                    _scale_need(rnr, rex.out_capacity)), nsl)
                needs = jnp.maximum(needs, nd)
                exch_need = jnp.maximum(exch_need, nd[0])
                outs = [lout, rout]
            else:
                for li, (leg, b) in enumerate(zip(stage.legs,
                                                  leg_batches)):
                    for op in _fuse_stage_ops(leg.ops):
                        b, nd = _apply_op(b, op, scale, [], self.axes,
                                          slack)
                        needs = jnp.maximum(needs, nd)
                    if leg.exchange is not None:
                        hint = (slot_hints[li]
                                if li < len(slot_hints) else None)
                        b, nd, slot, tie = _apply_exchange(
                            b, leg.exchange, scale, slack, bounds,
                            self.axes, slot_rows=hint)
                        needs = jnp.maximum(needs, nd)
                        ties = ties + tie
                        exch_need = jnp.maximum(exch_need, nd[0])
                        if li < _SLOT_FEEDBACK_LEGS:
                            slots = slots.at[li].set(slot)
                    outs.append(b)
            cur = outs[0]
            rest = outs[1:]
            for op in _fuse_stage_ops(stage.body):
                if op.kind in ("join", "semi_anti", "concat", "apply2",
                               "zip"):
                    if op.kind == "join" and facts is not None:
                        facts["join_in_bytes"] = self.nparts * sum(
                            x.size * x.dtype.itemsize
                            for b in (cur, rest[0])
                            for x in jax.tree.leaves(b.columns))
                        facts["build_rows"] = \
                            self.nparts * rest[0].capacity
                        if _join_kernel(op.params) != "lookup":
                            facts["search_sort_rows"] = \
                                self.nparts * kernels.search_sort_rows(
                                    cur.capacity, rest[0].capacity)
                    cur, nd = _apply_op(cur, op, scale, rest,
                                        self.axes, slack)
                    rest = []
                else:
                    cur, nd = _apply_op(cur, op, scale, [],
                                        self.axes, slack)
                needs = jnp.maximum(needs, nd)
            # ONE small per-shard info vector [need_scale, need_slack,
            # exchange_need_scale, out_count, slot_used x 4 legs,
            # tie_rows, gather_rows, gather_rows_cap, onehot_groups]:
            # the executor host-fetches exactly one array per stage — a
            # second fetch per stage costs a full link round trip, which
            # dominates iterative jobs on high-latency links.  The slot
            # lanes are the exchanges' own measured send-slot feedback
            # (free: they ride the fetch that happens anyway), and so do
            # the range exchange's tie counter (_INFO_TIE), what the
            # program's bounded gathers fetched (_INFO_GATHER) and its
            # group-bys' one-hot branches (_INFO_ONEHOT).
            fetched = sum((f for f, _ in gathers), jnp.zeros((), jnp.int32))
            unbounded = min(sum(c for _, c in gathers), 2**31 - 1)
            onehot = sum(onehots, jnp.zeros((), jnp.int32))
            info = jnp.concatenate([needs, exch_need[None],
                                    cur.count.astype(jnp.int32)[None],
                                    slots, ties[None], fetched[None],
                                    jnp.full((1,), unbounded, jnp.int32),
                                    onehot[None]])
            return _expand(cur), info[None]

        per_shard.__name__ = per_shard.__qualname__ = \
            stage_program_name(stage)
        in_specs = tuple([P(self.axes)] * n_legs +
                         ([P()] if has_bounds else []))
        fn = jax.shard_map(per_shard, mesh=self.mesh, in_specs=in_specs,
                           out_specs=(P(self.axes), P(self.axes)),
                           check_vma=False)
        return jax.jit(fn)

    # -- range bounds sampling --------------------------------------------

    def _range_bounds(self, src: PData, keys) -> jax.Array:
        """Split-point selection from per-partition samples: ``[P-1, L+1]``
        uint32 splitters over the L range lanes of the ``(column,
        descending)`` ``keys`` and the tiebreak lane, as
        shuffle.range_dest compares them.

        Sampling runs ON DEVICE: each partition subsamples at most
        _SAMPLES_PER_PART tuples (evenly spread over its valid rows), so
        only [P, S, L+1] u32 lanes are touched — never the full key
        column (the reference's 0.1% reservoir sampling,
        DryadLinqSampler.cs:38; VERDICT r1 weak item 3)."""
        if self.nparts == 1:
            return jnp.zeros((0, 1), jnp.uint32)
        S = self.config.range_samples_per_partition
        cols = {k: src.batch.columns[k] for k, _ in keys}
        lanes = _sample_lanes(cols, src.counts, tuple(keys), S)
        counts = src.counts
        if self._multiproc:
            from dryad_tpu.exec.data import replicate_tree
            lanes, counts = replicate_tree((lanes, counts), self.mesh)
        # split points computed ON DEVICE end to end: no host round trip
        # between the sampled stage and the range exchange (the per-stage
        # dispatch collapse, VERDICT r4 next-2 — bounds ride to the next
        # stage program as a device argument)
        return _splitters(lanes, counts)

    # -- execution ---------------------------------------------------------

    def run(self, graph: StageGraph,
            bindings: Optional[Dict[str, PData]] = None,
            spill_dir: Optional[str] = None,
            cost_report=None, event_log=None, job=None,
            failure_budget: Optional[int] = None,
            checkpoint=None, pause=None) -> PData:
        """Execute a graph with lineage-tracked recovery (exec.recovery.Run).
        With spill_dir, stage outputs are durably materialized.  With
        JobConfig.profile_dir, the whole run is captured in a
        jax.profiler device-time trace (xprof/TensorBoard viewable —
        the Artemis device-timeline role).  ``cost_report`` (the lint
        gate's static analysis/cost.py prediction) arms the per-stage
        runtime cross-check and seeds adaptive execution's priors.

        ``event_log``/``job``/``failure_budget`` make the run's driver
        state fully per-JOB (the service daemon runs many concurrent
        jobs over one shared executor): events route to the given sink
        tagged with the job id, never to the executor's process default."""
        from dryad_tpu.exec.recovery import Run
        prof = getattr(self.config, "profile_dir", None)
        if prof:
            import os

            import jax
            sub = prof
            if jax.process_count() > 1:
                sub = os.path.join(prof,
                                   f"worker-{jax.process_index()}")
            elif os.environ.get("DRYAD_WORKER_ID"):
                # standalone (elastic) workers run outside
                # jax.distributed but still need per-worker trace
                # attribution
                sub = os.path.join(
                    prof, f"worker-{os.environ['DRYAD_WORKER_ID']}")
            with jax.profiler.trace(sub):
                return Run(self, graph, bindings,
                           spill_dir=spill_dir,
                           cost_report=cost_report,
                           event=event_log, job=job,
                           failure_budget=failure_budget,
                           checkpoint=checkpoint,
                           pause=pause).output()
        return Run(self, graph, bindings, spill_dir=spill_dir,
                   cost_report=cost_report, event=event_log,
                   job=job, failure_budget=failure_budget,
                   checkpoint=checkpoint, pause=pause).output()

    def _check_cost(self, stage: Stage, scale: int, rows_total: int,
                    out_bytes: int, report=None, event=None) -> None:
        """Cross-check one settled (non-overflowing) stage against the
        static cost prediction; misses surface as ``cost_model_miss``
        events (the model-validation loop of the cost analyzer).
        ``report``/``event`` come from the CALLING run — there is no
        shared-executor fallback: with concurrent jobs on one executor
        (the service daemon) a process-global report would cross-check
        one job's stages against another job's model."""
        if report is None:
            return
        est = report.stage(stage.id)
        if est is None:
            return
        from dryad_tpu.analysis.cost import check_stage_measurement
        ev = event if event is not None else self._event
        for miss in check_stage_measurement(est, scale, rows_total,
                                            out_bytes, self.nparts):
            ev(miss)

    def _leg_input(self, leg, results, bindings) -> PData:
        if isinstance(leg.src, int):
            return results[leg.src]
        kind, v = leg.src
        if kind == "source":
            return v
        if kind == "placeholder":
            try:
                return bindings[v]
            except KeyError:
                raise KeyError(f"unbound placeholder {v!r}")
        raise ValueError(leg.src)

    def _decide_needs(self, stage: Stage, scale: int, slack: int,
                      salted: bool, need_scale: int, need_slack: int,
                      need_exch: int):
        """Shared retry policy: map a stage's measured needs to
        ("ok", ...) | ("retry", scale, slack, salted), raising
        CapacityError for unscalable overflows.  Used by the synchronous
        attempt loop AND by Run's deferred-needs settlement."""
        of = need_scale > 0 or need_slack > 0
        if not of:
            return ("ok",)
        if need_scale >= _UNSCALABLE or not _stage_overflow_scalable(stage):
            raise CapacityError(
                f"stage {stage.id} ({stage.label}) overflowed a fixed "
                f"capacity (with_capacity truncation, sliding_window "
                f"halo, or a zip alignment shortfall) — retrying at a "
                f"larger scale cannot succeed; raise the declared "
                f"capacity instead")
        if (not salted and stage.salt_ok
                and need_exch >= self.config.salt_trigger_factor * scale
                and self.nparts > 1):
            # hot-key EXCHANGE skew — see the attempt loop's comment
            new_scale = max(stage._capacity_scale,
                            -(-need_exch * 2 // self.nparts))
            if need_scale > need_exch:
                new_scale = max(new_scale, need_scale)
            return ("retry", new_scale,
                    max(slack, min(need_slack, self.nparts)), True)
        return ("retry", max(scale, need_scale),
                max(slack, min(need_slack, self.nparts)), salted)

    def _probe_slot_rows(self, pd: PData, keys, slack: int) -> int:
        """Counts-only pre-hop for an EXACT first exchange wave: one tiny
        cached program (hash -> per-destination histogram -> max, pmax'd)
        and one scalar fetch tell the stage compiler the measured slot
        need BEFORE the exchange ships — wave 1 then sends measured slots
        instead of the structural slack (the reference's pull shuffle
        reads real file sizes, kernel/DrCluster.cpp:553-569; static SPMD
        shapes force the measurement OUT of the exchange program).  Only
        meaningful for pure repartition legs, whose input IS the exchange
        input.  Quantized to C_struct/16 so the per-exchange compile-
        cache variants stay bounded."""
        from jax.sharding import PartitionSpec as P

        from dryad_tpu.ops.hashing import hash_batch_keys
        from dryad_tpu.ops.pallas_kernels import hist_buckets
        from dryad_tpu.parallel.shuffle import _canonical_hash_dest

        b0 = pd.batch
        cap = next(iter(jax.tree.leaves(b0))).shape[1]
        D = self.nparts
        sig = tuple(sorted((k, str(jnp.shape(v)),
                            str(getattr(v, "dtype", "str")))
                           for k, v in b0.columns.items()))
        # result cache: same keys + slack over the same live device
        # buffers -> same measured slots, no device->host sync
        import weakref
        leaves = jax.tree.leaves(b0)
        rkey = (tuple(keys), slack, sig, tuple(id(x) for x in leaves))
        hit = self._slot_probe_cache.get(rkey)
        if hit is not None:
            rows, refs = hit
            if all(r() is not None for r in refs):
                self._slot_probe_cache.move_to_end(rkey)
                return rows
            del self._slot_probe_cache[rkey]   # recycled id: not a hit
        key = ("slot_probe", tuple(keys), sig)
        fn = self._compile_cache.get(key)
        if fn is None:
            axes = self.axes

            def probe_slot_rows(batch):
                b = _squeeze(batch)
                _, lo = hash_batch_keys(b, list(keys))
                dest = _canonical_hash_dest(lo, axes)
                dest = jnp.where(b.valid_mask(), dest, D)
                counts = hist_buckets(dest, D)
                m = jnp.max(counts).astype(jnp.int32)
                return jax.lax.pmax(m, axes)[None]

            fn = jax.jit(jax.shard_map(
                probe_slot_rows, mesh=self.mesh, in_specs=P(self.axes),
                out_specs=P(self.axes[0]), check_vma=False))
            self._compile_cache[key] = fn
        slot = int(np.asarray(fn(b0)).max())
        c_struct = max(1, -(-slack * cap // D))
        q = max(16, c_struct // 16)
        rows = max(1, min(c_struct, -(-slot // q) * q))
        try:
            refs = tuple(weakref.ref(x) for x in leaves)
        except TypeError:
            return rows   # unexpected non-weakreffable leaf: don't cache
        self._slot_probe_cache[rkey] = (rows, refs)
        while len(self._slot_probe_cache) > 256:
            self._slot_probe_cache.popitem(last=False)
        return rows

    def _note_slot_feedback(self, stage: Stage, info) -> None:
        """Record each exchange leg's measured send-slot rows from a
        fetched stage info vector (the [4 + li] lanes — already pmax'd
        on device, so every shard records the same value).  Costs no
        extra sync: it rides the info fetch that happens anyway (sync
        attempt) or the one batched settle fetch (deferred path)."""
        if info.shape[1] < 4 + 1:
            return
        fp = stage.fingerprint()
        with self._cache_lock:
            for li, leg in enumerate(stage.legs[:_SLOT_FEEDBACK_LEGS]):
                ex = leg.exchange
                if ex is None or ex.kind == "broadcast":
                    continue
                if 4 + li >= info.shape[1]:
                    break
                slot = int(info[:, 4 + li].max())
                if slot > 0:
                    self._slot_feedback[(fp, li)] = slot
                    self._slot_feedback.move_to_end((fp, li))
            while len(self._slot_feedback) > 512:
                self._slot_feedback.popitem(last=False)

    def _slot_hints(self, stage: Stage, inputs, slack: int,
                    salted: bool) -> tuple:
        """Measured send-slot rows per leg, or None per leg for the
        structural slack.  Source order per exchange leg:

        1. the exchange's OWN slot feedback from a previous run of this
           stage (any hash/range leg, including multi-exchange stages
           and legs with ops) — zero host syncs;
        2. the counts-only pre-hop probe (_probe_slot_rows) for
           first-wave pure hash repartitions big enough to matter —
           one host sync, once (the result cache and the feedback above
           make every later wave sync-free);
        3. None: ship the structural slack (true discovery wave).

        ``exchange_probe_min_mb < 0`` disables BOTH measured paths (the
        wire_check A/B reference)."""
        thresh = getattr(self.config, "exchange_probe_min_mb", -1)
        if (thresh < 0 or salted or self.nparts < 2 or self._multiproc):
            # multi-process gangs fetch through replicate_tree; the probe
            # fetch would add a cross-host sync — structural slack there
            return ()
        fp = stage.fingerprint()
        hints = []
        for li, (leg, inp) in enumerate(zip(stage.legs, inputs)):
            hint = None
            ex = leg.exchange
            if ex is not None and ex.kind in ("hash", "range"):
                fb = (self._slot_feedback.get((fp, li))
                      if li < _SLOT_FEEDBACK_LEGS else None)
                if fb is not None:
                    hint = _quantize_slot_rows(fb)
                elif (ex.kind == "hash" and not leg.ops
                      and ex.axis is None and len(self.axes) == 1):
                    mb = sum(x.size * x.dtype.itemsize
                             for x in jax.tree.leaves(inp.batch)) \
                        / (1 << 20)
                    if mb >= thresh:
                        keys = list(ex.keys) or sorted(inp.batch.names)
                        hint = self._probe_slot_rows(inp, keys, slack)
            hints.append(hint)
        return tuple(hints) if any(h is not None for h in hints) else ()

    def _run_stage(self, stage: Stage, results, bindings,
                   defer: Optional[list] = None, event=None,
                   cost_report=None, stats_box: Optional[list] = None,
                   job=None, span=trace.NULL) -> PData:
        # per-job driver state (exec/recovery.Run threads these): the
        # event sink, cost report, and observed-stats box belong to the
        # CALLING run, not this (possibly shared) executor
        ev = event if event is not None else self._event
        # observed-stats slot for the adaptive manager (exec/recovery):
        # cleared per stage so a deferred or failed attempt can never
        # leak a previous stage's measurement into a rewrite decision
        self._last_stage_stats = None
        if stats_box is not None:
            stats_box[0] = None
        inputs = [self._leg_input(leg, results, bindings)
                  for leg in stage.legs]
        bounds = None
        for leg in stage.legs:
            if leg.exchange is not None and leg.exchange.kind == "range":
                src_pd = results[leg.exchange.bounds_from]
                bounds = self._range_bounds(src_pd,
                                            leg.exchange.sort_keys())

        scale = stage._capacity_scale
        slack = stage._send_slack or self.config.initial_send_slack
        salted = stage._salted
        max_retries = self.config.max_capacity_retries
        join = next((op for op in stage.body if op.kind == "join"), None)
        # a range-exchange stage says how many lanes its splitters
        # compare, the tiebreak included (static: the bounds' shape)
        range_attrs = ({} if bounds is None
                       else {"range_lanes": int(bounds.shape[1])})
        filter_attrs = {**_filter_counts(stage), **_wide_sum_count(stage)}
        for attempt in range(max_retries + 1):
            # salt knobs are baked into compiled salted programs — they
            # must key the cache or a re-configured job reuses stale code
            salt_cfg = ((self.config.salt_hot_factor,
                         self.config.salt_topk) if salted else None)
            slot_hints = self._slot_hints(stage, inputs, slack, salted)
            key = (stage.fingerprint(), scale, slack, salted, salt_cfg,
                   slot_hints,
                   tuple(str(jax.tree.map(lambda x: (jnp.shape(x), x.dtype),
                                          i.batch)) for i in inputs))
            args = [i.batch for i in inputs]
            if bounds is not None:
                args.append(bounds)
            with self._cache_lock:
                fn = self._compile_cache.get(key)
                if fn is not None:
                    self._compile_cache.move_to_end(key)
            compile_s = 0.0
            cache_hit = fn is not None
            facts = self._stage_facts.get(key, {})
            if fn is None:
                _M_CACHE_MISSES.inc()
                # AOT compile so the event stream separates compile time
                # from run time (the device-time profiling the reference
                # surfaces through Artemis; VERDICT r1 weak item 8)
                t0 = time.time()
                with trace.span("stage.compile", "compile", sink=ev) as csp:
                    fn = self._build_stage_fn(stage, scale, slack,
                                              len(inputs),
                                              bounds is not None,
                                              salted=salted,
                                              slot_hints=slot_hints,
                                              facts=facts
                                              ).lower(*args).compile()
                    compile_s = time.time() - t0
                    csp.set(compile_s=round(compile_s, 4))
                _M_COMPILE_S.inc(compile_s)
                with self._cache_lock:
                    self._compile_cache[key] = fn
                    if facts:
                        self._stage_facts[key] = facts
                    if len(self._compile_cache) > self._compile_cache_max:
                        self._evict_oldest_program()
            else:
                _M_CACHE_HITS.inc()
            if job is not None:
                # per-job compiled-stage hit/miss attribution: the
                # service dashboard's "did the Nth user pay compile"
                # signal (labels ride the same canonical families)
                _family(_METRICS, "cache_hits" if cache_hit
                        else "cache_misses", job=job).inc()
            # a join stage says what it was given and what it may return
            # (static: shapes and plan, no device sync)
            join_attrs = {} if join is None else {
                "out_capacity": join.params["out_capacity"] * scale,
                "right_unique": bool(join.params.get("right_unique",
                                                     False)),
                "join_kernel": _join_kernel(join.params),
                **facts}
            if span is not trace.NULL:
                span.set(program="jit_" + stage_program_name(stage),
                         cache_hit=cache_hit, **join_attrs, **range_attrs,
                         **filter_attrs)
            t0 = time.time()
            out_batch, info = fn(*args)
            if defer is not None and attempt == 0:
                # OPTIMISTIC path: no host sync here.  The needs vector
                # stays on device; Run._settle batch-fetches every
                # deferred info in ONE round trip at job end and replays
                # (synchronously) from the first overflowing stage if
                # any.  This is what collapses per-stage dispatches to
                # "one program launch per stage + one fetch per job" —
                # the reference GM likewise never chats mid-vertex (one
                # DVertexCommandBlock start per vertex,
                # dvertexcommand.h:199).
                # live counters must not wait for the settle (out_bytes
                # is STATIC shape metadata — no device sync here); the
                # capacity-retry counter alone is settled later, when
                # the overflow verdict exists (recovery._settle)
                enqueue_s = round(time.time() - t0, 4)
                out_bytes = int(sum(
                    x.size * x.dtype.itemsize
                    for x in jax.tree.leaves(out_batch)))
                # run_seconds is fed the wait for the device, which on
                # this path is Run._settle's
                _M_STAGE_RUNS.inc()
                _M_SHUFFLE_B.inc(out_bytes)
                defer.append({"stage": stage, "info": info,
                              "scale": scale, "slack": slack,
                              "salted": salted, "cache_hit": cache_hit,
                              "compile_s": round(compile_s, 4),
                              "out_bytes": out_bytes,
                              "enqueue_s": enqueue_s,
                              "join": join_attrs, "range": range_attrs,
                              "filters": filter_attrs})
                stage._capacity_scale = scale
                stage._send_slack = slack
                stage._salted = salted
                return PData(out_batch, self.nparts)
            if self._multiproc:
                from dryad_tpu.exec.data import replicate_tree
                info = replicate_tree(info, self.mesh)
            with trace.span("settle", "wait", sink=ev, deferred=0):
                info = np.asarray(info)  # [P, 4+legs] (the ONE device sync)
            wall = time.time() - t0
            # exchange slot feedback rides the fetch — a retry (and every
            # later run of this stage) ships measured exact slots
            self._note_slot_feedback(stage, info)
            need_scale = int(info[:, 0].max())
            need_slack = int(info[:, 1].max())
            need_exch = int(info[:, 2].max())
            of = need_scale > 0 or need_slack > 0
            rows = info[:, 3].tolist()
            if range_attrs:
                range_attrs["tie_rows"] = int(info[:, _INFO_TIE].sum())
                span.set(**range_attrs)
            info_attrs = stage_info_attrs(info)
            span.set(**info_attrs)
            out_bytes = int(sum(
                x.size * x.dtype.itemsize
                for x in jax.tree.leaves(out_batch)))
            _M_STAGE_RUNS.inc()
            _M_RUN_S.inc(wall)
            _M_SHUFFLE_B.inc(out_bytes)
            if of:
                _M_CAP_RETRIES.inc()
            ev({"event": "stage_done", "stage": stage.id,
                "label": stage.label, "attempt": attempt,
                "scale": scale, "slack": slack, "overflow": of,
                "need_scale": need_scale,
                "need_slack": need_slack,
                "need_exchange": need_exch, "salted": salted,
                "rows": rows, "out_bytes": out_bytes,
                "compile_s": round(compile_s, 4),
                "cache_hit": cache_hit,
                "dispatches": 2,   # program launch + info fetch
                "wall_s": round(wall, 4), **join_attrs, **range_attrs,
                **filter_attrs, **info_attrs})
            decision = self._decide_needs(stage, scale, slack, salted,
                                          need_scale, need_slack,
                                          need_exch)
            if decision[0] == "ok":
                stage._capacity_scale = scale
                stage._send_slack = slack
                stage._salted = salted
                self._check_cost(stage, scale, int(sum(rows)), out_bytes,
                                 report=cost_report, event=ev)
                pd = PData(out_batch, self.nparts)
                if getattr(self.config, "adaptive", "off") == "on":
                    # rows arrived replicated on multi-process meshes,
                    # so every gang member records identical stats and
                    # the rewrite rules stay mirrored
                    from dryad_tpu.adapt.stats import StageStats
                    st = StageStats(
                        stage.id, tuple(int(r) for r in rows),
                        capacity=int(pd.capacity), out_bytes=out_bytes,
                        wall_s=round(wall, 4))
                    self._last_stage_stats = st
                    if stats_box is not None:
                        stats_box[0] = st
                return pd
            # right-size from the measured requirements (the dynamic
            # distribution managers' size feedback, DrDynamicDistributor
            # .cpp:388): ONE retry at the exact need instead of a blind
            # doubling ladder — a 90%-hot-key repartition converges in a
            # single retry where doubling took three.  The salted rewrite
            # (hot-key exchange skew, DrDynamicDistributor.h:79) is
            # decided inside _decide_needs.
            _, scale, slack, salted = decision
        kinds = _stage_kinds(stage)
        hint = ""
        if kinds & _FIXED_OVERFLOW_KINDS:
            hint = (" — note the stage also contains a fixed-capacity op "
                    f"({sorted(kinds & _FIXED_OVERFLOW_KINDS)}); if that is "
                    "the overflow source, raise its declared capacity "
                    "(scaling retries cannot fix it)")
        raise CapacityError(
            f"stage {stage.id} ({stage.label}) still overflowing after "
            f"{max_retries} capacity retries (scale={scale}, "
            f"slack={slack})" + hint)
