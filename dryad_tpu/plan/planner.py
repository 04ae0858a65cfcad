"""Logical -> physical lowering.

The counterpart of the reference's three query-gen phases
(DryadLinqQueryGen.cs: phase1 node creation :269, phase2 pipelining into
supernodes + Tee insertion :391-456, phase3 :459) plus GraphBuilder's dynamic
manager wiring (GraphBuilder.cs:620-729).  Our phases:

1. walk the expression DAG, counting consumers;
2. grow "fragments" (chains of local ops) along each edge — the supernode
   pipelining: everything row-local fuses into one stage program;
3. cut stages at exchange points (group-by, join, repartition, sort) and at
   fan-out (Tee: a multiply-consumed node is materialized once);
4. lower aggregations into partial + exchange + final (the IDecomposable /
   PARTIALAGGR pattern), sorts into sample -> range exchange -> local sort
   (the RANGEDISTRIBUTOR pattern), small-side joins into broadcast
   (BROADCAST pattern).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

from dryad_tpu.plan import expr as E
from dryad_tpu.plan.stages import Exchange, Leg, Stage, StageGraph, StageOp

__all__ = ["Planner", "plan_query"]


@dataclasses.dataclass
class Fragment:
    src: Any  # int stage id | ("source", data) | ("placeholder", name)
    ops: List[StageOp]
    capacity: int
    partitioning: E.Partitioning


# Decomposition of aggregates into partial (pre-shuffle) and final
# (post-shuffle) parts — reference IDecomposable.cs:34
# (Initialize/Seed/Accumulate/RecursiveAccumulate/FinalReduce).
def _decompose_aggs(aggs: Dict[str, Tuple[str, Optional[str]]]):
    partial: Dict[str, Tuple[str, Optional[str]]] = {}
    final: Dict[str, Tuple[str, Optional[str]]] = {}
    mean_cols: List[str] = []
    for out, (kind, col) in aggs.items():
        if kind == "count":
            partial[out] = ("count", None)
            final[out] = ("sum", out)
        elif kind in ("sum", "min", "max", "any", "all"):
            partial[out] = (kind, col)
            merge_kind = "sum" if kind == "sum" else kind
            final[out] = (merge_kind, out)
        elif kind == "sum64":
            # partial 64-bit sums (Int64Column) merge by the wide sum
            partial[out] = (kind, col)
            final[out] = ("sum", out)
        elif kind == "mean":
            partial[out + "__sum"] = ("sum", col)
            partial[out + "__cnt"] = ("count", None)
            final[out + "__sum"] = ("sum", out + "__sum")
            final[out + "__cnt"] = ("sum", out + "__cnt")
            mean_cols.append(out)
        else:
            raise ValueError(f"aggregate kind {kind!r} not decomposable")
    return partial, final, mean_cols


# Builtin aggregate kinds as Decomposable (seed, merge, finalize) triples —
# used when a group_by mixes builtin kinds with user-defined Decomposables
# so the whole aggregation runs through one segmented-scan path.
def _rowcount_of(cols) -> int:
    v = next(iter(cols.values()))
    return v.lengths.shape[0] if hasattr(v, "lengths") else v.shape[0]


def _builtin_as_decomposable(kind: str, col: Optional[str]):
    import jax.numpy as jnp

    if kind == "count":
        return E.Decomposable(
            lambda c: jnp.ones(_rowcount_of(c), jnp.int32),
            lambda a, b: a + b, None)
    if kind == "sum":
        return E.Decomposable(lambda c: c[col], lambda a, b: a + b, None)
    if kind == "min":
        return E.Decomposable(lambda c: c[col], jnp.minimum, None)
    if kind == "max":
        return E.Decomposable(lambda c: c[col], jnp.maximum, None)
    if kind == "any":
        return E.Decomposable(lambda c: c[col].astype(jnp.bool_),
                              lambda a, b: a | b, None)
    if kind == "all":
        return E.Decomposable(lambda c: c[col].astype(jnp.bool_),
                              lambda a, b: a & b, None)
    if kind == "mean":
        def fin(s):
            tot, cnt = s
            cf = jnp.maximum(cnt, 1)
            return tot / cf.astype(tot.dtype) \
                if jnp.issubdtype(tot.dtype, jnp.floating) \
                else tot.astype(jnp.float32) / cf
        return E.Decomposable(
            lambda c: (c[col],
                       jnp.ones(c[col].shape[0], jnp.int32)),
            lambda a, b: (a[0] + b[0], a[1] + b[1]), fin)
    raise ValueError(f"aggregate kind {kind!r} not decomposable")


def _normalize_decs(aggs: Dict[str, Any]) -> Dict[str, Any]:
    """aggs (builtin tuples and/or Decomposables) -> out -> SHIPPABLE dec
    spec: the user's Decomposable object itself (registrable by name for
    cluster shipping) or a ("__builtin__", kind, col) tag rebuilt on the
    executing side.  Kernels resolve specs to (seed, merge, finalize)
    triples at trace time (ops.kernels.resolve_dec_spec)."""
    out = {}
    for name, spec in aggs.items():
        if isinstance(spec, E.Decomposable):
            out[name] = spec
        else:
            kind, col = spec
            out[name] = ("__builtin__", kind, col)
    return out


def _has_user_decs(aggs: Dict[str, Any]) -> bool:
    return any(isinstance(v, E.Decomposable) for v in aggs.values())




class Planner:
    def __init__(self, npartitions: int, hosts: int = 1, config=None,
                 levels: tuple = ()):
        self.nparts = npartitions
        self.hosts = hosts  # >1 => multi-level mesh: hierarchical aggs
        # hierarchy axes INNERMOST FIRST ("dp", ["host",] "dcn") — one
        # combine stage per level (the reference's machine->pod->overall
        # aggregation trees, DrDynamicAggregateManager.h:99); 2-level
        # default keeps the classic ICI-then-DCN lowering
        self.levels = tuple(levels) or (("dp", "dcn") if hosts > 1
                                        else ())
        self.config = config
        self.stages: List[Stage] = []
        self.frags: Dict[int, Fragment] = {}
        self.consumers: Dict[int, int] = {}
        # stage ids whose OUTPUT PLACEMENT a later lowering relied on
        # (partition elimination): those stages must never be salted
        self.placement_dependent: set = set()

    def _rely_on_placement(self, f: Fragment) -> None:
        if isinstance(f.src, int):
            self.placement_dependent.add(f.src)

    # -- stage helpers -----------------------------------------------------

    def _new_stage(self, legs: List[Leg], body: List[StageOp],
                   label: str) -> Stage:
        st = Stage(id=len(self.stages), legs=legs, body=body, label=label)
        self.stages.append(st)
        return st

    def _materialize(self, frag: Fragment, label: str = "tee") -> Tuple[int, Fragment]:
        """Ensure the fragment is a stage output; return (stage_id, fresh frag)."""
        if isinstance(frag.src, int) and not frag.ops:
            return frag.src, frag
        st = self._new_stage([Leg(frag.src, frag.ops, None)], [], label)
        nf = Fragment(st.id, [], frag.capacity, frag.partitioning)
        return st.id, nf

    # -- main --------------------------------------------------------------

    def plan(self, root: E.Node) -> StageGraph:
        order = E.walk(root)
        for n in order:
            for p in n.parents:
                self.consumers[p.id] = self.consumers.get(p.id, 0) + 1
        for n in order:
            pre_stages = len(self.stages)
            frag = self._lower(n)
            if self.consumers.get(n.id, 0) > 1:
                _, frag = self._materialize(frag, label=f"tee:{type(n).__name__}")
            self.frags[n.id] = frag
            # provenance: ops created lowering THIS node (in the pending
            # fragment or in stages it cut) inherit its creation span;
            # ops carried over from earlier fragments keep their own
            span = getattr(n, "span", None)
            if span is not None:
                for op in frag.ops:
                    if op.span is None:
                        op.span = span
                for st in self.stages[pre_stages:]:
                    for leg in st.legs:
                        for op in leg.ops:
                            if op.span is None:
                                op.span = span
                    for op in st.body:
                        if op.span is None:
                            op.span = span
        out_id, _ = self._materialize(self.frags[root.id], label="output")
        # a placement claim flows backward through exchange-less legs
        # (Tee/materialize pass-throughs), so reliance must disable
        # salting on the whole ancestor chain that carries the claim —
        # conservative closure: it only forgoes an optimization
        dependent = set(self.placement_dependent)
        changed = True
        while changed:
            changed = False
            for st in self.stages:
                if st.id not in dependent:
                    continue
                for leg in st.legs:
                    if (leg.exchange is None and isinstance(leg.src, int)
                            and leg.src not in dependent):
                        dependent.add(leg.src)
                        changed = True
        for sid in dependent:
            self.stages[sid].salt_ok = False
            # the reliance itself is recorded for the adaptive rewriter:
            # rules that would change output placement must refuse here
            self.stages[sid].placement_relied = True
        return StageGraph(self.stages, out_id)

    def _lower_group_decomposable(self, n: "E.GroupByAgg", f: Fragment,
                                  keys: Tuple[str, ...]) -> Fragment:
        """GroupBy with user-defined Decomposable aggregates: seed+merge
        map-side combine -> hash exchange of flattened states -> merge (+
        FinalReduce).  The state treedefs travel through a shared box
        filled at partial-trace time (partial stages always trace before
        their merge stages).  Reference: IDecomposable.cs:34 feeding the
        GM's aggregation trees."""
        decs = _normalize_decs(n.aggs)
        box: Dict[str, Any] = {}  # shared mutable plan state (treedefs)
        if self.nparts == 1 or (f.partitioning.kind == "hash"
                                and f.partitioning.keys == keys):
            if self.nparts > 1:
                self._rely_on_placement(f)
            f.ops.append(StageOp("dgroup_local", {"keys": keys,
                                                  "decs": decs, "box": box}))
            f.partitioning = E.Partitioning("hash", keys)
            return f
        f.ops.append(StageOp("dgroup_partial", {"keys": keys, "decs": decs,
                                                "box": box}))
        if self.levels:
            src, ops = f.src, f.ops
            st = None
            for i, ax in enumerate(self.levels):
                last = i == len(self.levels) - 1
                ex = Exchange("hash", keys=keys, out_capacity=f.capacity,
                              axis=ax)
                st = self._new_stage(
                    [Leg(src, ops, ex)],
                    [StageOp("dgroup_merge",
                             {"keys": keys, "decs": decs, "box": box,
                              "finalize": last})],
                    f"dgroupby-{ax}")
                src, ops = st.id, []
            return Fragment(st.id, [], f.capacity,
                            E.Partitioning("hash", keys))
        ex = Exchange("hash", keys=keys, out_capacity=f.capacity)
        st = self._new_stage(
            [Leg(f.src, f.ops, ex)],
            [StageOp("dgroup_merge", {"keys": keys, "decs": decs,
                                      "box": box, "finalize": True})],
            "dgroupby")
        return Fragment(st.id, [], f.capacity, E.Partitioning("hash", keys))

    def _frag(self, n: E.Node) -> Fragment:
        f = self.frags[n.id]
        # fragments are single-use unless materialized; copy op list
        return Fragment(f.src, list(f.ops), f.capacity, f.partitioning)

    def _colocate_then(self, f: Fragment, keys: Tuple[str, ...],
                       op: StageOp, label: str,
                       out_capacity: Optional[int] = None) -> Fragment:
        """Hash-co-locate rows by ``keys`` then apply ``op`` — the shared
        lowering of the GroupBy-contents family (group_apply/top-k/rank).
        Partition elimination applies when the input already hashes on the
        same keys (AssumeHashPartition parity)."""
        cap = out_capacity or f.capacity
        if self.nparts == 1 or (f.partitioning.kind == "hash"
                                and f.partitioning.keys == keys and keys):
            if self.nparts > 1:
                self._rely_on_placement(f)
            f.ops.append(op)
            f.capacity = cap
            f.partitioning = E.Partitioning("hash", keys)
            return f
        ex = Exchange("hash", keys=keys, out_capacity=f.capacity)
        st = self._new_stage([Leg(f.src, f.ops, ex)], [op], label)
        return Fragment(st.id, [], cap, E.Partitioning("hash", keys))

    def _lower(self, n: E.Node) -> Fragment:
        if isinstance(n, E.Source):
            cap = getattr(n.data, "capacity", None)
            if cap is None:
                raise ValueError("Source.data must expose .capacity")
            return Fragment(("source", n.data), [], cap, n.partitioning)

        if isinstance(n, E.Placeholder):
            cap = getattr(n, "capacity", None) or 0
            return Fragment(("placeholder", n.name), [], cap, n.partitioning)

        if isinstance(n, E.Map):
            f = self._frag(n.parents[0])
            f.ops.append(StageOp("fn", {"fn": n.fn, "label": n.label}))
            return f

        if isinstance(n, E.Filter):
            f = self._frag(n.parents[0])
            f.ops.append(StageOp("filter", {"fn": n.fn, "label": n.label}))
            return f

        if isinstance(n, E.FlatTokens):
            f = self._frag(n.parents[0])
            f.ops.append(StageOp("flat_tokens", {
                "column": n.column, "out_capacity": n.out_capacity,
                "max_token_len": n.max_token_len, "delims": n.delims,
                "lower": n.lower,
                "max_tokens_per_row": n.max_tokens_per_row}))
            f.capacity = n.out_capacity
            f.partitioning = E.Partitioning.none()
            return f

        if isinstance(n, E.ApplyPerPartition):
            f = self._frag(n.parents[0])
            f.ops.append(StageOp("apply", {"fn": n.fn, "label": n.label,
                                           "with_index": n.with_index}))
            f.partitioning = n.partitioning
            return f

        if isinstance(n, E.FlatMap):
            f = self._frag(n.parents[0])
            f.ops.append(StageOp("flat_map", {
                "fn": n.fn, "out_capacity": n.out_capacity,
                "label": n.label}))
            f.capacity = n.out_capacity
            f.partitioning = E.Partitioning.none()
            return f

        if isinstance(n, E.Zip):
            lf = self._frag(n.parents[0])
            rf = self._frag(n.parents[1])
            st = self._new_stage(
                [Leg(lf.src, lf.ops, None), Leg(rf.src, rf.ops, None)],
                [StageOp("zip", {"suffix": n.suffix})], "zip")
            return Fragment(st.id, [], min(lf.capacity, rf.capacity),
                            E.Partitioning.none())

        if isinstance(n, E.SlidingWindow):
            f = self._frag(n.parents[0])
            f.ops.append(StageOp("sliding_window", {"w": n.w}))
            f.partitioning = E.Partitioning.none()
            return f

        if isinstance(n, E.WithRowIndex):
            f = self._frag(n.parents[0])
            f.ops.append(StageOp("row_index", {"column": n.column}))
            return f

        if isinstance(n, E.AssumePartitioning):
            f = self._frag(n.parents[0])
            f.partitioning = E.Partitioning(n.kind, tuple(n.keys))
            return f

        if isinstance(n, E.SkipTake):
            f = self._frag(n.parents[0])
            if n.op == "skip":
                f.ops.append(StageOp("skip", {"n": n.n}))
            else:
                f.ops.append(StageOp(n.op, {"fn": n.fn}))
            return f

        if isinstance(n, E.Take):
            f = self._frag(n.parents[0])
            f.ops.append(StageOp("take", {"n": n.n, "global": True}))
            return f

        if isinstance(n, E.WithCapacity):
            f = self._frag(n.parents[0])
            f.ops.append(StageOp("recap", {"capacity": n.capacity}))
            f.capacity = n.capacity
            return f

        if isinstance(n, E.CrossApply):
            lf = self._frag(n.parents[0])
            rf = self._frag(n.parents[1])
            rex = None if self.nparts == 1 else Exchange(
                "broadcast", out_capacity=rf.capacity * self.nparts)
            st = self._new_stage(
                [Leg(lf.src, lf.ops, None), Leg(rf.src, rf.ops, rex)],
                [StageOp("apply2", {"fn": n.fn, "label": n.label})],
                "cross_apply")
            return Fragment(st.id, [], lf.capacity, E.Partitioning.none())

        if isinstance(n, E.GroupByAgg):
            f = self._frag(n.parents[0])
            keys = tuple(n.keys)
            if _has_user_decs(n.aggs):
                return self._lower_group_decomposable(n, f, keys)
            if self.nparts == 1:
                # single partition: everything is trivially co-located; the
                # partial/exchange/merge pipeline would be 3 extra full-batch
                # sorts for nothing
                f.ops.append(StageOp("group", {"keys": keys,
                                               "aggs": dict(n.aggs)}))
                f.partitioning = E.Partitioning("hash", keys)
                return f
            if f.partitioning.kind == "hash" and f.partitioning.keys == keys:
                # partition elimination: already co-located by these keys
                self._rely_on_placement(f)
                f.ops.append(StageOp("group", {"keys": keys, "aggs": dict(n.aggs)}))
                return f
            partial, final, mean_cols = _decompose_aggs(n.aggs)
            f.ops.append(StageOp("group", {"keys": keys, "aggs": partial}))
            if self.levels:
                # hierarchical aggregation over mesh axes (the reference's
                # machine->pod->overall trees,
                # DrDynamicAggregateManager.h:99): combine innermost
                # first, so each scarcer fabric carries one partial per
                # (level, key) instead of one per (device, key); depth
                # follows the mesh rank (3-level: dp -> host -> dcn)
                src, ops = f.src, f.ops
                st = None
                for i, ax in enumerate(self.levels):
                    last = i == len(self.levels) - 1
                    ex = Exchange("hash", keys=keys,
                                  out_capacity=f.capacity, axis=ax)
                    body: List[StageOp] = [
                        StageOp("group", {"keys": keys, "aggs": final})]
                    if last and mean_cols:
                        body.append(StageOp("mean_fin",
                                            {"cols": mean_cols}))
                    st = self._new_stage([Leg(src, ops, ex)], body,
                                         f"groupby-{ax}")
                    src, ops = st.id, []
                return Fragment(st.id, [], f.capacity,
                                E.Partitioning("hash", keys))
            ex = Exchange("hash", keys=keys, out_capacity=f.capacity)
            body = [StageOp("group", {"keys": keys, "aggs": final})]
            if mean_cols:
                body.append(StageOp("mean_fin", {"cols": mean_cols}))
            st = self._new_stage([Leg(f.src, f.ops, ex)], body, "groupby")
            return Fragment(st.id, [], f.capacity,
                            E.Partitioning("hash", keys))

        if isinstance(n, E.GroupApply):
            f = self._frag(n.parents[0])
            keys = tuple(n.keys)
            mg = n.max_groups or f.capacity
            oc = n.out_capacity or f.capacity
            op = StageOp("group_apply", {
                "keys": keys, "fn": n.fn, "max_groups": mg,
                "group_capacity": n.group_capacity,
                "out_rows": n.out_rows, "out_capacity": oc})
            return self._colocate_then(f, keys, op, "group_apply",
                                       out_capacity=oc)

        if isinstance(n, E.GroupTopK):
            f = self._frag(n.parents[0])
            op = StageOp("group_top_k", {
                "keys": tuple(n.keys), "k": n.k, "by": n.by,
                "descending": n.descending})
            return self._colocate_then(f, tuple(n.keys), op, "group_top_k")

        if isinstance(n, E.GroupRankSelect):
            f = self._frag(n.parents[0])
            op = StageOp("group_rank", {
                "keys": tuple(n.keys), "by": n.by, "rank": n.rank,
                "out": n.out})
            return self._colocate_then(f, tuple(n.keys), op, "group_rank")

        if isinstance(n, E.Distinct):
            f = self._frag(n.parents[0])
            keys = tuple(n.keys)
            if self.nparts == 1:
                f.ops.append(StageOp("distinct", {"keys": keys}))
                return f
            if f.partitioning.kind == "hash" and f.partitioning.keys == keys \
                    and keys:
                self._rely_on_placement(f)
                f.ops.append(StageOp("distinct", {"keys": keys}))
                return f
            f.ops.append(StageOp("distinct", {"keys": keys}))
            ex = Exchange("hash", keys=keys, out_capacity=f.capacity)
            st = self._new_stage(
                [Leg(f.src, f.ops, ex)],
                [StageOp("distinct", {"keys": keys})], "distinct")
            return Fragment(st.id, [], f.capacity, E.Partitioning("hash", keys))

        if isinstance(n, E.Join):
            lf = self._frag(n.parents[0])
            rf = self._frag(n.parents[1])
            lkeys, rkeys = tuple(n.left_keys), tuple(n.right_keys)
            out_cap = max(1, int(lf.capacity * n.expansion))
            # auto-broadcast a small build side (JobConfig
            # .broadcast_join_threshold; the reference's small-side
            # broadcast-join rewrite, DrDynamicBroadcastManager role)
            bthresh = getattr(self.config, "broadcast_join_threshold", 0.0) \
                if self.config else 0.0
            broadcast_right = n.broadcast_right or (
                bthresh > 0
                and rf.capacity * self.nparts <= bthresh * lf.capacity)
            if n.how in ("right", "full"):
                # a replicated right side would emit its unmatched rows once
                # PER PARTITION — right/full joins must co-locate by key
                broadcast_right = False
            if self.nparts == 1:
                lex = rex = None
            elif broadcast_right:
                rex = Exchange("broadcast",
                               out_capacity=rf.capacity * self.nparts)
                lex = None
            else:
                lex = None if (lf.partitioning.kind == "hash"
                               and lf.partitioning.keys == lkeys) else \
                    Exchange("hash", keys=lkeys, out_capacity=lf.capacity)
                rex = None if (rf.partitioning.kind == "hash"
                               and rf.partitioning.keys == rkeys) else \
                    Exchange("hash", keys=rkeys, out_capacity=rf.capacity)
                if lex is None:
                    self._rely_on_placement(lf)
                if rex is None:
                    self._rely_on_placement(rf)
            st = self._new_stage(
                [Leg(lf.src, lf.ops, lex), Leg(rf.src, rf.ops, rex)],
                [StageOp("join", {"left_keys": lkeys, "right_keys": rkeys,
                                  "out_capacity": out_cap,
                                  "how": n.how,
                                  "right_unique": n.right_unique})],
                "join")
            # the executor may salt this stage's exchanges on hot-key skew
            # — only the 2-hash-exchange inner/left shape, and plan() later
            # clears it where downstream elimination assumed the placement
            st.salt_ok = (lex is not None and rex is not None
                          and n.how in ("inner", "left")
                          and not broadcast_right)
            # broadcast join keeps the LEFT side's distribution (each
            # partition holds matches for its own left rows only)
            out_part = lf.partitioning if broadcast_right \
                else E.Partitioning("hash", lkeys)
            return Fragment(st.id, [], out_cap, out_part)

        if isinstance(n, E.OrderBy):
            f = self._frag(n.parents[0])
            sort_keys = tuple(k for k, _ in n.keys)
            all_asc = all(not d for _, d in n.keys)
            if self.nparts == 1:
                f.ops.append(StageOp("sort", {"keys": tuple(n.keys)}))
                f.partitioning = (E.Partitioning("range", sort_keys)
                                  if all_asc else E.Partitioning.none())
                return f
            pkeys = f.partitioning.keys
            if (f.partitioning.kind == "range" and all_asc
                    and len(sort_keys) <= len(pkeys)
                    and sort_keys == pkeys[:len(sort_keys)]):
                self._rely_on_placement(f)
                # Exchange elimination (AssumeOrderBy,
                # DryadLinqQueryable.cs:3639): sound only when the requested
                # ascending sort keys are a PREFIX of the claimed range keys.
                # "range(keys)" guarantees globally-sorted-by-keys data in
                # partition order but NOT that key ties are co-located
                # (assume_order_by data may split a tie run across
                # partitions), so a sort introducing any key beyond the
                # claim — or any descending direction — must keep its
                # exchange.  A stable local prefix sort of
                # already-(claim-)sorted partitions preserves the FULL
                # claim, so the original partitioning survives.
                f.ops.append(StageOp("sort", {"keys": tuple(n.keys)}))
                return f
            src_id, f = self._materialize(f, label="sort-input")
            ex = Exchange("range", keys=sort_keys, out_capacity=f.capacity,
                          descending=tuple(d for _, d in n.keys),
                          bounds_from=src_id)
            st = self._new_stage(
                [Leg(src_id, [], ex)],
                [StageOp("sort", {"keys": tuple(n.keys)})], "orderby")
            # the exchange compares the WHOLE key list, each key in its
            # direction, and cuts a run of equal keys by input position
            # (shuffle.range_dest): partition p holds only rows that
            # sort at or before partition p+1's, ties may straddle, and
            # the stable local sort orders each partition — the output
            # is globally sorted by all sort keys, equal keys in input
            # order.  The "range" claim is made for ascending keys only
            # (its consumer, the elimination above, assumes ascending)
            return Fragment(st.id, [], f.capacity,
                            E.Partitioning("range", sort_keys)
                            if all_asc else E.Partitioning.none())

        if isinstance(n, E.SetOp):
            lf = self._frag(n.parents[0])
            rf = self._frag(n.parents[1])
            lf.ops.append(StageOp("distinct", {"keys": ()}))
            if n.op != "union":
                rf.ops.append(StageOp("distinct", {"keys": ()}))
            lex = rex = None
            if self.nparts > 1:
                lex = Exchange("hash", keys=(), out_capacity=lf.capacity)
                rex = Exchange("hash", keys=(), out_capacity=rf.capacity)
            # the per-leg distinct dedups within a partition; after the
            # exchange, copies arriving from different partitions are
            # co-located, so a post-exchange distinct finishes the dedup
            if n.op == "union":
                body = [StageOp("concat", {}), StageOp("distinct", {"keys": ()})]
                cap = lf.capacity + rf.capacity
            elif n.op == "intersect":
                body = [StageOp("semi_anti", {"anti": False}),
                        StageOp("distinct", {"keys": ()})]
                cap = lf.capacity
            elif n.op == "except":
                body = [StageOp("semi_anti", {"anti": True}),
                        StageOp("distinct", {"keys": ()})]
                cap = lf.capacity
            else:
                raise ValueError(n.op)
            st = self._new_stage(
                [Leg(lf.src, lf.ops, lex), Leg(rf.src, rf.ops, rex)],
                body, n.op)
            return Fragment(st.id, [], cap, E.Partitioning("hash", ()))

        if isinstance(n, E.Concat):
            lf = self._frag(n.parents[0])
            rf = self._frag(n.parents[1])
            st = self._new_stage(
                [Leg(lf.src, lf.ops, None), Leg(rf.src, rf.ops, None)],
                [StageOp("concat", {})], "concat")
            return Fragment(st.id, [], lf.capacity + rf.capacity,
                            E.Partitioning.none())

        if isinstance(n, E.HashRepartition):
            f = self._frag(n.parents[0])
            if self.nparts == 1:
                f.partitioning = E.Partitioning("hash", tuple(n.keys))
                return f
            ex = Exchange("hash", keys=tuple(n.keys), out_capacity=f.capacity)
            st = self._new_stage([Leg(f.src, f.ops, ex)], [], "hashpartition")
            return Fragment(st.id, [], f.capacity,
                            E.Partitioning("hash", tuple(n.keys)))

        if isinstance(n, E.RangeRepartition):
            f = self._frag(n.parents[0])
            if self.nparts == 1:
                f.partitioning = E.Partitioning("range", tuple(n.keys))
                return f
            src_id, f = self._materialize(f, label="range-input")
            ex = Exchange("range", keys=tuple(n.keys),
                          out_capacity=f.capacity, bounds_from=src_id)
            st = self._new_stage([Leg(src_id, [], ex)], [], "rangepartition")
            return Fragment(st.id, [], f.capacity,
                            E.Partitioning("range", tuple(n.keys)))

        if isinstance(n, E.Broadcast):
            f = self._frag(n.parents[0])
            if self.nparts == 1:
                f.partitioning = E.Partitioning("replicated")
                return f
            ex = Exchange("broadcast",
                          out_capacity=f.capacity * self.nparts)
            st = self._new_stage([Leg(f.src, f.ops, ex)], [], "broadcast")
            return Fragment(st.id, [], f.capacity * self.nparts,
                            E.Partitioning("replicated"))

        raise TypeError(f"planner: unhandled node {type(n).__name__}")


def plan_query(root: E.Node, npartitions: int, hosts: int = 1,
               config=None, levels: tuple = ()) -> StageGraph:
    return Planner(npartitions, hosts=hosts, config=config,
                   levels=levels).plan(root)
