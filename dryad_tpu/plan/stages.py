"""Physical plan: a DAG of stages.

The counterpart of the reference's XML query plan + GM graph
(DryadLinqGraphManager/Query.cs — vertices with channel types and dynamic
managers; GraphBuilder.cs:564 building DrGraph stages).  Differences, by
design:

* A stage here is ONE jit+shard_map program executed SPMD over the partition
  mesh — local ops, an optional collective exchange, and post-exchange merge
  ops are fused into the same XLA program (the reference needs separate
  vertex processes + a materialized channel for each hop).
* Channel types (DISKFILE/TCPPIPE/MEMORYFIFO, Query.cs:64) collapse to:
  in-program XLA values (fusion), device-resident materialized arrays at
  stage boundaries (for fan-out/replay), and collective exchanges.
* Dynamic managers (SPLITTER/PARTIALAGGR/.../BROADCAST, Query.cs:34-43)
  become planner lowerings: partial+final aggregation around a hash
  exchange, broadcast via all_gather, range distribution via sampled bounds.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Tuple

__all__ = ["StageOp", "Exchange", "Leg", "Stage", "StageGraph"]

_stage_tokens = itertools.count()


@dataclasses.dataclass
class StageOp:
    """One fused local operator.  kind in:
    fn(map) | filter | flat_tokens | group | sort | distinct | join |
    semi_anti | concat | take | apply
    params are kind-specific (see exec.executor._apply_op)."""

    kind: str
    params: Dict[str, Any]
    # user-source provenance of the logical node this op lowers
    # ((file, line, func), plan/expr._creation_span): diagnostics and
    # runtime errors cite the query line.  NOT part of fingerprint().
    span: Optional[Tuple[str, int, str]] = None


@dataclasses.dataclass
class Exchange:
    """Collective repartition at a leg boundary.

    kind: hash | range | broadcast.  out_capacity resolved by the planner
    and scaled up by the executor on overflow (dynamic-repartition parity
    with DrDynamicDistributionManager).  A range exchange's ``keys`` are
    ALL its sort keys, in order, ``descending`` their directions (empty =
    all ascending): the splitters are sampled from stage ``bounds_from``
    and compared over every sort lane of every key
    (parallel/shuffle.range_dest)."""

    kind: str
    keys: Tuple[str, ...] = ()
    out_capacity: int = 0
    descending: Tuple[bool, ...] = ()
    bounds_from: Optional[int] = None  # stage id whose output seeds range bounds
    # None = global exchange over all mesh axes; "dp"/"dcn" = only that axis
    # (hierarchical aggregation hops, DrDynamicAggregateManager.h:99 parity)
    axis: Optional[str] = None

    def sort_keys(self) -> Tuple[Tuple[str, bool], ...]:
        """A range exchange's ``(column, descending)`` keys."""
        desc = self.descending or (False,) * len(self.keys)
        return tuple(zip(self.keys, desc))


@dataclasses.dataclass
class Leg:
    """One input arm of a stage: source stage (or bound source data), local
    ops applied before the exchange, optional exchange."""

    src: Any  # int stage id | ("source", data) | ("placeholder", name)
    ops: List[StageOp] = dataclasses.field(default_factory=list)
    exchange: Optional[Exchange] = None


@dataclasses.dataclass
class Stage:
    id: int
    legs: List[Leg]
    body: List[StageOp] = dataclasses.field(default_factory=list)
    label: str = ""
    token: int = dataclasses.field(default_factory=lambda: next(_stage_tokens))
    _capacity_scale: int = 1
    # send-slot slack factor for exchanges (C = ceil(slack*cap/D)); raised
    # by the executor from measured skew (dynamic-distribution feedback);
    # None = use JobConfig.initial_send_slack
    _send_slack: Optional[int] = None
    # True when the executor MAY rewrite this stage's exchanges into the
    # hot-key-salted form on skew overflow: a 2-leg hash-exchange join
    # whose output placement NO downstream stage assumed (the planner
    # clears it wherever partition elimination relied on the claim).
    # Reference: DrDynamicDistributor.h:79 dynamic redistribution.
    salt_ok: bool = False
    # True when a LATER lowering elided an exchange by trusting this
    # stage's output placement (the planner's placement_dependent
    # closure).  Adaptive rewrites that would change the output
    # placement (broadcast demotion, adapt/rules.BroadcastManager) must
    # refuse on these stages — the downstream elision would silently
    # mis-group.  salt_ok=False alone cannot encode this: broadcast
    # joins are born salt_ok=False without any reliance.
    placement_relied: bool = False
    _salted: bool = False   # executor runtime state (sticky per stage)

    def fingerprint(self) -> str:
        """Structural identity for the executor's compile cache.  Two stages
        with equal fingerprints and equal input shapes compute the same
        function, so a re-planned identical query (e.g. the same Dataset
        collected twice, or a do_while body) reuses compiled programs.
        Callables are identified by object id — fresh lambdas won't hit the
        cache, which is correct (their behavior is unknowable) just not
        optimal."""

        def val_fp(v) -> str:
            # shippable VALUES (plan/serialize.ship_ref_of — e.g. the
            # SQL front end's row-expression programs) fingerprint by
            # CONTENT: two submissions of the same query build fresh
            # objects computing the same function, and must hit the
            # compile cache (the service's warm-Nth-user story)
            if (hasattr(v, "__ship_payload__")
                    and hasattr(type(v), "__from_payload__")):
                import json
                return (f"ship:{type(v).__qualname__}:"
                        f"{json.dumps(v.__ship_payload__(), sort_keys=True)}")
            return "fn%x" % id(v) if callable(v) else repr(v)

        def op_fp(op: StageOp) -> str:
            items = []
            for k in sorted(op.params):
                items.append(f"{k}={val_fp(op.params[k])}")
            return f"{op.kind}({','.join(items)})"

        def ex_fp(ex: Optional[Exchange]) -> str:
            if ex is None:
                return "-"
            desc = "".join("d" if d else "a" for d in ex.descending)
            return (f"{ex.kind}[{','.join(ex.keys)}]cap{ex.out_capacity}"
                    f"{desc}@{ex.axis or '*'}")

        legs = ";".join(
            ",".join(op_fp(o) for o in leg.ops) + "=>" + ex_fp(leg.exchange)
            for leg in self.legs)
        body = ",".join(op_fp(o) for o in self.body)
        return f"legs:{legs}|body:{body}"

    def input_stage_ids(self) -> List[int]:
        out = []
        for leg in self.legs:
            if isinstance(leg.src, int):
                out.append(leg.src)
        bset = {leg.exchange.bounds_from for leg in self.legs
                if leg.exchange and leg.exchange.bounds_from is not None}
        out.extend(bset)
        return out


@dataclasses.dataclass
class StageGraph:
    stages: List[Stage]
    out_stage: int

    def stage(self, sid: int) -> Stage:
        return self.stages[sid]

    def topo_order(self) -> List[Stage]:
        # stages are created in topo order by the planner
        return self.stages

    def explain(self) -> str:
        """Plan pretty-printer (reference: DryadLinqQueryExplain.cs)."""
        lines = []
        for st in self.stages:
            srcs = []
            for leg in st.legs:
                if isinstance(leg.src, int):
                    s = f"stage{leg.src}"
                elif leg.src[0] == "placeholder":
                    s = f"placeholder:{leg.src[1]}"
                else:
                    s = "source"
                ops = ",".join(o.kind for o in leg.ops) or "-"
                ex = ""
                if leg.exchange:
                    ex = f" =>{leg.exchange.kind}({','.join(leg.exchange.keys)})"
                srcs.append(f"{s}[{ops}{ex}]")
            body = ",".join(o.kind for o in st.body) or "-"
            lines.append(f"stage{st.id} <{st.label}> legs: " +
                         " + ".join(srcs) + f" body: {body}")
        lines.append(f"output: stage{self.out_stage}")
        return "\n".join(lines)
