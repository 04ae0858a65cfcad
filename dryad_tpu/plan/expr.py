"""Logical query expression DAG.

The counterpart of the reference's plan node model
(LinqToDryad/DryadLinqQueryNode.cs:39 — `QueryNodeType` with 33 node kinds,
`DLinqQueryNode` carrying partition count/scheme/channel info).  A user's
``Dataset`` method chain builds this DAG lazily; the planner
(dryad_tpu/plan/planner.py) lowers it to physical stages.

Unlike the reference — whose nodes emit C# vertex code strings
(DryadLinqCodeGen.cs) — our nodes carry Python callables over columnar
Batches that will be traced and fused by XLA inside each stage's jit.

Partitioning metadata (`Partitioning`) mirrors the reference's partition-info
tracking used for shuffle elimination (DryadLinqQueryNode partition info /
`AssumeHashPartition`, DryadLinqQueryable.cs:3408).
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import sys
from typing import Any, Callable, Dict, Optional, Tuple

__all__ = [
    "Partitioning", "Node", "Source", "Placeholder", "Map", "Filter",
    "FlatTokens", "GroupByAgg", "GroupApply", "GroupTopK", "GroupRankSelect",
    "Join", "OrderBy", "Distinct", "Concat",
    "HashRepartition", "RangeRepartition", "Broadcast", "ApplyPerPartition",
    "Take", "SetOp", "WithCapacity", "CrossApply", "FlatMap", "Zip",
    "SlidingWindow", "WithRowIndex", "AssumePartitioning", "SkipTake",
    "walk",
]

_ids = itertools.count()

# creation-site provenance: every Node captures the first stack frame
# OUTSIDE the framework (dryad_tpu/* except apps/, which are user-shaped
# samples), so diagnostics (dryad_tpu/analysis) and runtime errors point
# at the user's query line — the reference keeps the LINQ expression's
# source info for exactly this (DryadLinqQueryGen error reporting)
_PKG_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_APPS_DIR = os.path.join(_PKG_ROOT, "apps")


def _creation_span() -> Optional[Tuple[str, int, str]]:
    f = sys._getframe(1)
    depth = 0
    while f is not None and depth < 32:
        fn = f.f_code.co_filename
        internal = (fn.startswith("<")
                    or (fn.startswith(_PKG_ROOT)
                        and not fn.startswith(_APPS_DIR)))
        if not internal:
            return (fn, f.f_lineno, f.f_code.co_name)
        f = f.f_back
        depth += 1
    return None


@dataclasses.dataclass(frozen=True)
class Partitioning:
    """How a dataset's rows are distributed over partitions."""

    kind: str  # "none" | "hash" | "range" | "replicated" | "single"
    keys: Tuple[str, ...] = ()

    @staticmethod
    def none() -> "Partitioning":
        return Partitioning("none")


class Node:
    """Base logical node.  Subclasses are dataclasses with `parents`."""

    id: int
    parents: Tuple["Node", ...]
    # (file, line, function) of the user call that created the node —
    # not a dataclass field (set in __post_init__, excluded from eq/repr)
    span: Optional[Tuple[str, int, str]]

    def __post_init__(self):
        object.__setattr__(self, "id", next(_ids))
        object.__setattr__(self, "span", _creation_span())

    @property
    def npartitions(self) -> int:
        return self.parents[0].npartitions

    @property
    def partitioning(self) -> Partitioning:
        """Partitioning of the output; default: destroyed by the op unless
        the op is row-local (preserves parent partitioning)."""
        return self.parents[0].partitioning


def _node(cls):
    return dataclasses.dataclass(frozen=True, eq=False)(cls)


@_node
class Source(Node):
    """Materialized input: a PBatch handle (exec.data.PartitionedData) or a
    store reference resolved by the executor.  Reference: DLinqInputNode
    (DryadLinqQueryNode.cs:837)."""

    parents: Tuple[Node, ...]
    data: Any
    _npartitions: int
    _partitioning: Partitioning = Partitioning.none()
    host: Any = None  # host-side copy of the columns, for the oracle

    @property
    def npartitions(self) -> int:
        return self._npartitions

    @property
    def partitioning(self) -> Partitioning:
        return self._partitioning


@_node
class Placeholder(Node):
    """Loop-carried input for do_while bodies; bound at execution time."""

    parents: Tuple[Node, ...]
    name: str
    _npartitions: int
    capacity: int = 0
    _partitioning: Partitioning = Partitioning.none()

    @property
    def npartitions(self) -> int:
        return self._npartitions

    @property
    def partitioning(self) -> Partitioning:
        return self._partitioning


@_node
class Map(Node):
    """Columnwise projection/transform: fn(cols) -> cols.
    Reference: DLinqSelectNode (DryadLinqQueryNode.cs:1155)."""

    parents: Tuple[Node, ...]
    fn: Callable
    label: str = "map"


@_node
class Filter(Node):
    """fn(cols) -> bool mask.  Reference: Where."""

    parents: Tuple[Node, ...]
    fn: Callable
    label: str = "where"


@_node
class FlatTokens(Node):
    """Tokenizing SelectMany over a string column (the WordCount kernel)."""

    parents: Tuple[Node, ...]
    column: str
    out_capacity: int
    max_token_len: int = 24
    delims: bytes = b" \t\r\n.,;:!?\"'()[]{}<>"
    lower: bool = False
    # static per-row token bound (None = the ceil(L/2) worst case); the
    # tokenizer's slot grid is cap x bound, so a workload-tuned bound
    # shrinks its dominant sort; overflow feeds the NEED retry channel
    max_tokens_per_row: int | None = None

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning.none()


@_node
class ApplyPerPartition(Node):
    """Arbitrary per-partition Batch -> Batch function (escape hatch).
    Reference: ApplyPerPartition (DryadLinqQueryable.cs:1084)."""

    parents: Tuple[Node, ...]
    fn: Callable
    label: str = "apply"
    preserves_partitioning: bool = False
    with_index: bool = False  # fn(batch, partition_index) when True
    host_fn: Any = None  # oracle interpretation (fn over the whole table)

    @property
    def partitioning(self) -> Partitioning:
        if self.preserves_partitioning:
            return self.parents[0].partitioning
        return Partitioning.none()


@dataclasses.dataclass(frozen=True)
class Decomposable:
    """User-defined decomposable aggregate (IDecomposable.cs:34 parity:
    Initialize/Seed -> ``seed``, Accumulate/RecursiveAccumulate ->
    ``merge``, FinalReduce -> ``finalize``).

    * ``seed(columns) -> state``: map the row columns (arrays, vectorized
      over rows) to a state pytree;
    * ``merge(a, b) -> state``: ASSOCIATIVE combine of two states
      (elementwise over rows — it runs inside a segmented scan);
    * ``finalize(state) -> value | dict[str, value]``: per-group result
      (None = identity; a dict fans out to multiple columns).
    """

    seed: Any
    merge: Any
    finalize: Any = None


@_node
class GroupByAgg(Node):
    """GroupBy + decomposable aggregation.
    aggs: out_name -> (kind, value_col | None) builtin aggregate, or a
    ``Decomposable`` for user-defined seed/merge/finalize.
    Reference: DLinqGroupByNode (DryadLinqQueryNode.cs:1581) +
    IDecomposable (IDecomposable.cs:34)."""

    parents: Tuple[Node, ...]
    keys: Tuple[str, ...]
    aggs: Dict[str, Any]

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning("hash", tuple(self.keys))


@_node
class GroupApply(Node):
    """GroupBy yielding group CONTENTS to an arbitrary per-group fn — the
    reference's general GroupBy result selector
    (DryadLinqVertex.cs:510-753, IGrouping to user code).
    fn(cols, count) -> (out_cols [out_rows, ...], mask [out_rows]); group
    keys are auto-attached to the output.  None capacities resolve to the
    input capacity at plan time."""

    parents: Tuple[Node, ...]
    keys: Tuple[str, ...]
    fn: Callable
    group_capacity: int
    max_groups: Optional[int] = None
    out_rows: int = 1
    out_capacity: Optional[int] = None

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning("hash", tuple(self.keys))


@_node
class GroupTopK(Node):
    """Per-group top-k rows by a column (all columns kept)."""

    parents: Tuple[Node, ...]
    keys: Tuple[str, ...]
    k: int
    by: str
    descending: bool = True

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning("hash", tuple(self.keys))


@_node
class GroupRankSelect(Node):
    """One row per group at a sorted rank of a column (median/min/max)."""

    parents: Tuple[Node, ...]
    keys: Tuple[str, ...]
    by: str
    rank: str = "median"
    out: Optional[str] = None

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning("hash", tuple(self.keys))


@_node
class Join(Node):
    """Equi-join (inner, or left-outer with zero-filled right columns).
    Reference: DLinqJoinNode (DryadLinqQueryNode.cs:2053); how="left" is
    the GroupJoin empty-group case."""

    parents: Tuple[Node, ...]  # (left, right)
    left_keys: Tuple[str, ...]
    right_keys: Tuple[str, ...]
    expansion: float = 1.0  # out_capacity multiplier over left capacity
    broadcast_right: bool = False
    how: str = "inner"
    # caller hint: right keys are unique (a lookup/dimension table) —
    # enables the gather-free merge-fill join path, VERIFIED at runtime
    # (falls back to the general path when duplicates appear); or
    # "verified": a key established where the right side's rows were
    # written — the merge-fill path alone, nothing checked at runtime
    right_unique: bool | str = False

    @property
    def npartitions(self) -> int:
        return self.parents[0].npartitions

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning("hash", tuple(self.left_keys))


@_node
class OrderBy(Node):
    """Global sort via sampling + range partition + local sort.
    Reference: DLinqOrderByNode; sampling DryadLinqSampler.cs:42."""

    parents: Tuple[Node, ...]
    keys: Tuple[Tuple[str, bool], ...]  # (column, descending)

    @property
    def partitioning(self) -> Partitioning:
        # as the planner: the claim is made for ascending keys only (a
        # reader that relies on it sorts each partition and keeps their
        # order, which under a descending key is the reverse)
        if any(desc for _, desc in self.keys):
            return Partitioning.none()
        return Partitioning("range", tuple(k for k, _ in self.keys))


@_node
class Distinct(Node):
    parents: Tuple[Node, ...]
    keys: Tuple[str, ...]  # empty = all columns

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning("hash", tuple(self.keys))


@_node
class SetOp(Node):
    """Union/Intersect/Except with set semantics (dedup), over all columns."""

    parents: Tuple[Node, ...]  # (left, right)
    op: str  # "union" | "intersect" | "except"

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning("hash", ())


@_node
class Concat(Node):
    parents: Tuple[Node, ...]  # (left, right)

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning.none()


@_node
class HashRepartition(Node):
    """Explicit HashPartition (DryadLinqQueryable.cs:275)."""

    parents: Tuple[Node, ...]
    keys: Tuple[str, ...]

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning("hash", tuple(self.keys))


@_node
class RangeRepartition(Node):
    """Explicit RangePartition (DryadLinqQueryable.cs:518)."""

    parents: Tuple[Node, ...]
    keys: Tuple[str, ...]

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning("range", tuple(self.keys))


@_node
class Broadcast(Node):
    """Replicate a (small) dataset to every partition.
    Reference: DrDynamicBroadcastManager (DrDynamicBroadcast.h:23)."""

    parents: Tuple[Node, ...]

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning("replicated")


@_node
class Take(Node):
    parents: Tuple[Node, ...]
    n: int


@_node
class FlatMap(Node):
    """Generic SelectMany: fn(cols) -> (out_cols each [cap, m, ...],
    mask [cap, m]); rows flattened in row-major order then compacted.
    Reference: SelectMany (DryadLinqQueryable.cs SelectMany overloads)."""

    parents: Tuple[Node, ...]
    fn: Callable
    out_capacity: int
    label: str = "flat_map"

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning.none()


@_node
class Zip(Node):
    """Pairwise combination by GLOBAL position (shorter-side semantics).
    Lowered to a realignment exchange: right rows move to the partition
    holding the same global row index on the left, so misaligned
    per-partition counts (e.g. after a filter) pair correctly
    (parallel/shuffle.zip_exchange).  Reference: DryadLinqQueryable Zip."""

    parents: Tuple[Node, ...]  # (left, right)
    suffix: str = "_r"

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning.none()


@_node
class SlidingWindow(Node):
    """Each row becomes the window of ``w`` consecutive rows starting at it
    (windows crossing the dataset end are dropped); columns gain a window
    axis.  Distributed via a halo exchange: every partition receives the
    first w-1 rows of the next partition over ICI (ppermute).
    Reference: SlidingWindow (DryadLinqQueryable.cs:1318)."""

    parents: Tuple[Node, ...]
    w: int


@_node
class WithRowIndex(Node):
    """Add a global row-index column (reference: the Long*/indexed operator
    variants, e.g. LongSelect with (elem, index) lambdas)."""

    parents: Tuple[Node, ...]
    column: str = "row_index"


@_node
class AssumePartitioning(Node):
    """Declare (without shuffling) that the data is already partitioned this
    way.  Reference: AssumeHashPartition / AssumeRangePartition
    (DryadLinqQueryable.cs:3408,3478)."""

    parents: Tuple[Node, ...]
    kind: str
    keys: Tuple[str, ...]

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning(self.kind, tuple(self.keys))


@_node
class SkipTake(Node):
    """Global skip / take_while / skip_while."""

    parents: Tuple[Node, ...]
    op: str  # "skip" | "take_while" | "skip_while"
    n: int = 0
    fn: Any = None


@_node
class WithCapacity(Node):
    """Coerce per-partition capacity (pad or truncate-with-overflow-check).
    Needed so do_while loop bodies keep shapes stable across iterations."""

    parents: Tuple[Node, ...]
    capacity: int


@_node
class CrossApply(Node):
    """Binary per-partition op: fn(left_batch, right_broadcast_batch) ->
    Batch.  The right side is replicated to every partition (small data).
    host_fn(table_l, table_r) -> table is the oracle's interpretation.
    Reference: the Apply overloads taking a second source
    (DryadLinqQueryable.cs:930-1045)."""

    parents: Tuple[Node, ...]  # (left, right)
    fn: Any
    host_fn: Any = None
    label: str = "cross_apply"

    @property
    def npartitions(self) -> int:
        return self.parents[0].npartitions

    @property
    def partitioning(self) -> Partitioning:
        return Partitioning.none()


def walk(root: Node):
    """Topological (parents-first) walk, each node once."""
    seen = set()
    order = []

    def visit(n: Node):
        if n.id in seen:
            return
        seen.add(n.id)
        for p in n.parents:
            visit(p)
        order.append(n)

    visit(root)
    return order
