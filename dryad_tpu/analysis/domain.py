"""Abstract domain for the static cost analyzer (analysis/cost.py).

The cost interpreter propagates two things over the plan:

* an :class:`Interval` of TOTAL valid row counts (lo certain, hi a sound
  upper bound, ``None`` = unbounded) — the row-count half of the domain;
* a concrete column schema (:class:`ColSpec` per column) plus the static
  per-partition capacity — the byte half.  Capacities are exact in this
  system (every batch is a fixed-shape padded tensor), so when the
  schema is known the materialized bytes of a stage output are KNOWN,
  not estimated: ``nparts * (capacity * row_bytes + 4)`` matches the
  executor's ``out_bytes`` (sum of leaf ``size * itemsize`` over the
  ``[P, cap, ...]`` batch, count vector included) to the byte.

Schema propagation through user callables uses ``jax.eval_shape`` — the
UDF is traced abstractly (zero FLOPs, zero device work), which is the
TPU-native way to "type-check" a Python callable.  Dependency note: this
module itself imports only numpy; jax is imported lazily inside the
abstract-batch helpers so the offline CLI path (serialized plans, no
callables) never needs it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

__all__ = ["Interval", "ColSpec", "AbsState", "schema_row_bytes",
           "schema_from_store_schema", "schema_from_columns",
           "schema_from_host_columns", "abstract_batch",
           "schema_of_abstract", "part_bytes", "out_bytes"]

# the executor materializes the [P] int32 count vector with every batch
_COUNT_BYTES_PER_PART = 4


@dataclasses.dataclass(frozen=True)
class Interval:
    """Integer interval [lo, hi]; ``hi=None`` means unbounded above.
    ``lo`` is a certain lower bound, ``hi`` a sound upper bound."""

    lo: int = 0
    hi: Optional[int] = None

    @staticmethod
    def exact(v: int) -> "Interval":
        return Interval(int(v), int(v))

    @staticmethod
    def upto(hi: Optional[int]) -> "Interval":
        return Interval(0, None if hi is None else int(hi))

    @property
    def bounded(self) -> bool:
        return self.hi is not None

    def __add__(self, other: "Interval") -> "Interval":
        hi = (None if self.hi is None or other.hi is None
              else self.hi + other.hi)
        return Interval(self.lo + other.lo, hi)

    def scale(self, k: int) -> "Interval":
        return Interval(self.lo * k,
                        None if self.hi is None else self.hi * k)

    def mul(self, other: "Interval") -> "Interval":
        hi = (None if self.hi is None or other.hi is None
              else self.hi * other.hi)
        return Interval(self.lo * other.lo, hi)

    def clamp_hi(self, cap: Optional[int]) -> "Interval":
        """Intersect with [0, cap] (a capacity bound)."""
        if cap is None:
            return self
        hi = cap if self.hi is None else min(self.hi, cap)
        return Interval(min(self.lo, hi), hi)

    def relax_lo(self) -> "Interval":
        """Drop the lower bound (ops that may shed rows)."""
        return Interval(0, self.hi)

    def contains(self, v: int) -> bool:
        return v >= self.lo and (self.hi is None or v <= self.hi)

    def union(self, other: "Interval") -> "Interval":
        hi = (None if self.hi is None or other.hi is None
              else max(self.hi, other.hi))
        return Interval(min(self.lo, other.lo), hi)

    def as_tuple(self) -> Tuple[int, Optional[int]]:
        return (self.lo, self.hi)


@dataclasses.dataclass(frozen=True)
class ColSpec:
    """Static description of one column's device representation.

    * dense: ``[capacity, *shape] dtype`` — row_bytes = itemsize * prod
    * str: ``[capacity, repeat?, max_len] u8`` data + int32 lengths —
      row_bytes = repeat * (max_len + 4)
    * int64: ``[capacity] int32`` upper + ``[capacity] uint32`` lower
      words (data/columnar.Int64Column) — row_bytes = 8

    ``repeat`` models window axes (sliding_window) on either kind.
    """

    kind: str                      # "dense" | "str" | "int64"
    dtype: str = "int32"
    shape: Tuple[int, ...] = ()
    max_len: int = 0
    repeat: int = 1

    @property
    def row_bytes(self) -> int:
        if self.kind == "str":
            return self.repeat * (self.max_len + 4)
        if self.kind == "int64":
            return 8
        n = 1
        for d in self.shape:
            n *= int(d)
        return self.repeat * np.dtype(self.dtype).itemsize * n


Schema = Dict[str, ColSpec]


def schema_row_bytes(schema: Schema) -> int:
    return sum(c.row_bytes for c in schema.values())


def part_bytes(schema: Schema, capacity: int) -> int:
    """Device bytes of ONE partition of a materialized batch."""
    return capacity * schema_row_bytes(schema) + _COUNT_BYTES_PER_PART


def out_bytes(schema: Schema, capacity: int, nparts: int) -> int:
    """Exact materialized bytes of a [P, cap, ...] stage output — the
    number the executor reports as ``out_bytes``."""
    return nparts * part_bytes(schema, capacity)


def schema_from_store_schema(store_schema: Dict[str, Any]) -> Schema:
    """From a store meta.json ``schema`` block (io/store.py layout)."""
    out: Schema = {}
    for k, spec in store_schema.items():
        if spec["kind"] == "str":
            out[k] = ColSpec("str", max_len=int(spec["max_len"]))
        elif spec["kind"] == "int64":
            out[k] = ColSpec("int64")
        else:
            out[k] = ColSpec("dense", dtype=str(spec["dtype"]),
                             shape=tuple(int(d)
                                         for d in spec.get("shape", ())))
    return out


def _leaf_spec(v: Any, lead_dims: int) -> ColSpec:
    """ColSpec of one dense column value (array / ShapeDtypeStruct /
    StringColumn handled by callers), dropping ``lead_dims`` leading
    dims ([P, cap] for PData columns, [cap] for per-shard batches)."""
    shape = tuple(int(d) for d in v.shape[lead_dims:])
    return ColSpec("dense", dtype=str(np.dtype(str(v.dtype))),
                   shape=shape)


def schema_from_columns(columns: Dict[str, Any],
                        lead_dims: int = 1) -> Schema:
    """From a Batch-style columns dict whose values are arrays /
    ShapeDtypeStructs or StringColumns.  ``lead_dims``: leading dims
    before the per-row shape (1 for per-shard [cap, ...], 2 for stacked
    PData [P, cap, ...])."""
    out: Schema = {}
    for k, v in columns.items():
        data = getattr(v, "data", None)
        if data is not None and hasattr(v, "lengths"):
            # StringColumn: data [..., cap, (repeat,) max_len]
            extra = data.shape[lead_dims:-1]
            rep = 1
            for d in extra:
                rep *= int(d)
            out[k] = ColSpec("str", max_len=int(data.shape[-1]),
                             repeat=rep)
        elif hasattr(v, "hi") and hasattr(v, "lo"):     # Int64Column
            out[k] = ColSpec("int64")
        else:
            out[k] = _leaf_spec(v, lead_dims)
    return out


def schema_from_host_columns(columns: Dict[str, Any],
                             str_max_len: int) -> Schema:
    """From user host columns (the from_columns / columns_spec shape):
    lists of str/bytes become StringColumns at ``str_max_len``."""
    out: Schema = {}
    for k, v in columns.items():
        if isinstance(v, (list, tuple)) and (
                len(v) == 0 or isinstance(v[0], (str, bytes))):
            out[k] = ColSpec("str", max_len=int(str_max_len))
        else:
            arr = np.asarray(v)
            out[k] = ColSpec("dense", dtype=str(arr.dtype),
                             shape=tuple(int(d) for d in arr.shape[1:]))
    return out


def abstract_batch(schema: Schema, capacity: int):
    """Build a per-shard Batch of ``jax.ShapeDtypeStruct`` leaves for
    ``jax.eval_shape`` — the abstract value a stage op is interpreted
    over.  Window-axis (repeat > 1) columns are not reconstructed; the
    analyzer treats post-window UDFs as approximate."""
    import jax

    from dryad_tpu.data.columnar import Batch, Int64Column, StringColumn
    sds = jax.ShapeDtypeStruct
    cols: Dict[str, Any] = {}
    for k, spec in schema.items():
        if spec.kind == "str":
            mid = () if spec.repeat == 1 else (spec.repeat,)
            cols[k] = StringColumn(
                sds((capacity,) + mid + (spec.max_len,), np.uint8),
                sds((capacity,) + mid, np.int32))
        elif spec.kind == "int64":
            cols[k] = Int64Column(sds((capacity,), np.int32),
                                  sds((capacity,), np.uint32))
        else:
            rep = () if spec.repeat == 1 else (spec.repeat,)
            cols[k] = sds((capacity,) + rep + spec.shape,
                          np.dtype(spec.dtype))
    return Batch(cols, sds((), np.int32))


def schema_of_abstract(batch_or_cols: Any) -> Tuple[Schema, int]:
    """(schema, capacity) of an eval_shape result — a Batch or a columns
    dict whose leaves are ShapeDtypeStructs."""
    cols = getattr(batch_or_cols, "columns", batch_or_cols)
    schema = schema_from_columns(cols, lead_dims=1)
    cap = 0
    for v in cols.values():
        data = getattr(v, "data", None)
        lead = data if data is not None else v
        cap = int(lead.shape[0])
        break
    return schema, cap


@dataclasses.dataclass
class AbsState:
    """Abstract value of one dataflow edge: total valid rows across all
    partitions, the static per-partition capacity, and (when known) the
    concrete column schema.  ``approx`` marks a state whose schema could
    not be derived (opaque UDF, unknown source) — byte predictions
    downstream of it are reported unbounded instead of wrong."""

    rows: Interval
    capacity: int
    schema: Optional[Schema] = None
    approx: bool = False
    notes: List[str] = dataclasses.field(default_factory=list)

    def rows_clamped(self, nparts: int) -> Interval:
        return self.rows.clamp_hi(
            self.capacity * nparts if self.capacity else None)

    def part_bytes(self) -> Optional[int]:
        if self.schema is None:
            return None
        return part_bytes(self.schema, self.capacity)

    def note(self, msg: str) -> "AbsState":
        if msg not in self.notes:
            self.notes.append(msg)
        return self


def fmt_bytes(b: Optional[float]) -> str:
    if b is None:
        return "?"
    if b == 0:
        return "0"
    units = ["B", "KiB", "MiB", "GiB", "TiB"]
    i = min(int(math.log(max(b, 1), 1024)), len(units) - 1)
    v = b / (1024 ** i)
    return f"{v:.0f}{units[i]}" if v >= 10 else f"{v:.1f}{units[i]}"
