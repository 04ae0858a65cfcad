"""Columnar record batches with static capacity.

This is the TPU-native replacement for the reference's byte-stream record
channels (reference: DryadVertex/VertexHost/system/channel/include/
channelinterface.h:212,515 and LinqToDryad/DryadLinqBinaryReader.cs /
DryadLinqBinaryWriter.cs).  Where Dryad streams arbitrary C# records through
256KB-block byte channels with per-type generated serializers, a TPU wants
fixed-shape tensors that XLA can tile onto the VPU/MXU.  So a dataset
partition is a ``Batch``:

* every column is a fixed-capacity array whose leading dim is the (static)
  row capacity,
* a ``count`` scalar says how many leading rows are valid (rows past
  ``count`` are padding and their contents are unspecified),
* variable-length data (strings / byte blobs) is a ``StringColumn``:
  a padded ``[capacity, max_len] uint8`` matrix plus a ``[capacity] int32``
  length vector.

Everything is a pytree, so a Batch flows through ``jax.jit`` / ``shard_map``
unchanged, and "serialization" (the reference's DryadLinqSerialization.cs)
collapses to host<->device transfer of dense arrays.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "StringColumn",
    "Int64Column",
    "Batch",
    "Schema",
    "batch_from_numpy",
    "batch_to_numpy",
    "string_column_from_list",
    "string_column_to_list",
    "concat_batches",
]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StringColumn:
    """Padded byte-matrix representation of a variable-length bytes column.

    ``data[i, :lengths[i]]`` are the bytes of row ``i``; the rest of the row
    is zero padding.  ``max_len`` (data.shape[1]) is static.
    """

    data: jax.Array  # [capacity, max_len] uint8
    lengths: jax.Array  # [capacity] int32

    @property
    def capacity(self) -> int:
        return self.data.shape[0]

    @property
    def max_len(self) -> int:
        return self.data.shape[1]

    def gather(self, idx: jax.Array) -> "StringColumn":
        return StringColumn(jnp.take(self.data, idx, axis=0),
                            jnp.take(self.lengths, idx, axis=0))

    def tree_flatten(self):
        return (self.data, self.lengths), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Int64Column:
    """A 64-bit signed integer column as two 32-bit words.

    The package runs without ``jax_enable_x64``, where an ``int64`` array
    is silently cut to ``int32`` on its way to the device.  What must not
    wrap — ``SUM`` over an integer column — is held as the value's upper
    word (signed) and lower word (unsigned): ``value = hi * 2**32 + lo``.
    The kernels that carry rows move the two words as two lanes
    (ops/kernels._pack_columns_u32), a sort compares ``hi`` then ``lo``
    (sort_lanes_for), ``collect`` and the store hand back numpy ``int64``
    (``to_numpy``).  Arithmetic and comparisons on it are not provided:
    the SQL binder types it ``bigint`` and rejects them at bind time.
    """

    hi: jax.Array  # [capacity] int32
    lo: jax.Array  # [capacity] uint32

    @property
    def capacity(self) -> int:
        return self.hi.shape[0]

    def gather(self, idx: jax.Array) -> "Int64Column":
        return Int64Column(jnp.take(self.hi, idx, axis=0),
                           jnp.take(self.lo, idx, axis=0))

    def tree_flatten(self):
        return (self.hi, self.lo), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @staticmethod
    def from_numpy(values) -> "Int64Column":
        """Host ``int64`` values -> the two words (numpy leaves)."""
        v = np.asarray(values, np.int64)
        return Int64Column((v >> 32).astype(np.int32),
                           (v & 0xFFFFFFFF).astype(np.uint32))

    @staticmethod
    def to_numpy(hi, lo) -> np.ndarray:
        """The two words (any leading shape) -> numpy ``int64``."""
        return (np.asarray(hi).astype(np.int64) << 32) \
            | np.asarray(lo).astype(np.int64)


Column = Any  # jax.Array | StringColumn | Int64Column


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class Batch:
    """A fixed-capacity columnar record batch.

    Invariants:
      * all columns share the same leading dimension (the capacity);
      * ``count`` is an int32 scalar, 0 <= count <= capacity;
      * rows with index >= count are padding with unspecified contents.
    """

    columns: Dict[str, Column]
    count: jax.Array  # int32 scalar

    # -- structure ---------------------------------------------------------

    @property
    def capacity(self) -> int:
        for c in self.columns.values():
            if isinstance(c, (StringColumn, Int64Column)):
                return c.capacity
            return c.shape[0]
        raise ValueError("Batch has no columns")

    @property
    def names(self) -> Sequence[str]:
        return list(self.columns.keys())

    def column(self, name: str) -> Column:
        return self.columns[name]

    def __getitem__(self, name: str) -> Column:
        return self.columns[name]

    def valid_mask(self) -> jax.Array:
        """[capacity] bool — True for valid rows."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.count

    # -- row-wise transforms ----------------------------------------------

    def gather(self, idx: jax.Array, count: jax.Array | None = None) -> "Batch":
        """Row gather; ``idx`` is [new_capacity] int32.  Keeps count unless given."""
        cols = {}
        for k, v in self.columns.items():
            if isinstance(v, (StringColumn, Int64Column)):
                cols[k] = v.gather(idx)
            else:
                cols[k] = jnp.take(v, idx, axis=0)
        return Batch(cols, self.count if count is None else
                     jnp.asarray(count, jnp.int32))

    def with_columns(self, new: Mapping[str, Column]) -> "Batch":
        cols = dict(self.columns)
        cols.update(new)
        return Batch(cols, self.count)

    def select_columns(self, names: Sequence[str]) -> "Batch":
        return Batch({n: self.columns[n] for n in names}, self.count)

    def rename(self, mapping: Mapping[str, str]) -> "Batch":
        cols = {mapping.get(k, k): v for k, v in self.columns.items()}
        return Batch(cols, self.count)

    def with_count(self, count) -> "Batch":
        return Batch(self.columns, jnp.asarray(count, jnp.int32))

    def pad_to(self, capacity: int) -> "Batch":
        """Grow (or keep) capacity; padding rows are zeros."""
        cur = self.capacity
        if capacity == cur:
            return self
        if capacity < cur:
            raise ValueError(f"pad_to smaller than capacity ({capacity} < {cur})")
        extra = capacity - cur
        cols = {}
        for k, v in self.columns.items():
            if isinstance(v, StringColumn):
                cols[k] = StringColumn(
                    jnp.pad(v.data, ((0, extra), (0, 0))),
                    jnp.pad(v.lengths, (0, extra)))
            elif isinstance(v, Int64Column):
                cols[k] = Int64Column(jnp.pad(v.hi, (0, extra)),
                                      jnp.pad(v.lo, (0, extra)))
            else:
                pad = [(0, extra)] + [(0, 0)] * (v.ndim - 1)
                cols[k] = jnp.pad(v, pad)
        return Batch(cols, self.count)

    def tree_flatten(self):
        names = tuple(sorted(self.columns.keys()))
        children = tuple(self.columns[n] for n in names) + (self.count,)
        return children, names

    @classmethod
    def tree_unflatten(cls, names, children):
        cols = dict(zip(names, children[:-1]))
        return cls(cols, children[-1])


@dataclasses.dataclass(frozen=True)
class Schema:
    """Static description of a Batch: column name -> (kind, dtype/max_len, trailing shape)."""

    fields: Dict[str, Any]  # name -> jax.ShapeDtypeStruct-like spec

    @classmethod
    def of(cls, batch: Batch) -> "Schema":
        fields = {}
        for k, v in batch.columns.items():
            if isinstance(v, StringColumn):
                fields[k] = ("str", v.max_len)
            elif isinstance(v, Int64Column):
                fields[k] = ("int64",)
            else:
                fields[k] = ("dense", v.dtype, v.shape[1:])
        return cls(fields)

    def empty_batch(self, capacity: int) -> Batch:
        cols: Dict[str, Column] = {}
        for k, spec in self.fields.items():
            if spec[0] == "str":
                cols[k] = StringColumn(
                    jnp.zeros((capacity, spec[1]), jnp.uint8),
                    jnp.zeros((capacity,), jnp.int32))
            elif spec[0] == "int64":
                cols[k] = Int64Column(jnp.zeros((capacity,), jnp.int32),
                                      jnp.zeros((capacity,), jnp.uint32))
            else:
                _, dtype, trailing = spec
                cols[k] = jnp.zeros((capacity,) + tuple(trailing), dtype)
        return Batch(cols, jnp.zeros((), jnp.int32))


# -- host-side constructors -------------------------------------------------


def string_column_from_list(strings: Sequence[bytes | str], capacity: int,
                            max_len: int) -> StringColumn:
    n = len(strings)
    if n > capacity:
        raise ValueError(f"{n} strings > capacity {capacity}")
    data = np.zeros((capacity, max_len), np.uint8)
    lengths = np.zeros((capacity,), np.int32)
    for i, s in enumerate(strings):
        b = s.encode() if isinstance(s, str) else bytes(s)
        if len(b) > max_len:
            b = b[:max_len]
        data[i, : len(b)] = np.frombuffer(b, np.uint8)
        lengths[i] = len(b)
    return StringColumn(jnp.asarray(data), jnp.asarray(lengths))


def string_column_to_list(col: StringColumn, count: int) -> list:
    from dryad_tpu import native

    data = np.asarray(col.data)
    lengths = np.asarray(col.lengths)
    return native.unpack_rows(data[:count], lengths[:count])


def batch_from_numpy(columns: Mapping[str, Any], capacity: int | None = None,
                     str_max_len: int = 64) -> Batch:
    """Build a Batch from host data.  Lists of str/bytes become StringColumns."""
    n = None
    for v in columns.values():
        n = len(v)
        break
    if n is None:
        raise ValueError("no columns")
    cap = capacity or n
    cols: Dict[str, Column] = {}
    for k, v in columns.items():
        if len(v) != n:
            raise ValueError("ragged column lengths")
        if isinstance(v, (list, tuple)) and (n == 0 or isinstance(v[0], (str, bytes))):
            cols[k] = string_column_from_list(v, cap, str_max_len)
        else:
            arr = np.asarray(v)
            pad = [(0, cap - n)] + [(0, 0)] * (arr.ndim - 1)
            cols[k] = jnp.asarray(np.pad(arr, pad))
    return Batch(cols, jnp.asarray(n, jnp.int32))


def batch_to_numpy(batch: Batch) -> Dict[str, Any]:
    """Extract the valid rows of a Batch to host (numpy arrays / byte lists)."""
    n = int(batch.count)
    out: Dict[str, Any] = {}
    for k, v in batch.columns.items():
        if isinstance(v, StringColumn):
            out[k] = string_column_to_list(v, n)
        elif isinstance(v, Int64Column):
            out[k] = Int64Column.to_numpy(v.hi, v.lo)[:n]
        else:
            out[k] = np.asarray(v)[:n]
    return out


def concat_batches(batches: Sequence[Batch], capacity: int | None = None) -> Batch:
    """Concatenate batches (compacting valid rows).  Host-side helper."""
    assert batches
    parts = [batch_to_numpy(b) for b in batches]
    names = batches[0].names
    total = sum(int(b.count) for b in batches)
    cap = capacity or max(total, 1)
    merged: Dict[str, Any] = {}
    for k in names:
        vals = [p[k] for p in parts]
        if isinstance(batches[0].columns[k], StringColumn):
            flat = [s for v in vals for s in v]
            merged[k] = string_column_from_list(
                flat, cap, batches[0].columns[k].max_len)
        elif isinstance(batches[0].columns[k], Int64Column):
            wide = Int64Column.from_numpy(
                np.pad(np.concatenate(vals), (0, cap - total)))
            merged[k] = Int64Column(jnp.asarray(wide.hi),
                                    jnp.asarray(wide.lo))
        else:
            arr = np.concatenate(vals, axis=0)
            pad = [(0, cap - total)] + [(0, 0)] * (arr.ndim - 1)
            merged[k] = jnp.asarray(np.pad(arr, pad))
    return Batch(merged, jnp.asarray(total, jnp.int32))
