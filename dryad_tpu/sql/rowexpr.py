"""Row-expression programs: the SQL front end's compiled callables.

The reference compiles LINQ expression trees to C# vertex code shipped
as a DLL (DryadLinqCodeGen.cs).  Here a bound SQL scalar expression
compiles to a small JSON program (nested lists) interpreted over a
columns dict — the SAME callable runs in three places:

* the in-memory executor (jnp arrays / StringColumns under jit+vmap),
* the sequential oracle (numpy arrays / lists of bytes),
* cluster workers, where the program crosses the wire AS DATA via the
  shippable-value protocol (plan/serialize.ship_ref_of): a SQL plan
  ships with zero fn_table registration and no ``--fn-module``.

Program grammar (JSON-able, deterministic)::

    ["col", name]                  column reference (physical name)
    ["lit", value, type]           scalar literal; type "str" encodes
                                   the value utf-8 at eval time
    ["const", value, type]         literal broadcast to a whole column
    ["bin", op, lhs, rhs]          op in + - * / = != < <= > >= and or
    ["not", x] / ["neg", x]

Only dtype-generic array operators are used, so the interpreter is
backend-agnostic by construction; string equality handles both the
device representation (StringColumn byte matrix) and host lists of
bytes.
"""

from __future__ import annotations

from typing import Any, Dict, List

__all__ = ["Predicate", "Projector", "render_prog", "prog_columns",
           "rename_prog", "fold_prog"]


def _is_strcol(v: Any) -> bool:
    """Device string column (data/columnar.StringColumn duck-typed —
    this module must import on workers before jax is configured)."""
    return hasattr(v, "data") and hasattr(v, "lengths")


def _is_host_str(v: Any) -> bool:
    if isinstance(v, (list, tuple)):
        return len(v) == 0 or isinstance(v[0], (bytes, str))
    dt = getattr(v, "dtype", None)
    return dt is not None and getattr(dt, "kind", "") in ("S", "U", "O")


def _str_eq(a: Any, b: Any):
    """Elementwise string equality across representations; either side
    may be a column (StringColumn / host list) or a bytes literal."""
    if isinstance(a, bytes):
        a, b = b, a
    if _is_strcol(a):
        import jax.numpy as jnp
        if isinstance(b, bytes):
            if len(b) > a.max_len:
                # no stored string can equal a literal longer than the
                # column's max_len — comparing the truncation instead
                # would spuriously match its own prefix
                return jnp.zeros(a.lengths.shape, bool)
            pad = b + b"\x00" * (a.max_len - len(b))
            row = jnp.asarray(bytearray(pad), dtype=jnp.uint8)
            return ((a.lengths == len(b))
                    & (a.data == row[None, :]).all(axis=1))
        # column vs column: compare over the common width, then the
        # longer side's overhang must be empty (padding is zero)
        w = min(a.max_len, b.max_len)
        same = (a.data[:, :w] == b.data[:, :w]).all(axis=1)
        return same & (a.lengths == b.lengths)
    # host representations (oracle): lists / object arrays of bytes
    import numpy as np

    def norm(x):
        return x if isinstance(x, bytes) else str(x).encode()

    if isinstance(b, bytes):
        return np.asarray([norm(x) == b for x in a], dtype=bool)
    return np.asarray([norm(x) == norm(y) for x, y in zip(a, b)],
                      dtype=bool)


def _const_like(cols: Dict[str, Any], value: Any, typ: str):
    """A whole column holding ``value``, row-count matched to the batch
    (the lowering's global-aggregate key; api.dataset._const_key_like
    pattern)."""
    v = next(iter(cols.values()))
    if hasattr(v, "hi") and hasattr(v, "lo"):   # Int64Column: its words
        v = v.hi
    if _is_strcol(v):
        n = v.lengths.shape[0]
    elif hasattr(v, "shape"):
        n = v.shape[0]
    else:
        n = len(v)
    if hasattr(v, "shape") or _is_strcol(v):
        import jax.numpy as jnp
        return jnp.full((n,), value, _np_dtype(typ))
    import numpy as np
    return np.full((n,), value, dtype=_np_dtype(typ))


def _np_dtype(typ: str):
    return {"int": "int32", "float": "float32",
            "bool": "bool_"}.get(typ, "int32")


def _ev(prog: List, cols: Dict[str, Any]) -> Any:
    head = prog[0]
    if head == "col":
        return cols[prog[1]]
    if head == "lit":
        v, t = prog[1], prog[2]
        return v.encode() if t == "str" else v
    if head == "const":
        return _const_like(cols, prog[1], prog[2])
    if head == "not":
        v = _ev(prog[1], cols)
        # column-free subtrees fold to Python scalars (WHERE NOT(1=1));
        # ~True is -2, not False
        return (not v) if isinstance(v, bool) else ~v
    if head == "neg":
        return -_ev(prog[1], cols)
    if head == "bin":
        op = prog[1]
        a = _ev(prog[2], cols)
        b = _ev(prog[3], cols)
        str_sides = (isinstance(a, bytes) or isinstance(b, bytes)
                     or _is_strcol(a) or _is_strcol(b)
                     or _is_host_str(a) or _is_host_str(b))
        if op == "=":
            return _str_eq(a, b) if str_sides else a == b
        if op == "!=":
            return ~_str_eq(a, b) if str_sides else a != b
        if op == "+":
            return a + b
        if op == "-":
            return a - b
        if op == "*":
            return a * b
        if op == "/":
            return a / b
        if op == "<":
            return a < b
        if op == "<=":
            return a <= b
        if op == ">":
            return a > b
        if op == ">=":
            return a >= b
        if op == "and":
            return a & b
        if op == "or":
            return a | b
    raise ValueError(f"bad row-expression program node {prog!r}")


def render_prog(prog: List) -> str:
    """SQL-ish rendering for EXPLAIN / repr."""
    head = prog[0]
    if head == "col":
        return prog[1]
    if head in ("lit", "const"):
        v = prog[1]
        return f"'{v}'" if prog[2] == "str" else repr(v)
    if head == "not":
        return f"(NOT {render_prog(prog[1])})"
    if head == "neg":
        return f"(-{render_prog(prog[1])})"
    op = prog[1].upper() if prog[1] in ("and", "or") else prog[1]
    return f"({render_prog(prog[2])} {op} {render_prog(prog[3])})"


def prog_columns(prog: List) -> set:
    """Set of physical column names a program reads (dead-column
    pruning + scan-prefix analysis, analysis/canon.py)."""
    head = prog[0]
    if head == "col":
        return {prog[1]}
    if head in ("lit", "const"):
        return set()
    if head in ("not", "neg"):
        return prog_columns(prog[1])
    if head == "bin":
        return prog_columns(prog[2]) | prog_columns(prog[3])
    raise ValueError(f"bad row-expression program node {prog!r}")


def rename_prog(prog: List, names: Dict[str, str]) -> List:
    """``prog`` with its column references renamed through ``names``
    (a name it lacks stays)."""
    head = prog[0]
    if head == "col":
        return ["col", names.get(prog[1], prog[1])]
    if head in ("lit", "const"):
        return list(prog)
    if head in ("not", "neg"):
        return [head, rename_prog(prog[1], names)]
    return ["bin", prog[1], rename_prog(prog[2], names),
            rename_prog(prog[3], names)]


def fold_prog(prog: List) -> List:
    """Constant-fold column-free subtrees to ``["lit", v, typ]`` —
    pure data-to-data, mirroring :func:`_ev`'s scalar semantics, so
    the folded program computes the SAME function.  Division by zero
    (and any other eval-time surprise) leaves the subtree unfolded;
    the runtime keeps its behavior."""
    head = prog[0]
    if head in ("col", "lit", "const"):
        return list(prog)
    if head in ("not", "neg"):
        x = fold_prog(prog[1])
        if x[0] == "lit":
            if head == "not":
                return ["lit", not x[1], "bool"]
            return ["lit", -x[1], x[2]]
        return [head, x]
    # head == "bin"
    op = prog[1]
    a, b = fold_prog(prog[2]), fold_prog(prog[3])
    if a[0] == "lit" and b[0] == "lit":
        va, vb = a[1], b[1]
        try:
            v = {"+": lambda: va + vb, "-": lambda: va - vb,
                 "*": lambda: va * vb, "/": lambda: va / vb,
                 "=": lambda: va == vb, "!=": lambda: va != vb,
                 "<": lambda: va < vb, "<=": lambda: va <= vb,
                 ">": lambda: va > vb, ">=": lambda: va >= vb,
                 "and": lambda: bool(va) and bool(vb),
                 "or": lambda: bool(va) or bool(vb)}[op]()
        except (ZeroDivisionError, TypeError):
            return ["bin", op, a, b]
        if op in ("=", "!=", "<", "<=", ">", ">=", "and", "or"):
            return ["lit", bool(v), "bool"]
        typ = ("float" if op == "/" or "float" in (a[2], b[2])
               else a[2])
        return ["lit", v, typ]
    return ["bin", op, a, b]


class _Shippable:
    """Base: the shippable-value protocol (plan/serialize.ship_ref_of).
    Content-identical instances fingerprint identically
    (plan/stages.Stage.fingerprint), so resubmitting a query hits the
    executor's compile cache."""

    def __ship_payload__(self):
        raise NotImplementedError

    @classmethod
    def __from_payload__(cls, payload):
        raise NotImplementedError

    def __eq__(self, other):
        return (type(other) is type(self)
                and other.__ship_payload__() == self.__ship_payload__())

    def __hash__(self):
        import json
        return hash(json.dumps(self.__ship_payload__(), sort_keys=True))


class Predicate(_Shippable):
    """Boolean row filter: ``Predicate(prog)(cols) -> bool mask``."""

    def __init__(self, prog: List):
        self.prog = list(prog)

    def __call__(self, cols: Dict[str, Any]):
        mask = _ev(self.prog, cols)
        if isinstance(mask, (bool, int)):
            # column-free predicate (WHERE 1 = 1): broadcast the
            # scalar verdict to a whole mask column
            return _const_like(cols, bool(mask), "bool")
        return mask if getattr(mask, "dtype", None) is not None \
            and str(mask.dtype) == "bool" else mask.astype(bool)

    def __ship_payload__(self):
        return {"prog": self.prog}

    @classmethod
    def __from_payload__(cls, payload):
        return cls(payload["prog"])

    def __repr__(self):
        return f"sql:{render_prog(self.prog)}"


class Projector(_Shippable):
    """Columnwise projection: ``Projector({out: prog})(cols) -> cols``.
    Plain ``["col", name]`` programs pass the column object through
    untouched (renames are free — string columns included)."""

    def __init__(self, outputs: Dict[str, List]):
        self.outputs = dict(outputs)

    def __call__(self, cols: Dict[str, Any]) -> Dict[str, Any]:
        return {name: _ev(prog, cols)
                for name, prog in self.outputs.items()}

    def __ship_payload__(self):
        return {"outputs": self.outputs}

    @classmethod
    def __from_payload__(cls, payload):
        return cls(payload["outputs"])

    def __repr__(self):
        inner = ", ".join(f"{render_prog(p)} AS {n}"
                          for n, p in self.outputs.items())
        return f"sql:[{inner}]"
