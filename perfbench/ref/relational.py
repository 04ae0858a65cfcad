"""Plain numpy reference of a one-table filter -> project -> group ->
aggregate query, read from a specification kept as data in the traffic
file (so that a new query needs no new code):

    {"table": "lineitem",
     "filter": ["<=", "l_shipdate", 10471],
     "group_by": ["l_returnflag", "l_linestatus"],
     "aggregates": {"sum_qty": ["sum", "l_quantity"], "n": ["count"]},
     "order_by": ["l_returnflag", "l_linestatus"]}

Expressions are prefix lists over column names and numbers: ``+ - * /``,
comparisons ``< <= > >= == !=`` and ``and`` / ``or``.  Arithmetic runs in
float64 and sums accumulate in float64.  With ``precision`` set to
``"bfloat16"`` every input and every arithmetic result is rounded to
bfloat16 (sums still accumulate wide): the control.  It imports nothing
of the program."""

from __future__ import annotations

import numpy as np

_ARITH = {"+": np.add, "-": np.subtract, "*": np.multiply,
          "/": np.divide}
_CMP = {"<": np.less, "<=": np.less_equal, ">": np.greater,
        ">=": np.greater_equal, "==": np.equal, "!=": np.not_equal}


def _rounder(precision):
    if precision in (None, "float64"):
        return lambda a: a
    if precision == "bfloat16":
        import ml_dtypes
        return lambda a: np.asarray(a, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float64)
    if precision == "float32":
        return lambda a: np.asarray(a, np.float32).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def evaluate(expr, cols, rnd):
    if isinstance(expr, str):
        v = cols[expr]
        if isinstance(v, tuple):
            raise ValueError(f"string column {expr!r} in arithmetic")
        if v.dtype.kind == "f":
            return rnd(v.astype(np.float64))
        return v
    if isinstance(expr, (int, float)):
        return expr
    op, *args = expr
    vals = [evaluate(a, cols, rnd) for a in args]
    if op in _ARITH:
        out = _ARITH[op](np.asarray(vals[0], np.float64),
                         np.asarray(vals[1], np.float64))
        return rnd(out)
    if op in _CMP:
        return _CMP[op](vals[0], vals[1])
    if op == "and":
        return np.logical_and.reduce(vals)
    if op == "or":
        return np.logical_or.reduce(vals)
    raise ValueError(f"unknown operator {op!r}")


def _key_codes(v):
    """A column -> (dense codes, the distinct values as Python objects)."""
    if isinstance(v, tuple):
        data, lens = v
        width = data.shape[1]
        rows = np.ascontiguousarray(data).view(f"V{width}").ravel()
        uniq, first, inv = np.unique(rows, return_index=True,
                                     return_inverse=True)
        vals = [bytes(data[i, :lens[i]]) for i in first]
        return inv, vals
    uniq, inv = np.unique(v, return_inverse=True)
    return inv, [x.item() for x in uniq]


def run(spec, tables, precision=None):
    """The query's answer: {"keys": [tuple, ...] in output order,
    "columns": {name: float64 array in that order}, "rows_in": rows that
    passed the filter}."""
    rnd = _rounder(precision)
    cols = tables[spec["table"]]
    n = len(next(v[1] if isinstance(v, tuple) else v
                 for v in cols.values()))
    keep = (evaluate(spec["filter"], cols, rnd) if spec.get("filter")
            else np.ones(n, bool))
    sel = np.flatnonzero(keep)
    used = columns_used(spec)
    cols = {k: ((v[0][sel], v[1][sel]) if isinstance(v, tuple) else v[sel])
            for k, v in cols.items() if k in used}
    code = np.zeros(len(sel), np.int64)
    key_vals = []
    for k in spec.get("group_by", []):
        inv, vals = _key_codes(cols[k])
        code = code * len(vals) + inv
        key_vals.append(vals)
    present, dense = np.unique(code, return_inverse=True)
    ng = len(present)
    keys = []
    for c in present:
        t = []
        for vals in reversed(key_vals):
            c, r = divmod(int(c), len(vals))
            t.append(vals[r])
        keys.append(tuple(reversed(t)))
    count = np.bincount(dense, minlength=ng).astype(np.float64)
    out = {}
    for name, (fn, *arg) in spec["aggregates"].items():
        if fn == "count":
            out[name] = count
            continue
        if fn not in ("sum", "avg"):
            raise ValueError(f"unknown aggregate {fn!r}")
        x = np.asarray(evaluate(arg[0], cols, rnd), np.float64)
        x = np.broadcast_to(x, dense.shape)
        s = np.bincount(dense, weights=x, minlength=ng)
        out[name] = s if fn == "sum" else s / count
    # np.unique returns codes ascending = keys ascending column by column
    return {"keys": keys, "columns": out, "rows_in": int(len(sel))}


def columns_used(spec) -> set:
    """The columns the query names: what it has to read."""
    def names(e):
        if isinstance(e, str):
            yield e
        elif isinstance(e, list):
            for a in e[1:]:
                yield from names(a)
    used = set(spec.get("group_by", []))
    used |= set(names(spec.get("filter") or []))
    for fn, *arg in spec["aggregates"].values():
        if arg:
            used |= set(names(arg[0]))
    return used


def compare(spec, ref, got):
    """Numbers compared between the reference's answer and a collected
    result (dict of columns: string columns as lists of bytes)."""
    key_cols = spec.get("group_by", [])
    names = list(spec["aggregates"])
    counts = [n for n, a in spec["aggregates"].items() if a[0] == "count"]
    res = {"groups_wrong": 0, "counts_wrong": 0, "rows_out_of_order": 0,
           "columns_missing": sum(1 for c in key_cols + names
                                  if c not in got),
           "agg_max_rel_err": 0.0}
    if res["columns_missing"]:
        res["agg_max_rel_err"] = float("inf")
        return res
    n_got = len(got[names[0]])

    def cell(v):
        return bytes(v) if isinstance(v, (bytes, bytearray, np.bytes_)) \
            else (v.encode("latin1") if isinstance(v, str)
                  else np.asarray(v).item())

    got_keys = [tuple(cell(got[k][i]) for k in key_cols)
                for i in range(n_got)]
    ref_index = {k: i for i, k in enumerate(ref["keys"])}
    res["groups_wrong"] = len(set(got_keys) ^ set(ref_index)) + \
        (len(got_keys) - len(set(got_keys)))
    if spec.get("order_by") and got_keys != sorted(got_keys):
        res["rows_out_of_order"] = 1
    worst = 0.0
    for gi, k in enumerate(got_keys):
        ri = ref_index.get(k)
        if ri is None:
            continue
        for name in names:
            g = float(np.asarray(got[name][gi]))
            r = float(ref["columns"][name][ri])
            if name in counts:
                res["counts_wrong"] += int(g != r)
                continue
            err = abs(g - r) / abs(r) if r else abs(g)
            worst = max(worst, err if np.isfinite(err) else float("inf"))
    res["agg_max_rel_err"] = worst
    return res


def as_collected(spec, ans):
    """A reference answer in the shape ``collect()`` gives, so that the
    control can stand in the program's place."""
    out = {k: [key[i] for key in ans["keys"]]
           for i, k in enumerate(spec.get("group_by", []))}
    out.update({k: np.asarray(v) for k, v in ans["columns"].items()})
    return out


def check(answer, data, spec, nparts):
    """The numbers compared for one collected answer."""
    ref = data.setdefault("_relational_ref", {}).get(id(spec))
    if ref is None:
        ref = data["_relational_ref"][id(spec)] = run(spec, data["tables"])
    return compare(spec, ref, answer["collected"])


def control(data, spec, nparts):
    return {"collected": as_collected(spec, run(
        spec, data["tables"], precision=spec.get("control_precision",
                                                 "bfloat16")))}
