"""Plain numpy reference of a filter -> inner equi-join -> group ->
aggregate -> order -> limit query over several tables, read from a
specification kept as data in the traffic file (so that the next join
query needs no new code):

    {"tables": ["customer", "orders", "lineitem"],
     "filters": {"customer": ["str==", "c_mktsegment", "BUILDING"],
                 "orders": ["<", "o_orderdate", 9204]},
     "joins": [["customer", "c_custkey", "orders", "o_custkey"],
               ["orders", "o_orderkey", "lineitem", "l_orderkey"]],
     "group_by": ["l_orderkey", "o_orderdate", "o_shippriority"],
     "aggregates": {"revenue": ["sum", ["*", "l_extendedprice",
                                        ["-", 1, "l_discount"]]]},
     "order_by": [["revenue", "desc"], ["o_orderdate", "asc"]],
     "limit": 10}

A column name is unique over the tables (TPC-H's prefixes).  Each table
is filtered alone, the joins are taken in the order listed (each names a
table already joined and one that may be new; keys are integers), the
joined rows are grouped and summed by ``relational.run`` (float64, or
rounded to bfloat16 for the control), ordered, and cut.  ``filters``
speaks ``relational``'s expressions and ``["str==", column, "TEXT"]``.
It imports nothing of the program."""

from __future__ import annotations

import numpy as np

from perfbench.ref import relational

REL_TOL = 1e-5       # a float32 sum of float32 products against float64


def _nrows(cols):
    v = next(iter(cols.values()))
    return len(v[1] if isinstance(v, tuple) else v)


def _keep(expr, cols, rnd):
    if expr[0] == "and":
        return np.logical_and.reduce([_keep(e, cols, rnd)
                                      for e in expr[1:]])
    if expr[0] == "str==":
        data, lens = cols[expr[1]]
        text = np.frombuffer(expr[2].encode("latin1"), np.uint8)
        if len(text) > data.shape[1]:
            return np.zeros(len(lens), bool)
        return (lens == len(text)) & \
            (data[:, :len(text)] == text).all(axis=1)
    return relational.evaluate(expr, cols, rnd)


def match(lkeys, rkeys):
    """Every pair (i, j) with ``lkeys[i] == rkeys[j]``, as two index
    arrays: the smaller side sorted, the larger looked up in it."""
    if len(lkeys) < len(rkeys):
        j, i = match(rkeys, lkeys)
        return i, j
    order = np.argsort(rkeys, kind="stable")
    srt = rkeys[order]
    lo = np.searchsorted(srt, lkeys, "left")
    cnt = np.searchsorted(srt, lkeys, "right") - lo
    i = np.repeat(np.arange(len(lkeys)), cnt)
    within = np.arange(int(cnt.sum())) - np.repeat(np.cumsum(cnt) - cnt,
                                                   cnt)
    return i, order[np.repeat(lo, cnt) + within]


def joined_rows(spec, tables, rnd, drop_filter=None):
    """{table: row indices}, one entry a joined row, and the rows each
    join returned."""
    sel = {}
    for t in spec["tables"]:
        f = spec.get("filters", {}).get(t)
        n = _nrows(tables[t])
        sel[t] = (np.flatnonzero(_keep(f, tables[t], rnd))
                  if f and t != drop_filter else np.arange(n))
    rel, cards = {}, []
    for lt, lk, rt, rk in spec["joins"]:
        if not rel:
            rel[lt] = sel[lt]
        if lt not in rel:
            lt, lk, rt, rk = rt, rk, lt, lk
        lkeys = tables[lt][lk][rel[lt]]
        if rt in rel:                  # both joined already: a filter
            same = lkeys == tables[rt][rk][rel[rt]]
            rel = {t: ix[same] for t, ix in rel.items()}
        else:
            i, j = match(lkeys, tables[rt][rk][sel[rt]])
            rel = {t: ix[i] for t, ix in rel.items()}
            rel[rt] = sel[rt][j]
        cards.append(len(rel[lt]))
    return rel, cards


def run(spec, tables, precision=None, drop_filter=None):
    """The query's whole answer, ordered, before the limit: {"keys":
    [tuple, ...], "columns": {name: float64 array}, "join_rows": rows
    each join returned}."""
    rnd = relational._rounder(precision)
    rel, cards = joined_rows(spec, tables, rnd, drop_filter)
    flat = {}
    for name in relational.columns_used(spec):
        t = next(t for t in spec["tables"] if name in tables[t])
        v = tables[t][name]
        flat[name] = ((v[0][rel[t]], v[1][rel[t]]) if isinstance(v, tuple)
                      else v[rel[t]])
    grouped = relational.run(
        {"table": "joined", "group_by": spec["group_by"],
         "aggregates": spec["aggregates"]}, {"joined": flat}, precision)
    order = np.arange(len(grouped["keys"]))
    for name, way in reversed(spec.get("order_by", [])):
        v = (grouped["columns"][name] if name in grouped["columns"] else
             np.asarray([k[spec["group_by"].index(name)]
                         for k in grouped["keys"]]))[order]
        order = order[np.argsort(-v if way == "desc" else v,
                                 kind="stable")]
    return {"keys": [grouped["keys"][i] for i in order],
            "columns": {k: v[order]
                        for k, v in grouped["columns"].items()},
            "join_rows": cards}


def as_collected(spec, ans, limit):
    """A reference answer in the shape ``collect()`` gives, cut to the
    limit, so that a control can stand in the program's place."""
    keys = ans["keys"][:limit]
    out = {k: np.asarray([key[i] for key in keys])
           for i, k in enumerate(spec["group_by"])}
    out.update({k: np.asarray(v[:limit])
                for k, v in ans["columns"].items()})
    return out


def compare(spec, ref, got):
    """Numbers compared between the reference's whole answer and a
    collected result."""
    key_cols = spec["group_by"]
    names = list(spec["aggregates"])
    res = {"columns_missing": sum(1 for c in key_cols + names
                                  if c not in got),
           "rows_returned_wrong": 0, "groups_wrong": 0,
           "rows_out_of_order": 0, "top_rows_left_out": 0,
           "agg_max_rel_err": 0.0}
    if res["columns_missing"]:
        res["agg_max_rel_err"] = float("inf")
        return res
    limit = spec.get("limit")
    n_got = len(got[names[0]])
    want = len(ref["keys"]) if limit is None else min(limit,
                                                      len(ref["keys"]))
    res["rows_returned_wrong"] = int(n_got != want)
    got_keys = [tuple(np.asarray(got[k][i]).item() for k in key_cols)
                for i in range(n_got)]
    ref_index = {k: i for i, k in enumerate(ref["keys"])}
    res["groups_wrong"] = sum(1 for k in got_keys if k not in ref_index) \
        + (len(got_keys) - len(set(got_keys)))
    # the order asked for, read off the program's own columns
    order = spec.get("order_by", [])
    cols = [np.asarray(got[name], np.float64) * (-1 if way == "desc"
                                                 else 1)
            for name, way in order]
    for i in range(n_got - 1):
        a = tuple(c[i] for c in cols)
        b = tuple(c[i + 1] for c in cols)
        res["rows_out_of_order"] += int(a > b)
    # what a cut answer may not leave out: reference rows that sort
    # before its last row by more than the rounding
    if order and n_got and limit is not None:
        name, way = order[0]
        sign = -1.0 if way == "desc" else 1.0
        last = sign * float(np.asarray(got[name][n_got - 1]))
        r = sign * ref["columns"][name]
        ahead = np.flatnonzero(r < last - REL_TOL * abs(last))
        have = set(got_keys)
        res["top_rows_left_out"] = sum(
            1 for i in ahead if ref["keys"][i] not in have)
    elif limit is None:
        res["top_rows_left_out"] = len(set(ref_index) - set(got_keys))
    worst = 0.0
    for gi, k in enumerate(got_keys):
        ri = ref_index.get(k)
        if ri is None:
            continue
        for name in names:
            g = float(np.asarray(got[name][gi]))
            r = float(ref["columns"][name][ri])
            err = abs(g - r) / abs(r) if r else abs(g)
            worst = max(worst, err if np.isfinite(err) else float("inf"))
    res["agg_max_rel_err"] = worst
    return res


def reference(data, spec):
    """The whole answer for this data, computed once a run.  The rows
    each join returned are left in the specification as
    ``join_rows_found``, where the roofline's reader finds them
    (``layers/join_roofline.py``: the harness hands a reader the traffic
    file, not the data)."""
    cache = data.setdefault("_relational_join_ref", {})
    if id(spec) not in cache:
        cache[id(spec)] = run(spec, data["tables"])
        spec["join_rows_found"] = cache[id(spec)]["join_rows"]
    return cache[id(spec)]


def check(answer, data, spec, nparts):
    """The numbers compared for one collected answer."""
    return compare(spec, reference(data, spec), answer["collected"])


def control(data, spec, nparts):
    """The reference in the next lower precision, in the program's
    place."""
    return {"collected": as_collected(spec, run(
        spec, data["tables"], precision=spec.get("control_precision",
                                                 "bfloat16")),
        spec.get("limit"))}


def control_dropped_filter(data, spec, nparts):
    """The reference with one table's filter left out (the traffic file
    names the table), in the program's place: a guarantee broken, not a
    precision."""
    return {"collected": as_collected(spec, run(
        spec, data["tables"], drop_filter=spec["control_drop_filter"]),
        spec.get("limit"))}
