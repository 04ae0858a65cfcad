"""Sequential semantics oracle (the reference's LocalDebug mode).

The reference runs every query twice in tests — cluster mode and
LINQ-to-objects (`context.LocalDebug = true`, LinqToDryad/DryadLinqQuery.cs:349,
DryadLinqEnumerable.cs) — and compares.  This module is our LINQ-to-objects:
a pure numpy/python interpreter of the logical expression DAG, independent of
JAX, batches, partitions, and collectives.  Tests run each query through both
paths and compare row multisets (tests/utils.py).
"""

from __future__ import annotations

import collections
from typing import Any, Dict, List

import numpy as np

from dryad_tpu.plan import expr as E

__all__ = ["run_oracle"]

Table = Dict[str, Any]  # column name -> np.ndarray | list[bytes]


def _nrows(t: Table) -> int:
    for v in t.values():
        return len(v)
    return 0


def _row(t: Table, i: int):
    return {k: (v[i] if isinstance(v, list) else v[i]) for k, v in t.items()}


def _take_rows(t: Table, idx) -> Table:
    out = {}
    for k, v in t.items():
        if isinstance(v, list):
            out[k] = [v[i] for i in idx]
        else:
            out[k] = np.asarray(v)[idx]
    return out


def _to_np(cols: Table) -> Table:
    return {k: (v if isinstance(v, list) else np.asarray(v))
            for k, v in cols.items()}


def _tokenize(line: bytes, delims: bytes, max_len: int, lower: bool):
    out = []
    tok = bytearray()
    for b in line:
        if b in delims:
            if tok:
                out.append(bytes(tok[:max_len]))
                tok = bytearray()
        else:
            tok.append(b)
    if tok:
        out.append(bytes(tok[:max_len]))
    if lower:
        out = [t.lower() for t in out]
    return out


def _apply_device_fn(fn, tables: List[Table], with_index: bool = False
                     ) -> Table:
    """Oracle-side evaluation of a DEVICE UDF (Batch -> Batch) over whole
    tables treated as one partition: build Batches with jax (host
    backend), call the same callable the executor jits, and read the
    valid rows back.  This closes the oracle blind spot where
    apply_per_partition / cross_apply went unchecked without a host_fn
    (VERDICT r3 weak 7) — the reference's LocalDebug likewise runs the
    IDENTICAL user lambda through LINQ-to-objects
    (DryadLinqQuery.cs:349)."""
    import jax.numpy as jnp

    from dryad_tpu.data.columnar import batch_from_numpy, batch_to_numpy

    def widest(t: Table) -> int:
        w = 1
        for v in t.values():
            if isinstance(v, list):
                w = max(w, max((len(x) for x in v), default=1))
        return w

    batches = [batch_from_numpy(t, str_max_len=widest(t)) for t in tables]
    args = list(batches)
    if with_index:
        args.append(jnp.zeros((), jnp.int32))  # the single oracle "partition"
    out = fn(*args)
    return {k: (v if isinstance(v, list) else np.asarray(v))
            for k, v in batch_to_numpy(out).items()}


def _agg(kind: str, vals: List[Any]):
    if kind == "count":
        return len(vals)
    if kind == "sum":
        return np.sum(vals, axis=0)
    if kind == "sum64":
        return np.sum(np.asarray(vals, np.int64))
    if kind == "min":
        return np.min(vals, axis=0)
    if kind == "max":
        return np.max(vals, axis=0)
    if kind == "mean":
        return np.mean(vals, axis=0)
    if kind == "any":
        return bool(np.any(vals))
    if kind == "all":
        return bool(np.all(vals))
    raise ValueError(kind)


def _eval_decomposable(dec: "E.Decomposable", t: Dict[str, Any],
                       idx: List[int], oname: str) -> Dict[str, Any]:
    """Sequential-reference evaluation of a Decomposable over one group:
    seed each row, left-fold merge, finalize.  Mirrors the kernel's
    segmented-scan semantics exactly (same seed/merge/finalize callables,
    applied per single-row state)."""
    import functools

    from dryad_tpu.data.columnar import string_column_from_list

    # string columns feed seed as 1-row StringColumns (the same columnar
    # repr the kernel's seed sees, width = the column's widest value so
    # every row state has matching shapes for merge)
    widths = {k: max((len(x) for x in v), default=1) or 1
              for k, v in t.items() if isinstance(v, list)}

    def row_state(i):
        cols = {}
        for k, v in t.items():
            if isinstance(v, list):  # bytes column
                cols[k] = string_column_from_list([v[i]], 1, widths[k])
            else:
                cols[k] = np.asarray(v)[i: i + 1]
        return dec.seed(cols)

    states = [row_state(i) for i in idx]
    merged = functools.reduce(dec.merge, states)
    val = dec.finalize(merged) if dec.finalize is not None else merged
    named = val if isinstance(val, dict) else {oname: val}
    return {k: np.asarray(v)[0] if np.asarray(v).shape
            and np.asarray(v).shape[0] == 1 else np.asarray(v)
            for k, v in named.items()}


def _key_of(row: dict, keys) -> tuple:
    names = keys if keys else sorted(row.keys())
    out = []
    for k in names:
        v = row[k]
        out.append(v if isinstance(v, bytes) else
                   (v.item() if hasattr(v, "item") else v))
    return tuple(out)


def run_oracle(root: E.Node, bindings: Dict[str, Table] | None = None) -> Table:
    bindings = bindings or {}
    memo: Dict[int, Table] = {}

    def ev(n: E.Node) -> Table:
        if n.id in memo:
            return memo[n.id]
        t = _ev(n)
        memo[n.id] = t
        return t

    def _ev(n: E.Node) -> Table:
        if isinstance(n, E.Source):
            if n.host is None:
                raise ValueError("Source has no host data for oracle")
            return _to_np(n.host)
        if isinstance(n, E.Placeholder):
            return _to_np(bindings[n.name])
        if isinstance(n, E.Map):
            t = ev(n.parents[0])
            out = n.fn(dict(t))
            return {k: (v if isinstance(v, list) else np.asarray(v))
                    for k, v in out.items()}
        if isinstance(n, E.Filter):
            t = ev(n.parents[0])
            mask = np.asarray(n.fn(dict(t))).astype(bool)
            return _take_rows(t, np.nonzero(mask)[0])
        if isinstance(n, E.FlatTokens):
            t = ev(n.parents[0])
            toks: List[bytes] = []
            for line in t[n.column]:
                toks.extend(_tokenize(line, n.delims, n.max_token_len,
                                      n.lower))
            return {n.column: toks}
        if isinstance(n, E.ApplyPerPartition):
            t = ev(n.parents[0])
            if n.host_fn is not None:
                out = n.host_fn(dict(t))
                return {k: (v if isinstance(v, list) else np.asarray(v))
                        for k, v in out.items()}
            # no host_fn: run the DEVICE fn itself over the whole table
            # as one partition (index 0)
            return _apply_device_fn(n.fn, [t], with_index=n.with_index)
        if isinstance(n, E.FlatMap):
            t = ev(n.parents[0])
            out_cols, mask = n.fn({k: np.asarray(v) for k, v in t.items()})
            mask = np.asarray(mask).astype(bool)
            idx = np.nonzero(mask.reshape(-1))[0]
            out = {}
            for k, v in out_cols.items():
                arr = np.asarray(v)
                flat = arr.reshape((-1,) + arr.shape[2:])
                out[k] = flat[idx]
            return out
        if isinstance(n, E.Zip):
            lt, rt = ev(n.parents[0]), ev(n.parents[1])
            nmin = min(_nrows(lt), _nrows(rt))
            out = {k: (v[:nmin] if isinstance(v, list) else
                       np.asarray(v)[:nmin]) for k, v in lt.items()}
            for k, v in rt.items():
                name = k if k not in out else k + n.suffix
                out[name] = (v[:nmin] if isinstance(v, list)
                             else np.asarray(v)[:nmin])
            return out
        if isinstance(n, E.SlidingWindow):
            t = ev(n.parents[0])
            nrows = _nrows(t)
            nwin = max(0, nrows - n.w + 1)
            out = {}
            for k, v in t.items():
                if isinstance(v, list):
                    out[k] = [[v[i + j] for j in range(n.w)]
                              for i in range(nwin)]
                else:
                    arr = np.asarray(v)
                    out[k] = np.stack([arr[i:i + n.w]
                                       for i in range(nwin)]) if nwin else \
                        np.zeros((0, n.w) + arr.shape[1:], arr.dtype)
            return out
        if isinstance(n, E.WithRowIndex):
            t = ev(n.parents[0])
            out = dict(t)
            out[n.column] = np.arange(_nrows(t), dtype=np.int32)
            return out
        if isinstance(n, E.AssumePartitioning):
            return ev(n.parents[0])
        if isinstance(n, E.SkipTake):
            t = ev(n.parents[0])
            nrows = _nrows(t)
            if n.op == "skip":
                return _take_rows(t, range(min(n.n, nrows), nrows))
            pred = np.asarray(n.fn({k: np.asarray(v) if not isinstance(v, list)
                                    else v for k, v in t.items()})).astype(bool)
            cut = nrows
            for i in range(nrows):
                if not pred[i]:
                    cut = i
                    break
            if n.op == "take_while":
                return _take_rows(t, range(cut))
            return _take_rows(t, range(cut, nrows))
        if isinstance(n, E.GroupByAgg):
            t = ev(n.parents[0])
            nrows = _nrows(t)
            groups: Dict[tuple, List[int]] = collections.defaultdict(list)
            order: List[tuple] = []
            for i in range(nrows):
                k = _key_of({kk: t[kk][i] for kk in n.keys}, tuple(n.keys))
                if k not in groups:
                    order.append(k)
                groups[k].append(i)
            out: Table = {k: [] for k in n.keys}
            agg_out_names: List[str] = []
            for k in order:
                idx = groups[k]
                for kk, kv in zip(n.keys, k):
                    out[kk].append(kv)
                for oname, spec in n.aggs.items():
                    if isinstance(spec, E.Decomposable):
                        named = _eval_decomposable(spec, t, idx, oname)
                    else:
                        kind, col = spec
                        vals = [t[col][i] for i in idx] if col \
                            else [None] * len(idx)
                        named = {oname: _agg(kind, vals)}
                    for cname, v in named.items():
                        out.setdefault(cname, []).append(v)
                        if cname not in agg_out_names:
                            agg_out_names.append(cname)
            return {k: (v if v and isinstance(v[0], bytes) else np.asarray(v))
                    for k, v in out.items()}
        if isinstance(n, (E.GroupApply, E.GroupTopK, E.GroupRankSelect)):
            t = ev(n.parents[0])
            nrows = _nrows(t)
            groups: Dict[tuple, List[int]] = collections.defaultdict(list)
            order: List[tuple] = []
            for i in range(nrows):
                k = _key_of({kk: t[kk][i] for kk in n.keys}, tuple(n.keys))
                if k not in groups:
                    order.append(k)
                groups[k].append(i)
            if isinstance(n, E.GroupTopK):
                idx: List[int] = []
                for k in order:
                    g = groups[k]
                    # python sorted is stable even with reverse=True, same
                    # as the device's stable inverted-lane lexsort
                    top = sorted(g, key=lambda i: t[n.by][i],
                                 reverse=n.descending)[:n.k]
                    idx.extend(top)
                return _take_rows(t, idx)
            if isinstance(n, E.GroupRankSelect):
                out: Table = {k: [] for k in n.keys}
                oname = n.out or n.by
                out[oname] = []
                for k in order:
                    g = sorted(groups[k], key=lambda i: t[n.by][i])
                    if n.rank == "median":
                        pick = g[(len(g) - 1) // 2]
                    elif n.rank == "min":
                        pick = g[0]
                    else:
                        pick = g[-1]
                    for kk, kv in zip(n.keys, k):
                        out[kk].append(kv)
                    out[oname].append(t[n.by][pick])
                return {k: (v if v and isinstance(v[0], bytes)
                            else np.asarray(v)) for k, v in out.items()}
            # GroupApply: run the SAME fn per group (jax works eagerly on
            # numpy inputs), padding each group to group_capacity — rows
            # past count are zeros, which fn must not read (the device
            # contract: rows >= count are unspecified)
            import jax.numpy as jnp

            from dryad_tpu.data.columnar import StringColumn
            # the device right-sizes group_capacity via measured-need
            # retries, so the eager reference must be exact regardless of
            # the declared capacity: pad to the largest group
            C = max([n.group_capacity] + [len(g) for g in groups.values()])
            out_rows: List[Dict[str, Any]] = []
            for k in order:
                g = groups[k]
                cols: Dict[str, Any] = {}
                for kk, v in t.items():
                    if isinstance(v, list):
                        L = max([len(b) for b in v] or [1]) or 1
                        data = np.zeros((C, L), np.uint8)
                        lens = np.zeros((C,), np.int32)
                        for r, i in enumerate(g[:C]):
                            b = v[i]
                            data[r, :len(b)] = np.frombuffer(b, np.uint8)
                            lens[r] = len(b)
                        cols[kk] = StringColumn(jnp.asarray(data),
                                                jnp.asarray(lens))
                    else:
                        arr = np.asarray(v)
                        p = np.zeros((C,) + arr.shape[1:], arr.dtype)
                        p[:min(len(g), C)] = arr[g[:C]]
                        # hand fn jax arrays, exactly as on device — numpy
                        # arrays fancy-indexed by jax index arrays return
                        # wrong results silently
                        cols[kk] = jnp.asarray(p)
                oc, mask = n.fn(cols, jnp.int32(len(g)))
                mask = np.asarray(mask).astype(bool)
                for r in np.nonzero(mask)[0]:
                    row: Dict[str, Any] = {}
                    for kk, kv in zip(n.keys, k):
                        row[kk] = kv
                    for cname, cv in oc.items():
                        if isinstance(cv, StringColumn):
                            d = np.asarray(cv.data)[r]
                            l = int(np.asarray(cv.lengths)[r])
                            row[cname] = bytes(d[:l])
                        else:
                            row[cname] = np.asarray(cv)[r]
                    out_rows.append(row)
            if not out_rows:
                names = list(n.keys)
            else:
                names = list(out_rows[0].keys())
            res: Table = {kk: [] for kk in names}
            for row in out_rows:
                for kk in names:
                    res[kk].append(row[kk])
            return {k: (v if v and isinstance(v[0], bytes)
                        else np.asarray(v)) for k, v in res.items()}
        if isinstance(n, E.Join):
            lt, rt = ev(n.parents[0]), ev(n.parents[1])
            rmap: Dict[tuple, List[int]] = collections.defaultdict(list)
            for j in range(_nrows(rt)):
                rmap[_key_of({k: rt[k][j] for k in n.right_keys},
                             tuple(n.right_keys))].append(j)
            rkeyset = set(n.right_keys)
            rextra = [k for k in rt.keys() if k not in rkeyset]
            out_names = list(lt.keys()) + [
                (k if k not in lt else k + "_r") for k in rextra]
            out: Table = {k: [] for k in out_names}
            how = getattr(n, "how", "inner")

            def _zero_of(proto):
                if isinstance(proto, list):
                    return b""
                p = np.asarray(proto)
                return np.zeros((1,) + p.shape[1:], p.dtype)[0]

            matched_right: set = set()
            for i in range(_nrows(lt)):
                k = _key_of({kk: lt[kk][i] for kk in n.left_keys},
                            tuple(n.left_keys))
                matches = rmap.get(k, ())
                matched_right.update(matches)
                for j in matches:
                    for kk in lt.keys():
                        out[kk].append(lt[kk][i])
                    for kk in rextra:
                        name = kk if kk not in lt else kk + "_r"
                        out[name].append(rt[kk][j])
                if how in ("left", "full") and not matches:
                    # unmatched left row: right columns zero-filled
                    for kk in lt.keys():
                        out[kk].append(lt[kk][i])
                    for kk in rextra:
                        name = kk if kk not in lt else kk + "_r"
                        out[name].append(_zero_of(rt[kk]))
            if how in ("right", "full"):
                key_map = dict(zip(n.left_keys, n.right_keys))
                for j in range(_nrows(rt)):
                    if j in matched_right:
                        continue
                    # unmatched right row: left key columns take the right
                    # key values, other left columns zero-filled
                    for kk in lt.keys():
                        if kk in key_map:
                            out[kk].append(rt[key_map[kk]][j])
                        else:
                            out[kk].append(_zero_of(lt[kk]))
                    for kk in rextra:
                        name = kk if kk not in lt else kk + "_r"
                        out[name].append(rt[kk][j])
            return {k: (v if v and isinstance(v[0], bytes) else np.asarray(v))
                    for k, v in out.items()}
        if isinstance(n, E.OrderBy):
            t = ev(n.parents[0])
            nrows = _nrows(t)
            # lexicographic multi-key sort via successive stable sorts from
            # the least significant key (handles bytes descending exactly)
            idx = list(range(nrows))
            for col, desc in reversed(n.keys):
                vals = t[col]
                idx.sort(key=lambda i: vals[i], reverse=desc)
            return _take_rows(t, idx)
        if isinstance(n, E.Distinct):
            t = ev(n.parents[0])
            seen = set()
            idx = []
            keys = tuple(n.keys) or tuple(sorted(t.keys()))
            for i in range(_nrows(t)):
                k = _key_of({kk: t[kk][i] for kk in keys}, keys)
                if k not in seen:
                    seen.add(k)
                    idx.append(i)
            return _take_rows(t, idx)
        if isinstance(n, E.SetOp):
            lt, rt = ev(n.parents[0]), ev(n.parents[1])
            names = list(lt.keys())
            lrows = [_key_of({k: lt[k][i] for k in names}, tuple(names))
                     for i in range(_nrows(lt))]
            rrows = {_key_of({k: rt[k][i] for k in names}, tuple(names))
                     for i in range(_nrows(rt))}
            seen = set()
            idx = []
            for i, k in enumerate(lrows):
                if k in seen:
                    continue
                if n.op == "union":
                    seen.add(k)
                    idx.append(i)
                elif n.op == "intersect" and k in rrows:
                    seen.add(k)
                    idx.append(i)
                elif n.op == "except" and k not in rrows:
                    seen.add(k)
                    idx.append(i)
            out = _take_rows(lt, idx)
            if n.op == "union":
                extra = []
                for i in range(_nrows(rt)):
                    k = _key_of({kk: rt[kk][i] for kk in names}, tuple(names))
                    if k not in seen:
                        seen.add(k)
                        extra.append(i)
                radd = _take_rows(rt, extra)
                out = {k: (list(out[k]) + list(radd[k])
                           if isinstance(out[k], list)
                           else np.concatenate([out[k], radd[k]]))
                       for k in names}
            return out
        if isinstance(n, E.Concat):
            lt, rt = ev(n.parents[0]), ev(n.parents[1])
            return {k: (list(lt[k]) + list(rt[k]) if isinstance(lt[k], list)
                        else np.concatenate([lt[k], rt[k]]))
                    for k in lt.keys()}
        if isinstance(n, (E.HashRepartition, E.RangeRepartition)):
            return ev(n.parents[0])
        if isinstance(n, E.Broadcast):
            t = ev(n.parents[0])
            reps = n.parents[0].npartitions
            return {k: (list(v) * reps if isinstance(v, list)
                        else np.tile(v, (reps,) + (1,) * (v.ndim - 1)))
                    for k, v in t.items()}
        if isinstance(n, E.Take):
            t = ev(n.parents[0])
            return _take_rows(t, range(min(n.n, _nrows(t))))
        if isinstance(n, E.WithCapacity):
            return ev(n.parents[0])
        if isinstance(n, E.CrossApply):
            lt, rt = ev(n.parents[0]), ev(n.parents[1])
            if n.host_fn is not None:
                out = n.host_fn(dict(lt), dict(rt))
                return {k: (v if isinstance(v, list) else np.asarray(v))
                        for k, v in out.items()}
            # no host_fn: the device fn sees (left partition, full right
            # table); with one oracle partition that is exactly (lt, rt)
            return _apply_device_fn(n.fn, [lt, rt])
        raise TypeError(f"oracle: unhandled node {type(n).__name__}")

    return ev(root)
