"""One standing-query refresh: delta scan -> merge -> finalize -> commit.

The refresh runs the pipeline through the NORMAL SQL lowering (the same
Dataset chain, executor, compile cache, and event stream as any batch
query) — only the base table's scan is scoped to the chunks appended
since the committed watermark, via a catalog view whose ``dataset()``
reads just those store partitions.  For the aggregate shape the engine
computes per-group PARTIALS over the delta (the state statement of
inc/delta_plan.py) and the host merges them into persisted state with
the engine's own arithmetic: sums add in the engine's dtype, mean
finalizes as ``sum.astype(float32)/count`` exactly like the builtin
Decomposable triple (plan/planner.py) — so an incremental result is
bit-identical to a full rescan for integer-valued aggregates.

Commit discipline: the engine run is read-only; the ONLY mutation is
the single atomic state+watermark replace (inc/state.py).  A crash
anywhere before it changes nothing; a crash after it is a completed
refresh.  Chunks are therefore processed exactly once per state
lineage — never double-counted, never skipped.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

import numpy as np

from dryad_tpu.inc import state as inc_state
from dryad_tpu.inc.delta_plan import (REBUILD_DELTA_FRACTION, DeltaPlan,
                                      plan_delta, state_statement)
from dryad_tpu.sql.binder import BoundSelect
from dryad_tpu.sql.catalog import Catalog

__all__ = ["RefreshResult", "run_refresh", "table_payload"]


@dataclasses.dataclass
class RefreshResult:
    """Outcome of one refresh (the record behind the ``inc_refresh``
    event SSE followers consume)."""

    mode: str                   # incremental | rebuild | rescan | noop
    shape: Optional[str]        # aggregate | append | None
    code: str                   # DTA401 | DTA402 | DTA403
    generation: int             # store generation this refresh covers
    watermark: int              # committed watermark (== generation)
    delta_parts: List[int]      # store partitions scanned
    delta_rows: int             # input rows scanned
    table: Dict[str, Any]       # full current result columns
    rows: int
    changed: Dict[str, Any]     # rows that changed this refresh
    changed_rows: int
    wall_s: float = 0.0


def table_payload(table: Dict[str, Any], cap: Optional[int] = None
                  ) -> Dict[str, Any]:
    """JSON-able ``{"table": cols, "rows": n}`` form of a host table —
    the same conversion as the service's SQL combine (bytes decode
    utf-8, numpy scalars to Python), optionally row-capped for event
    payloads."""
    out: Dict[str, Any] = {}
    n = 0
    for k, v in table.items():
        vals = list(v if cap is None else v[:cap])
        out[k] = [x.decode("utf-8", "replace")
                  if isinstance(x, (bytes, bytearray))
                  else (x.item() if hasattr(x, "item") else x)
                  for x in vals]
        n = max(n, len(vals))
    return {"table": out, "rows": n}


class _DeltaCatalog(Catalog):
    """Catalog view that scopes ONE table's scan to an explicit store
    partition subset — the mechanism by which the unchanged SQL
    lowering runs over only the chunk delta."""

    def __init__(self, base: Catalog, table: str,
                 partitions: List[int]):
        super().__init__()
        self.tables = base.tables
        self._table = table
        self._partitions = list(partitions)

    def dataset(self, ctx, name: str, loader=None, columns=None):
        # ``loader`` (the service's scan-share hook) is ignored on
        # purpose: a delta scan reads an explicit partition subset, so
        # a shared full-table PData would be the WRONG rows
        if name != self._table:
            return super().dataset(ctx, name, columns=columns)
        from dryad_tpu.api.dataset import Dataset
        from dryad_tpu.io.store import read_store, store_meta
        t = self.tables[name]
        # capacity scoped to the partitions actually read: the manifest
        # capacity is sized for the LARGEST part of the whole store, and
        # padding a small chunk delta to it would make the incremental
        # scan compute at full-store scale.  When the scanned-part count
        # differs from the mesh size read_store re-blocks rows evenly,
        # so the bound is ceil(total/nparts); verbatim loads need the
        # largest scanned part — the max of both covers either path
        meta = store_meta(t.path)
        counts = [int(meta["counts"][p]) for p in self._partitions]
        total = sum(counts)
        cap = max(max(counts or [1]), -(-total // max(ctx.nparts, 1)), 1)
        pd = read_store(t.path, ctx.mesh, capacity=cap,
                        partitions=self._partitions,
                        verify=getattr(ctx.config,
                                       "store_verify_checksums", True),
                        columns=columns)
        ds = ctx.from_pdata(pd)
        assert isinstance(ds, Dataset)
        return ds, ds.node.data


def _run_statement(ctx, catalog: Catalog, bound: BoundSelect,
                   event=None, job: Optional[str] = None
                   ) -> Dict[str, Any]:
    """Lower + plan + execute one statement under ``ctx``; host table."""
    from dryad_tpu.exec.data import maybe_shrink_for_collect, \
        pdata_to_host
    from dryad_tpu.plan.planner import plan_query
    from dryad_tpu.sql.lower import lower
    ds, _handles = lower(ctx, catalog, bound)
    graph = plan_query(ds.node, ctx.nparts, hosts=ctx.hosts,
                       levels=ctx.levels, config=ctx.config)
    pd = ctx.executor.run(graph, event_log=event, job=job)
    return pdata_to_host(maybe_shrink_for_collect(pd,
                                                  config=ctx.config))


def _rows_of(table: Dict[str, Any]) -> int:
    for v in table.values():
        return len(v)
    return 0


def _trim(table: Dict[str, Any], limit: Optional[int]
          ) -> Dict[str, Any]:
    if limit is None:
        return table
    return {k: v[:limit] for k, v in table.items()}


def _is_str_col(v) -> bool:
    return (isinstance(v, list)
            or getattr(getattr(v, "dtype", None), "kind", "") == "S")


def _as_py_key(x):
    """Canonical hashable form of one group-key value."""
    if isinstance(x, (bytes, bytearray)):
        return bytes(x)
    return x.item() if hasattr(x, "item") else x


def _merge_state(plan: DeltaPlan, prev: Dict[str, Any],
                 partial: Dict[str, Any]):
    """Merge an engine partial table into the persisted state columns.

    Returns ``(columns, touched, dtypes)`` — merged columns as python
    lists (value cells stay numpy scalars so addition happens in the
    ENGINE dtype, wraparound and all), the set of group row indices
    this partial touched, and the numeric dtypes to commit with."""
    keys = plan.group_keys
    aggs = plan.state_aggs
    names = keys + list(aggs)
    cols: Dict[str, list] = {}
    dtypes: Dict[str, Any] = {}
    for name in names:
        pv = prev.get(name)
        cols[name] = list(pv) if pv is not None else []
        for src in (partial.get(name), pv):
            if src is not None and not _is_str_col(src) \
                    and name not in dtypes:
                dtypes[name] = np.asarray(src).dtype
    index = {tuple(_as_py_key(cols[k][i]) for k in keys): i
             for i in range(len(cols[names[0]]) if names else 0)}
    touched = set()
    n_part = _rows_of(partial)
    for r in range(n_part):
        kt = tuple(_as_py_key(partial[k][r]) for k in keys)
        i = index.get(kt)
        if i is None:
            i = len(cols[names[0]]) if names else 0
            index[kt] = i
            for k in keys:
                cols[k].append(partial[k][r])
            for a in aggs:
                cols[a].append(partial[a][r])
        else:
            for a, (kind, _in) in aggs.items():
                cur, new = cols[a][i], partial[a][r]
                if kind in ("sum", "sum64", "count"):
                    cols[a][i] = cur + new
                elif kind == "min":
                    cols[a][i] = min(cur, new)
                else:                               # max
                    cols[a][i] = max(cur, new)
        touched.add(i)
    return cols, touched, dtypes


def _state_arrays(cols: Dict[str, list],
                  dtypes: Dict[str, Any]) -> Dict[str, np.ndarray]:
    out: Dict[str, np.ndarray] = {}
    for name, vals in cols.items():
        if name in dtypes:
            out[name] = np.asarray(vals, dtype=dtypes[name])
        else:                                       # string key column
            out[name] = np.asarray([bytes(v) for v in vals])
    return out


def _finalize(plan: DeltaPlan, cols: Dict[str, Any],
              idx: Optional[List[int]] = None) -> Dict[str, Any]:
    """State columns -> the SELECT's output columns, optionally row-
    sliced.  Mean divides with the engine's exact arithmetic (the
    builtin Decomposable finalize of plan/planner.py)."""
    def pick(name):
        v = cols[name]
        if idx is not None:
            return ([v[i] for i in idx] if isinstance(v, list)
                    else np.asarray(v)[np.asarray(idx, dtype=int)]
                    if len(idx) else np.asarray(v)[:0])
        return v

    out: Dict[str, Any] = {}
    for name, spec in plan.finalize.items():
        if spec[0] in ("key", "state"):
            v = pick(spec[1])
            out[name] = (v if isinstance(v, list)
                         else np.asarray(v))
        else:                                       # ("mean", sum, cnt)
            tot = np.asarray(pick(spec[1]))
            cnt = np.asarray(pick(spec[2]))
            cf = np.maximum(cnt, 1)
            if np.issubdtype(tot.dtype, np.floating):
                out[name] = tot / cf.astype(tot.dtype)
            else:
                out[name] = (tot.astype(np.float32)
                             / cf.astype(np.float32))
    return out


def run_refresh(ctx, catalog: Catalog, bound: BoundSelect, norm: str,
                state_dir: str, event=None, job: Optional[str] = None
                ) -> RefreshResult:
    """Execute one refresh of a standing query under ``ctx`` (a real
    api.Context whose executor/mesh carry the run).  ``norm`` is the
    normalized query text (state fingerprint component); ``event`` an
    optional sink for the inc_* lifecycle events."""
    from dryad_tpu.io.store import (parts_since, store_generation,
                                    store_meta)
    t0 = time.perf_counter()
    emit = event if event is not None else (lambda e: None)
    table = catalog.tables[bound.base_table]
    if table.kind != "store":
        raise ValueError(f"standing query base table "
                         f"{bound.base_table!r} is {table.kind}-backed "
                         f"— refreshes need a growing store")
    meta = store_meta(table.path)
    gen = store_generation(meta)
    plan = plan_delta(catalog, bound)
    sp = inc_state.state_path(
        state_dir, inc_state.state_key(norm, bound.base_table,
                                       table.path, meta["schema"]))
    loaded = inc_state.load_state(sp)
    watermark = loaded[0] if loaded is not None else -1
    delta = parts_since(meta, watermark)

    def done(mode, code, parts, res_table, changed, extra_event=None):
        wall = time.perf_counter() - t0
        drows = sum(int(meta["counts"][p]) for p in parts)
        res = RefreshResult(
            mode=mode, shape=plan.shape, code=code, generation=gen,
            watermark=gen, delta_parts=list(parts), delta_rows=drows,
            table=res_table, rows=_rows_of(res_table),
            changed=changed, changed_rows=_rows_of(changed),
            wall_s=wall)
        if extra_event:
            emit(extra_event)
        emit({"event": "inc_refresh", "mode": mode, "code": code,
              "generation": gen, "delta_parts": len(parts),
              "delta_rows": drows, "rows": res.rows,
              "changed_rows": res.changed_rows,
              "wall_s": round(wall, 4),
              "delta": table_payload(changed, cap=64)})
        return res

    if not plan.decomposable:
        # full re-run each refresh; the watermark-only state records
        # how far the result has seen, so restarts / schedulers know
        # whether a store generation is already reflected
        res_table = _trim(_run_statement(ctx, catalog, bound,
                                         event=event, job=job),
                          bound.limit)
        inc_state.commit_state(sp, gen, {})
        emit({"event": "inc_state_write", "watermark": gen,
              "state_rows": 0, "path": sp})
        return done("rescan", "DTA402", delta, res_table, res_table,
                    extra_event={"event": "inc_fallback_rescan",
                                 "code": "DTA402",
                                 "reasons": plan.reasons})

    if not delta:
        # nothing appended since the committed watermark: finalize the
        # state in hand (aggregate) or emit an empty delta (append)
        if plan.shape == "aggregate" and loaded is not None:
            full = _finalize(plan, loaded[1])
            empty = {k: v[:0] if not isinstance(v, list) else []
                     for k, v in full.items()}
            return done("noop", plan.code, [], full, empty)
        return done("noop", plan.code, [], {}, {})

    if plan.shape == "append":
        # each refresh emits exactly the rows its delta produced
        dcat = _DeltaCatalog(catalog, bound.base_table, delta)
        res_table = _run_statement(ctx, dcat, bound, event=event,
                                   job=job)
        inc_state.commit_state(sp, gen, {})
        emit({"event": "inc_state_write", "watermark": gen,
              "state_rows": 0, "path": sp})
        return done("incremental", "DTA401", delta, res_table,
                    res_table)

    # aggregate shape.  Cost rule (DTA403): when the delta is most of
    # the store, merging saves nothing — rebuild state from a full scan
    rebuild = False
    if loaded is not None:
        delta_bytes = sum(int(meta["bytes"][p]) for p in delta)
        total_bytes = sum(int(b) for b in meta["bytes"])
        rebuild = (total_bytes > 0 and
                   delta_bytes > REBUILD_DELTA_FRACTION * total_bytes)
    scan = (list(range(int(meta["npartitions"])))
            if rebuild or loaded is None else delta)
    stmt = state_statement(bound, plan)
    dcat = _DeltaCatalog(catalog, bound.base_table, scan)
    partial = _run_statement(ctx, dcat, stmt, event=event, job=job)
    prev = {} if (rebuild or loaded is None) else loaded[1]
    cols, touched, dtypes = _merge_state(plan, prev, partial)
    inc_state.commit_state(sp, gen, _state_arrays(cols, dtypes))
    emit({"event": "inc_state_write", "watermark": gen,
          "state_rows": len(cols[plan.group_keys[0]])
          if plan.group_keys else _rows_of(cols), "path": sp})
    full = _finalize(plan, cols)
    if rebuild:
        return done("rebuild", "DTA403", scan, full, full,
                    extra_event={"event": "inc_fallback_rescan",
                                 "code": "DTA403",
                                 "delta_parts": len(delta)})
    changed = _finalize(plan, cols, idx=sorted(touched))
    return done("incremental", "DTA401", scan, full, changed)
