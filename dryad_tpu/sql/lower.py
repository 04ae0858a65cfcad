"""Lowering: BoundSelect -> api.Dataset operator chain.

The DryadLINQ layer-1 translation (LINQ expression tree -> query plan),
re-targeted: a bound SQL statement becomes the SAME ``Dataset`` calls a
Python user would write, so every query inherits the whole stack for
free — pre-submit lint + DTA2xx cost forecasts, ``EXPLAIN [COST]`` via
``Dataset.explain()``, adaptive stage-boundary rewrites, streamed
sources, and per-tenant admission when submitted through the service.

Shape of the lowered chain::

    FROM t [, u | JOIN u]  catalog.dataset() roots, read from the store
                           at the columns the statement reads, + rename
                           Projector (a column the statement names becomes
                           ``alias.col``; the others are dropped)
    WHERE, one table's     .where(Predicate) on that table's scan, below
                           its join; a column only it reads goes after
    JOIN / FROM-list keys  .join(...), the larger input on the left; a
                           build side joined on a key — the key its
                           store carries, or one what is joined so far
                           kept through the joins before: right_unique=
                           "verified", the lookup kernel alone
    WHERE, the residual    .where(Predicate) above the joins
    GROUP BY + aggregates  pre-Projector (keys + agg-input exprs)
                           -> .group_by(keys, aggs) [-> .where(HAVING)]
    SELECT list            final Projector (output names)
    DISTINCT               .distinct()
    ORDER BY               .order_by([(name, desc)])
    LIMIT                  .take(n)

All callables are :mod:`dryad_tpu.sql.rowexpr` programs — shippable as
data (plan/serialize.ship_ref_of) and content-fingerprinted for the
executor's compile cache, so a resubmitted query is a warm hit.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from dryad_tpu.sql.binder import BoundSelect
from dryad_tpu.sql.catalog import Catalog
from dryad_tpu.sql.rowexpr import (Predicate, Projector, prog_columns,
                                   rename_prog)

__all__ = ["lower", "GLOBAL_AGG_KEY"]

GLOBAL_AGG_KEY = "__sqlagg_key"


def _rename_projector(renames: Dict[str, str]) -> Projector:
    return Projector({phys: ["col", src] for phys, src in
                      renames.items()})


def _stamp(ds, span):
    """Point the node's provenance INTO THE QUERY TEXT (file slot =
    query origin, func slot = ``sql:<col>``): analyzer findings and
    runtime errors for SQL-lowered nodes quote the query, and offline
    plan JSON is deterministic regardless of which Python frame drove
    the lowering."""
    if span is not None:
        object.__setattr__(ds.node, "span",
                           (span.file, span.line, f"sql:{span.col}"))
    return ds


def lower(ctx, catalog: Catalog, bound: BoundSelect, loader=None,
          span=None) -> Tuple[Any, Dict[int, str]]:
    """(dataset, source-handle map) for a bound statement under ``ctx``
    (api.Context or sql.catalog.SchemaContext).  The handle map
    (``id(Source.data) -> table name``) lets the service re-bind plan
    source slots on a warm plan-cache hit.  ``loader`` (optional,
    ``name -> PData``) is forwarded to :meth:`Catalog.dataset` — the
    service's scan-share hook (one cold scan for concurrent jobs over
    the same table).  ``span`` (the caller's ``sql.lower`` span) is
    given ``columns_kept`` / ``columns_stored``, a table."""
    handles: Dict[int, str] = {}

    # an inner join's output drops its right input's key columns; what
    # the statement reads of them above the join reads the left input's
    # (equal) key instead: dropped name -> the name that lives on
    subst: Dict[str, str] = {}

    def live(name: str) -> str:
        while name in subst:
            name = subst[name]
        return name

    def above(prog):
        return rename_prog(prog, {k: live(k) for k in subst})

    # the columns each scan keeps: what the statement names above the
    # scans (keys, residual, group, aggregates, select list) ...
    named = set(bound.group_keys)
    for j in bound.joins:
        named |= set(j.left_keys) | set(j.right_keys)
    progs = list((bound.pre_projection if bound.grouped
                  else bound.outputs).values())
    if bound.residual is not None:
        progs.append(bound.residual)
    for prog in progs:
        named |= prog_columns(prog)
    kept: Dict[str, int] = {}
    stored: Dict[str, int] = {}

    def root(table: str, alias: str, renames: Dict[str, str], span):
        pred = bound.scan_filters.get(alias)
        # ... and, until it has run, what the table's own filter reads
        pred_reads = prog_columns(pred) if pred is not None else set()
        keep = [p for p in renames if p in named]
        only_pred = [p for p in renames
                     if p in pred_reads and p not in named]
        if not keep:
            # COUNT(*) names no column; a batch needs one to have rows:
            # one the filter reads anyway, else a numeric one
            keep = only_pred[:1] or [min(renames, key=lambda p: (
                catalog.get(table).schema[renames[p]]["kind"] == "str"))]
            only_pred = only_pred[1:]
        reads = keep + only_pred
        # the store is read at the columns the statement reads, no others
        ds, data = catalog.dataset(ctx, table, loader=loader,
                                   columns=[renames[p] for p in reads])
        handles[id(data)] = table
        _stamp(ds, span)
        stored[table] = stored.get(table, 0) + len(renames)
        kept[table] = kept.get(table, 0) + len(keep)
        ds = _stamp(ds.select(_rename_projector(
            {p: renames[p] for p in reads}), label=f"sql-scan {alias}"),
            span)
        if pred is not None:
            ds = _stamp(ds.where(Predicate(pred),
                                 label=f"sql-where {alias}"),
                        bound.where_span or span)
            if only_pred:
                ds = _stamp(ds.select(_rename_projector(
                    {p: p for p in keep}), label=f"sql-prune {alias}"),
                    span)
        return ds

    cur = root(bound.base_table, bound.base_alias, bound.base_renames,
               bound.span)
    for j in bound.joins:
        other = root(j.table, j.alias, j.renames, j.span)
        lks = [live(k) for k in j.left_keys]
        # a key the catalog carries was verified where the rows were
        # written, and one kept through marked joins holds by their
        # kernel: no run-time check, no second kernel in the program
        ru = "verified" if j.unique else False
        cur = _stamp(other.join(cur, j.right_keys, lks, how=j.how,
                                right_unique=ru)
                     if j.swap else
                     cur.join(other, lks, j.right_keys, how=j.how,
                              right_unique=ru), j.span)
        if j.how == "inner":
            subst.update(zip(lks, j.right_keys) if j.swap
                         else zip(j.right_keys, lks))
    if span is not None:
        span.set(columns_kept=kept, columns_stored=stored,
                 unique_joins=sum(j.unique for j in bound.joins),
                 inherited_unique_joins=sum(j.unique_by == "inherited"
                                            for j in bound.joins))
    if bound.residual is not None:
        cur = _stamp(cur.where(Predicate(above(bound.residual)),
                               label="sql-where"),
                     bound.where_span or bound.span)
    if bound.grouped:
        pre = {name: above(prog)
               for name, prog in (bound.pre_projection or {}).items()}
        keys = list(bound.group_keys)
        if not keys:
            # global aggregate: one constant key, dropped again by the
            # final projection (api.Dataset.aggregate pattern)
            pre[GLOBAL_AGG_KEY] = ["const", 0, "int"]
            keys = [GLOBAL_AGG_KEY]
        cur = _stamp(cur.select(Projector(pre), label="sql-agg-in"),
                     bound.span)
        cur = _stamp(cur.group_by(keys, dict(bound.aggs)), bound.span)
        if bound.having is not None:
            cur = _stamp(cur.where(Predicate(bound.having),
                                   label="sql-having"),
                         bound.having_span or bound.span)
        outputs = bound.outputs
    else:
        outputs = {name: above(prog)
                   for name, prog in bound.outputs.items()}
    cur = _stamp(cur.select(Projector(outputs), label="sql-select"),
                 bound.span)
    if bound.distinct:
        cur = _stamp(cur.distinct(), bound.span)
    if bound.order_by:
        cur = _stamp(cur.order_by(list(bound.order_by)), bound.span)
    if bound.limit is not None:
        cur = _stamp(cur.take(bound.limit), bound.span)
    # belt+braces: any node a Context helper built internally (e.g. a
    # streamed from_store chain) still carries a Python creation span —
    # restamp everything reachable so the whole SQL plan points at the
    # query
    from dryad_tpu.plan import expr as E
    for n in E.walk(cur.node):
        sp = getattr(n, "span", None)
        if sp is None or not str(sp[2] if sp else "").startswith("sql:"):
            object.__setattr__(n, "span",
                               (bound.span.file, bound.span.line,
                                f"sql:{bound.span.col}")
                               if bound.span is not None else None)
    return cur, handles


def source_tables(graph, handles: Dict[int, str]
                  ) -> Dict[str, Optional[str]]:
    """Map a planned StageGraph's source slots ("sid:leg", the
    runtime/shiplan spec key format) back to catalog table names via
    the handle identities recorded by :func:`lower`."""
    out: Dict[str, Optional[str]] = {}
    for st in graph.stages:
        for li, leg in enumerate(st.legs):
            if isinstance(leg.src, tuple) and leg.src[0] == "source":
                out[f"{st.id}:{li}"] = handles.get(id(leg.src[1]))
    return out
