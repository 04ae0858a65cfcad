"""SQL front end: declarative queries compiling to the plan DAG.

DryadLINQ's thesis is a language-integrated query layer over a general
DAG engine (PAPER.md layer 1; the reference's ``LinqToDryad/`` query
compiler).  This package is the second front end ROADMAP item 5 calls
for: a dependency-free SQL compiler — lexer -> recursive-descent parser
-> binder/catalog -> lowering — whose output is ordinary
:class:`api.Dataset` calls, so a query inherits pre-submit analysis,
``EXPLAIN [COST]``, adaptive rewrites, and multi-tenant service
admission with zero new engine code.

Entry points::

    from dryad_tpu import sql
    cat = sql.Catalog().register_store("lineitem", "file:///...")
    ds  = sql.query(ctx, cat, "SELECT k, SUM(v) AS s FROM t GROUP BY k")
    ds.collect()                      # ... or .explain(cost=True), etc.

    python -m dryad_tpu.sql --catalog cat.json          # REPL
    python -m dryad_tpu.sql --catalog cat.json \
        -e "EXPLAIN COST SELECT ..."                     # one-shot

Compile errors raise :class:`SqlError` — ONE exception carrying every
DTA3xx finding with line:column spans into the query text.  Every
successful lowering emits a ``sql_query`` event (normalized query text
+ catalog fingerprint) so history/forensics bundles identify SQL jobs.
"""

from __future__ import annotations

from typing import Tuple

from dryad_tpu.obs import trace
from dryad_tpu.sql.binder import BoundSelect, bind, conjuncts
from dryad_tpu.sql.catalog import (Catalog, CatalogTable, SchemaContext,
                                   SchemaOnlyTableError)
from dryad_tpu.sql.errors import SqlError
from dryad_tpu.sql.lower import lower, source_tables
from dryad_tpu.sql.parser import parse, parse_statement

__all__ = [
    "Catalog", "CatalogTable", "SchemaContext", "SchemaOnlyTableError",
    "SqlError",
    "parse", "parse_statement", "bind", "lower", "source_tables",
    "normalize_query", "compile_query", "query", "explain",
    "offline_explain", "offline_plan_json",
]


def normalize_query(text: str) -> str:
    """Whitespace-collapsed query text: the identity used for the
    ``sql_query`` event and the service's plan-cache key (two spellings
    of one query hit the same cache entry)."""
    return " ".join(text.split())


def compile_query(catalog: Catalog, text: str,
                  origin: str = "<sql>") -> Tuple[str, BoundSelect]:
    """Parse + bind (no Context needed): returns (mode, BoundSelect)
    where mode reflects a leading ``EXPLAIN [COST]``.  Raises
    :class:`SqlError` with all DTA3xx findings."""
    with trace.span("sql.parse", "front"):
        mode, stmt = parse_statement(text, origin=origin)
    with trace.span("sql.bind", "front") as sp:
        bound = bind(catalog, stmt)
        sp.set(joins=len(bound.joins),
               pushed_conjuncts=sum(len(conjuncts(p)) for p in
                                    bound.scan_filters.values()),
               residual_conjuncts=len(conjuncts(bound.residual)))
        return mode, bound


def query(ctx, catalog: Catalog, text: str, origin: str = "<sql>",
          event=None):
    """Compile ``text`` to a lazy :class:`api.Dataset` under ``ctx``.
    A leading EXPLAIN is rejected here (use :func:`explain`)."""
    ds, _handles = _lowered(ctx, catalog, text, origin=origin,
                            event=event)
    return ds


def _lowered(ctx, catalog: Catalog, text: str, origin: str = "<sql>",
             event=None):
    with trace.span("sql.query", "query"):
        mode, bound = compile_query(catalog, text, origin=origin)
        if mode != "run":
            raise ValueError(
                "EXPLAIN statements build no dataset — use sql.explain()")
        # the catalog's from_store (an eager store.read) nests in here
        with trace.span("sql.lower", "front") as sp:
            ds, handles = lower(ctx, catalog, bound, span=sp)
        _emit(ctx, event, text, catalog, bound)
    return ds, handles


def _emit(ctx, event, text: str, catalog: Catalog,
          bound: BoundSelect) -> None:
    sink = event if event is not None else getattr(ctx, "_event_log",
                                                   None)
    if sink is None:
        return
    sink({"event": "sql_query", "query": normalize_query(text),
          "catalog": catalog.fingerprint(),
          "tables": list(bound.tables)})
    sink({"event": "sql_lowered",
          "outputs": list(bound.outputs),
          "grouped": bound.grouped, "joins": len(bound.joins),
          "limit": bound.limit})


def explain(ctx, catalog: Catalog, text: str, origin: str = "<sql>",
            event=None) -> str:
    """EXPLAIN text for a query (with or without a leading EXPLAIN
    [COST | ANALYZE] keyword).  COST adds the DTA2xx predicted-cost
    table and the static diagnostics; ANALYZE **executes the query
    once** under an event capture and appends the measured per-stage
    actuals annotated against the cost model (obs/analyze.py — needs a
    real in-process Context with loadable tables, like running the
    query does)."""
    mode, bound = compile_query(catalog, text, origin=origin)
    ds, _ = lower(ctx, catalog, bound)
    _emit(ctx, event, text, catalog, bound)
    cost = mode == "explain_cost"
    out = ds.explain(verify=cost, cost=cost,
                     analyze=mode == "explain_analyze")
    if bound.emit_every is not None:
        # continuous queries: the static refresh verdict (DTA401/402 —
        # incremental merge vs full re-run) so a user knows BEFORE
        # registering whether each refresh pays O(delta) or O(store)
        from dryad_tpu.inc.delta_plan import plan_delta, render_verdict
        out += "\n" + render_verdict(catalog, bound,
                                     plan_delta(catalog, bound))
    return out


def offline_explain(catalog: Catalog, text: str, nparts: int = 8,
                    origin: str = "<sql>") -> str:
    """Textual EXPLAIN with NO mesh/devices/data (schema-only catalogs
    suffice) — the CLI's offline mode."""
    from dryad_tpu.plan.planner import plan_query
    _mode, bound = compile_query(catalog, text, origin=origin)
    ctx = SchemaContext(nparts=nparts)
    ds, _ = lower(ctx, catalog, bound)
    out = plan_query(ds.node, nparts, hosts=1,
                     config=ctx.config).explain()
    if bound.emit_every is not None:
        from dryad_tpu.inc.delta_plan import plan_delta, render_verdict
        out += "\n" + render_verdict(catalog, bound,
                                     plan_delta(catalog, bound))
    return out


def offline_plan_json(catalog: Catalog, text: str, nparts: int = 8,
                      origin: str = "<sql>") -> str:
    """Deterministic lowered-plan JSON with NO mesh/devices/data: the
    golden-plan drift gate (``python -m dryad_tpu.analysis
    --selfcheck``) and the offline CLI's EXPLAIN run on this.  Row-
    expression callables serialize as data (``__shipped__``), so the
    output round-trips through graph_from_json."""
    from dryad_tpu.plan.planner import plan_query
    from dryad_tpu.plan.serialize import graph_to_json
    mode, bound = compile_query(catalog, text, origin=origin)
    ctx = SchemaContext(nparts=nparts)
    ds, _ = lower(ctx, catalog, bound)
    graph = plan_query(ds.node, nparts, hosts=1, config=ctx.config)
    return graph_to_json(graph)
