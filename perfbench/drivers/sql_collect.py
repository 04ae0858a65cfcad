"""Driver ``sql_collect``: one query is ``sql.query(ctx, catalog,
text).collect()`` against a ``Catalog`` with every table the kind
ingested registered by ``register_store``.  The traffic file gives
``"query_file"``, a path under ``perfbench/``.  In a traced run the front
end is also timed alone (``sql.compile_query``: parse and bind, the call
``sql.query`` makes first)."""

from __future__ import annotations

import os


def prepare(ctx, state, traffic, workdir):
    from dryad_tpu import sql
    cat = sql.Catalog()
    for name, path in state["tables"].items():
        cat.register_store(name, path)
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, traffic["query_file"])) as f:
        return {"catalog": cat, "text": f.read()}


def query(ctx, d, i, spans):
    from dryad_tpu import sql
    if spans.traced:
        with spans.span("sql_bind"):
            sql.compile_query(d["catalog"], d["text"])
    with spans.span("sql_front"):
        ds = sql.query(ctx, d["catalog"], d["text"])
        spans.sync([n.data.batch for n in _sources(ds)])
    with spans.span("execute_and_fetch"):
        return {"collected": ds.collect()}


def _sources(ds):
    from dryad_tpu.plan import expr as E
    return [n for n in E.walk(ds.node)
            if isinstance(n, E.Source) and hasattr(n.data, "batch")]


def release(answer):
    pass
