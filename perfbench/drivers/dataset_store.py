"""Driver ``dataset_store``: one query is ``ctx.from_store(src)``, the
``Dataset`` calls listed in the traffic file, then ``to_store(dst_i)`` or
``collect()``.  The traffic file gives:

    "source_table": name of a table the kind ingested,
    "ops": [[method, [args...]], ...]   e.g. [["order_by", [[["key", false]]]]]
    "sink": "to_store" | "collect"
"""

from __future__ import annotations

import os
import shutil


def prepare(ctx, state, traffic, workdir):
    return {"src": state["tables"][traffic["source_table"]],
            "ops": traffic.get("ops", []), "sink": traffic["sink"],
            "out": os.path.join(workdir, "out")}


def query(ctx, d, i, spans):
    with spans.span("store_read"):
        ds = ctx.from_store(d["src"])
        spans.sync(ds.node.data.batch)
    for method, args in d["ops"]:
        ds = getattr(ds, method)(*[_untuple(a) for a in args])
    if d["sink"] == "collect":
        with spans.span("execute_and_fetch"):
            return {"collected": ds.collect()}
    dst = f"{d['out']}-{i}" if i >= 0 else f"{d['out']}-warm"
    with spans.span("execute_and_write"):
        ds.to_store(dst)
    return {"store": dst}


def _untuple(a):
    """JSON has no tuples: a list of [name, flag] pairs is what
    ``order_by`` takes as tuples."""
    if isinstance(a, list) and a and all(
            isinstance(x, list) and len(x) == 2 for x in a):
        return [tuple(x) for x in a]
    return a


def release(answer):
    if "store" in answer:
        shutil.rmtree(answer["store"], ignore_errors=True)
