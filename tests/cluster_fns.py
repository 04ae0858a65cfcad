"""Module-level UDFs for cluster-mode tests — plan callables must be
importable by workers (runtime/shiplan.py), the analogue of the
reference's `assembly!class.method` vertex entries (QueryParser.cs:100)."""


def double_v(cols):
    return dict(cols, v=cols["v"] * 2)


def v_as_x(cols):
    return {"x": cols["v"]}


def v_doubled_as_y(cols):
    return {"y": cols["v"] * 2}


def poison_wide_lines(cols):
    """Deterministically raises for the partition whose packed string
    column is wider than 64 bytes (StringColumn.max_len is static, so
    the raise fires identically at trace time on the worker AND under
    `python -m dryad_tpu.obs replay` — the forensics-reproduction
    fixture)."""
    w = cols["line"].max_len
    if w > 64:
        raise ValueError(f"poison partition: line bytes {w} > 64")
    return cols


def keep_positive(cols):
    return cols["v"] > 0


FN_TABLE = {}


def inc_v(cols):
    return dict(cols, v=cols["v"] + 1)


def _topsum_seed(cols):
    return cols["v"]


def _topsum_merge(a, b):
    return a + b


def make_sum_dec():
    from dryad_tpu.plan.expr import Decomposable
    return Decomposable(_topsum_seed, _topsum_merge, None)


def second_largest(cols, count):
    """group_apply fn: per-group 2nd-largest v (largest for singletons)."""
    import jax.numpy as jnp
    v = cols["v"]
    lo = (jnp.finfo(v.dtype).min if jnp.issubdtype(v.dtype, jnp.floating)
          else jnp.iinfo(v.dtype).min)
    masked = jnp.where(jnp.arange(v.shape[0]) < count, v, lo)
    s = jnp.sort(masked)[::-1]
    pick = jnp.where(count >= 2, s[1], s[0])
    return {"second": pick[None]}, jnp.ones((1,), jnp.bool_)


# registered-by-name objects for cluster shipping (shiplan FN_TABLE path)
SUM_DEC = make_sum_dec()
FN_TABLE = {"sum_dec": SUM_DEC}


# -- streamed-cluster PageRank body fns (importable, fixed constants) -------

PR_NODES = 60
PR_DAMPING = 0.85


def pr_contrib(cols):
    return {"node": cols["dst"], "c": cols["rank"] / cols["deg"]}


def pr_damp(cols):
    return {"node": cols["node"],
            "rank": (1.0 - PR_DAMPING) / PR_NODES
            + PR_DAMPING * cols["s"]}
