"""numpy <-> stores through the program's public way in and out, with no
per-row Python.  Copies of ``chip_smoke._to_device`` / ``_write_input``
(PERF.md, Open questions); the check reads stores back with
``ref/storefile.py``, not with the program.  A string column is a pair
``(bytes [n, L] uint8, lengths [n] int32)``."""

from __future__ import annotations

import numpy as np


def to_device(ctx, columns, n):
    """numpy columns [n, ...] -> PData block-partitioned over the mesh."""
    from dryad_tpu.data.columnar import Batch, StringColumn
    from dryad_tpu.exec.data import PData, put_batch
    parts = ctx.nparts
    if n % parts:
        raise ValueError(f"{n} rows do not divide over {parts} partitions")
    cap = n // parts

    def block(a):
        return a.reshape((parts, cap) + a.shape[1:])

    cols = {k: (StringColumn(block(v[0]), block(v[1]))
                if isinstance(v, tuple) else block(v))
            for k, v in columns.items()}
    batch = put_batch(Batch(cols, np.full((parts,), cap, np.int32)),
                      ctx.mesh)
    return PData(batch, parts)


def write_input(ctx, path, columns, n) -> int:
    """Ingest through ``from_pdata -> to_store``.  Returns the bytes the
    input held on the device."""
    import jax
    pd = to_device(ctx, columns, n)
    ctx.from_pdata(pd).to_store(path)
    nbytes = int(sum(x.nbytes for x in jax.tree.leaves(pd.batch)))
    del pd
    return nbytes


def stored_bytes(path) -> int:
    """Payload bytes of a store as its manifest counts them."""
    from dryad_tpu.io.store import store_meta
    return int(sum(store_meta(path)["bytes"]))
