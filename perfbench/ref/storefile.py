"""Plain reader of a store directory as it lies on disk (``meta.json`` and
``part-NNNNN.bin``: the columns of one partition in sorted-name order, a
string column as its bytes then its int32 lengths).  The check reads the
bytes the timed query wrote with this, not with the program."""

from __future__ import annotations

import json
import os

import numpy as np


def read(path):
    """-> (columns, rows of each partition); a string column is a pair
    ``(bytes [n, L] uint8, lengths [n] int32)``; rows in partition order."""
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    if meta.get("compression"):
        raise ValueError("storefile.read: compressed store")
    schema = meta["schema"]
    counts = [int(c) for c in meta["counts"]]
    parts = {k: [] for k in schema}
    for p, n in enumerate(counts):
        fn = os.path.join(path, f"part-{p:05d}.bin")
        off = 0
        for k in sorted(schema):
            spec = schema[k]
            if spec["kind"] == "str":
                w = int(spec["max_len"])
                d = np.fromfile(fn, np.uint8, n * w, offset=off)
                off += n * w
                ln = np.fromfile(fn, np.int32, n, offset=off)
                off += n * 4
                parts[k].append((d.reshape(n, w), ln))
            else:
                dt = np.dtype(spec["dtype"])
                shape = tuple(spec["shape"])
                cnt = n * int(np.prod(shape, dtype=np.int64))
                a = np.fromfile(fn, dt, cnt, offset=off)
                off += cnt * dt.itemsize
                parts[k].append(a.reshape((n,) + shape))
        if off != os.path.getsize(fn):
            raise ValueError(f"{fn}: {os.path.getsize(fn)} bytes on disk, "
                             f"the manifest describes {off}")
    cols = {}
    for k, ps in parts.items():
        if schema[k]["kind"] == "str":
            cols[k] = (np.concatenate([d for d, _ in ps]),
                       np.concatenate([ln for _, ln in ps]))
        else:
            cols[k] = np.concatenate(ps)
    return cols, counts
