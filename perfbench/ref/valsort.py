"""Plain numpy validator of a sort of gensort records, after the sort
benchmark's own ``valsort``: the output is the input permuted, every
payload is with its key, and keys never decrease byte-lexicographically
over the whole output in partition order.  It imports nothing of the
program and sorts nothing: each output row names its input row by the
record number in its payload."""

from __future__ import annotations

import numpy as np


def key_lanes(keys):
    """[n, 10] u8 -> (u64 of bytes 0-7, u16 of bytes 8-9), big-endian, so
    integer order is byte-lexicographic order."""
    hi = np.ascontiguousarray(keys[:, :8]).view(">u8").ravel()
    lo = np.ascontiguousarray(keys[:, 8:10]).view(">u2").ravel()
    return hi.astype(np.uint64), lo.astype(np.uint16)


def validate(data, out_keys, out_key_len, out_payload, out_payload_len,
             part_rows, nparts):
    """Numbers compared, all exact (limit 0)."""
    n = data["n"]
    keys, payload = data["keys"], data["payload"]
    res = {"rows_missing": abs(n - len(out_keys)), "rows_misplaced": 0,
           "rows_not_input": 0, "rows_out_of_order": 0,
           "partitions_wrong": int(len(part_rows) != nparts)}
    if len(out_keys) != n or out_keys.shape[1:] != keys.shape[1:] \
            or out_payload.shape != payload.shape:
        res["rows_missing"] = max(res["rows_missing"], 1)
        return res
    bad_len = (out_key_len != keys.shape[1]) | \
        (out_payload_len != payload.shape[1])
    rec = np.ascontiguousarray(out_payload[:, :8]).view(">u8").ravel()
    in_range = rec < n
    seen = np.bincount(rec[in_range].astype(np.int64), minlength=n)
    # rows of the input that the output does not hold exactly once
    res["rows_not_input"] = int((seen != 1).sum() + (~in_range).sum())
    src = np.where(in_range, rec, 0).astype(np.int64)
    wrong = bad_len | ~in_range
    wrong |= (out_keys != keys[src]).any(axis=1)
    wrong |= (out_payload != payload[src]).any(axis=1)
    res["rows_misplaced"] = int(wrong.sum())
    hi, lo = key_lanes(out_keys)
    desc = (hi[1:] < hi[:-1]) | ((hi[1:] == hi[:-1]) & (lo[1:] < lo[:-1]))
    res["rows_out_of_order"] = int(desc.sum())
    return res


def check(answer, data, spec, nparts):
    """The numbers compared for one answer: a store the timed query wrote
    (read back from disk as it lies there) or, for the control, columns
    handed over directly."""
    if "store" in answer:
        from perfbench.ref import storefile
        cols, counts = storefile.read(answer["store"])
    else:
        cols, counts = answer["columns"], answer["counts"]
    k, kl = cols[spec.get("key", "key")]
    p, pl = cols[spec.get("payload", "payload")]
    return validate(data, k, kl, p, pl, counts, nparts)


def control(data, spec, nparts):
    """The control: a sort that compares only the first
    ``control_prefix_bytes`` of the key (one 32-bit lane), the guarantee a
    faster kernel would be tempted to weaken, cut evenly into the cell's
    partitions.  Returns what a store read-back gives."""
    n = data["n"]
    prefix_bytes = int(spec.get("control_prefix_bytes", 4))
    pre = np.zeros((n, 4), np.uint8)
    pre[:, :prefix_bytes] = data["keys"][:, :prefix_bytes]
    order = np.argsort(pre.view(">u4").ravel(), kind="stable")
    kb, pb = data["keys"].shape[1], data["payload"].shape[1]
    return {"columns": {"key": (data["keys"][order],
                                np.full(n, kb, np.int32)),
                        "payload": (data["payload"][order],
                                    np.full(n, pb, np.int32))},
            "counts": [n // nparts + (1 if p < n % nparts else 0)
                       for p in range(nparts)]}
