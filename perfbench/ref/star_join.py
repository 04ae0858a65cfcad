"""The check of a star join whose measures are integers: the reference
is ``relational_join`` (per-table filters, inner equi-joins, group, sum —
float64 holds every integer below 2**53, and these cells' sums stay
below 2**35), read from the same specification in the traffic file.
What this adds is the comparison: an integer sum is compared **for equality** (``sums_wrong``:
sums that differ from the integer at all), and the order asked for may
name a string key.  It imports nothing of the program."""

from __future__ import annotations

import numpy as np

from perfbench.ref import relational_join


def _cell(v):
    return bytes(v) if isinstance(v, (bytes, bytearray, np.bytes_)) \
        else (v.encode("latin1") if isinstance(v, str)
              else np.asarray(v).item())


def compare(spec, ref, got):
    """Numbers compared between the reference's whole answer and a
    collected result (string columns as lists of bytes)."""
    key_cols = spec["group_by"]
    names = list(spec["aggregates"])
    res = {"columns_missing": sum(1 for c in key_cols + names
                                  if c not in got),
           "rows_returned_wrong": 0, "groups_wrong": 0,
           "rows_out_of_order": 0, "sums_wrong": 0}
    if res["columns_missing"]:
        res["sums_wrong"] = len(ref["keys"]) * len(names)
        return res
    n_got = len(got[names[0]])
    res["rows_returned_wrong"] = int(n_got != len(ref["keys"]))
    got_keys = [tuple(_cell(got[k][i]) for k in key_cols)
                for i in range(n_got)]
    ref_index = {k: i for i, k in enumerate(ref["keys"])}
    res["groups_wrong"] = len(set(got_keys) ^ set(ref_index)) \
        + (len(got_keys) - len(set(got_keys)))

    # the order asked for, read off the program's own columns: a row may
    # not sort before its predecessor
    def sort_key(i):
        out = []
        for name, way in spec.get("order_by", []):
            v = _cell(got[name][i])
            if way == "desc":
                if isinstance(v, bytes):
                    raise ValueError("descending order by a string key")
                v = -v
            out.append(v)
        return tuple(out)

    order = [sort_key(i) for i in range(n_got)]
    res["rows_out_of_order"] = sum(a > b for a, b in zip(order, order[1:]))
    for gi, k in enumerate(got_keys):
        ri = ref_index.get(k)
        if ri is None:
            continue
        for name in names:
            # Python integers on both sides: no width to wrap in
            res["sums_wrong"] += int(
                int(np.asarray(got[name][gi]).item())
                != int(ref["columns"][name][ri]))
    return res


def check(answer, data, spec, nparts):
    """The numbers compared for one collected answer."""
    return compare(spec, relational_join.reference(data, spec),
                   answer["collected"])


def as_collected(spec, ans, wrap32=False):
    """A reference answer in the shape ``collect()`` gives: keys as lists
    (strings as bytes), sums as ``int64`` — or, for the control, wrapped
    to 32 bits as an ``int32`` accumulator leaves them."""
    out = {k: [key[i] for key in ans["keys"]]
           for i, k in enumerate(spec["group_by"])}
    for k, v in ans["columns"].items():
        s = np.asarray(v).astype(np.int64)
        out[k] = ((s + 2**31) % 2**32 - 2**31) if wrap32 else s
    return out


def control(data, spec, nparts):
    """The reference with its sums wrapped to 32 bits, in the program's
    place: the precision below the one the configuration states."""
    return {"collected": as_collected(
        spec, relational_join.run(spec, data["tables"]), wrap32=True)}


def control_dropped_filter(data, spec, nparts):
    """The reference with one table's filter left out (the traffic file
    names the table), in the program's place: a guarantee broken."""
    return {"collected": as_collected(spec, relational_join.run(
        spec, data["tables"], drop_filter=spec["control_drop_filter"]))}
