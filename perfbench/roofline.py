"""The bytes an algorithm has to move, from shapes alone, so that a
roofline share reads the same whatever later implements the kernel.  The
least time is bytes over the chip's HBM bytes/s (``peaks.json``); all of
these are bound by bytes, not by operations."""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json: "
                       f"an unknown device is an error, not a default")
    return table[device_kind]


def device_row_bytes(schema):
    """Bytes of one row as the program holds it: a string column is its
    ``max_len`` bytes and a 4-byte length lane.  What a tiled layout pads
    on top of that is the implementation's, not the algorithm's."""
    total = 0
    for spec in schema.values():
        if spec["kind"] == "str":
            total += int(spec["max_len"]) + 4
        else:
            n = 1
            for s in spec.get("shape", []):
                n *= int(s)
            total += n * int(spec["itemsize"])
    return total


def sort_bytes(rows_per_device, row_bytes):
    """A sort has to read every row once and write it once."""
    return 2 * rows_per_device * row_bytes


def scan_bytes(rows_per_device, schema, columns):
    """A scan that feeds a filter and a group-by into few groups has to
    read the columns the query names, once; what it writes is nothing
    beside that."""
    return rows_per_device * device_row_bytes(
        {c: schema[c] for c in columns})


def least_seconds(nbytes, device_kind):
    return nbytes / peaks(device_kind)["hbm_bytes_per_s"]
