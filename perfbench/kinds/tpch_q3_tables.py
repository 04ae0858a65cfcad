"""Kind ``tpch_q3_tables``: TPC-H's ``customer``, ``orders`` and
``lineitem`` (spec v3, 1.4.1 and 4.2.3) at their 8, 9 and 16 columns and
dbgen's value domains, made with numpy from the seed, the three tables
consistent with each other: every ``l_orderkey`` is an order, every
``o_custkey`` a customer, a line ships 1 to 121 days after its order's
date, ``o_totalprice`` and ``o_orderstatus`` come from the order's lines.
``lineitem`` follows ``tpch_lineitem``'s rules column for column.  Where
this differs from dbgen the configuration file lists it under
``assumed``.  Dates are int32 days since 1970-01-01, decimals float32,
CHAR/VARCHAR columns ``(bytes, lengths)`` pairs."""

from __future__ import annotations

import os

import numpy as np

from perfbench import storeio
from perfbench.kinds.tpch_lineitem import (D_1992_01_01, D_1995_06_17,
                                           D_1998_08_02, SHIPINSTRUCT,
                                           SHIPMODE, _choice_strings)

SEGMENTS = (b"AUTOMOBILE", b"BUILDING", b"FURNITURE", b"MACHINERY",
            b"HOUSEHOLD")
PRIORITIES = (b"1-URGENT", b"2-HIGH", b"3-MEDIUM", b"4-NOT SPECIFIED",
              b"5-LOW")
TABLES = ("customer", "orders", "lineitem")


def sizes(cfg, rehearse=False):
    """{table: rows}: ``lineitem`` as the file says, the others in the
    specification's proportion (4 lines an order, 10 orders a customer)."""
    n = int(cfg["rehearse"]["rows"] if rehearse else cfg["rows"])
    return {"customer": n // 40, "orders": n // 4, "lineitem": n}


def _text(rng, n, lo, hi, width):
    """Random letters of ``lo`` to ``hi`` bytes in a field of ``width``
    (dbgen's lengths, not its grammar)."""
    lens = rng.integers(lo, hi + 1, size=n).astype(np.int32)
    data = rng.integers(ord("a"), ord("z") + 1, size=(n, width),
                        dtype=np.uint8)
    data *= np.arange(width, dtype=np.int32)[None, :] < lens[:, None]
    return data, lens


def _tagged(prefix, number, digits, width):
    """``prefix`` followed by ``number`` zero-padded to ``digits``, in a
    field of ``width``: Customer#000000001, Clerk#000000951."""
    n = len(number)
    data = np.zeros((n, width), np.uint8)
    data[:, :len(prefix)] = np.frombuffer(prefix, np.uint8)
    for d in range(digits):
        data[:, len(prefix) + d] = \
            (number // 10 ** (digits - 1 - d)) % 10 + ord("0")
    return data, np.full(n, len(prefix) + digits, np.int32)


def _phone(rng, nationkey):
    """dbgen's phone: country code nationkey + 10, then 3-3-4 digits."""
    n = len(nationkey)
    data = np.full((n, 15), ord("-"), np.uint8)
    parts = ((0, 2, nationkey + 10),
             (3, 3, rng.integers(100, 1000, size=n)),
             (7, 3, rng.integers(100, 1000, size=n)),
             (11, 4, rng.integers(1000, 10000, size=n)))
    for at, digits, number in parts:
        for d in range(digits):
            data[:, at + d] = \
                (number // 10 ** (digits - 1 - d)) % 10 + ord("0")
    return data, np.full(n, 15, np.int32)


def generate(seed, cfg, rehearse=False):
    size = sizes(cfg, rehearse)
    n_cust, n_ord, n = size["customer"], size["orders"], size["lineitem"]
    sf = float(cfg["scale_factor"])
    rng = np.random.default_rng([int(seed), 3])

    # ---- customer (1.4.1; 4.2.3) ----------------------------------------
    custkey = np.arange(1, n_cust + 1)
    nationkey = rng.integers(0, 25, size=n_cust)
    customer = {
        "c_custkey": custkey.astype(np.int32),
        "c_name": _tagged(b"Customer#", custkey, 9, 25),
        "c_address": _text(rng, n_cust, 10, 40, 40),
        "c_nationkey": nationkey.astype(np.int32),
        "c_phone": _phone(rng, nationkey),
        "c_acctbal": (rng.integers(-99999, 1000000, size=n_cust) / 100.0)
        .astype(np.float32),
        "c_mktsegment": _choice_strings(rng, n_cust, SEGMENTS, 10),
        "c_comment": _text(rng, n_cust, 29, 116, 117),
    }

    # ---- orders: 1..7 lines each, n lines in all -------------------------
    per = rng.integers(1, 8, size=n_ord)
    diff = int(per.sum()) - n
    room = np.flatnonzero(per > 1 if diff > 0 else per < 7)
    per[rng.choice(room, size=abs(diff), replace=False)] -= np.sign(diff)
    first = np.cumsum(per) - per
    order_of = np.repeat(np.arange(n_ord), per)
    i = np.arange(n_ord)
    # dbgen's sparse order keys: 8 of every 32 values are used
    okey = (((i >> 3) << 5) | (i & 7)) + 1
    odate = rng.integers(D_1992_01_01, D_1998_08_02 + 1, size=n_ord)
    # a third of the customers have no orders: no key a multiple of 3
    o_cust = rng.integers(1, n_cust + 1, size=n_ord)
    bad = o_cust % 3 == 0
    o_cust[bad] += np.where(o_cust[bad] < n_cust, 1, -1)

    # ---- lineitem, by tpch_lineitem's rules ------------------------------
    linenumber = (np.arange(n) - first[order_of] + 1).astype(np.int32)
    n_part = max(int(sf * 200000), 1)
    n_supp = max(int(sf * 10000), 4)
    partkey = rng.integers(1, n_part + 1, size=n)
    supp_i = rng.integers(0, 4, size=n)
    suppkey = (partkey + supp_i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1
    quantity = rng.integers(1, 51, size=n)
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    extendedprice = (quantity * retail_cents) / 100.0
    discount = rng.integers(0, 11, size=n) / 100.0
    tax = rng.integers(0, 9, size=n) / 100.0
    shipdate = odate[order_of] + rng.integers(1, 122, size=n)
    commitdate = odate[order_of] + rng.integers(30, 91, size=n)
    receiptdate = shipdate + rng.integers(1, 31, size=n)
    returned = receiptdate <= D_1995_06_17
    returnflag = np.where(returned,
                          np.where(rng.integers(0, 2, size=n) == 0,
                                   ord("R"), ord("A")),
                          ord("N")).astype(np.uint8)
    open_line = shipdate > D_1995_06_17
    linestatus = np.where(open_line, ord("O"), ord("F")).astype(np.uint8)
    one = np.ones(n, np.int32)
    lineitem = {
        "l_orderkey": okey[order_of].astype(np.int32),
        "l_partkey": partkey.astype(np.int32),
        "l_suppkey": suppkey.astype(np.int32),
        "l_linenumber": linenumber,
        "l_quantity": quantity.astype(np.float32),
        "l_extendedprice": extendedprice.astype(np.float32),
        "l_discount": discount.astype(np.float32),
        "l_tax": tax.astype(np.float32),
        "l_returnflag": (returnflag.reshape(n, 1), one),
        "l_linestatus": (linestatus.reshape(n, 1), one),
        "l_shipdate": shipdate.astype(np.int32),
        "l_commitdate": commitdate.astype(np.int32),
        "l_receiptdate": receiptdate.astype(np.int32),
        "l_shipinstruct": _choice_strings(rng, n, SHIPINSTRUCT, 25),
        "l_shipmode": _choice_strings(rng, n, SHIPMODE, 10),
        "l_comment": _text(rng, n, 10, 43, 44),
    }

    # ---- the rest of orders, from its lines ------------------------------
    total = np.bincount(order_of, minlength=n_ord, weights=extendedprice
                        * (1 + tax) * (1 - discount))
    n_open = np.bincount(order_of, weights=open_line, minlength=n_ord)
    status = np.where(n_open == per, ord("O"),
                      np.where(n_open == 0, ord("F"), ord("P")))
    orders = {
        "o_orderkey": okey.astype(np.int32),
        "o_custkey": o_cust.astype(np.int32),
        "o_orderstatus": (status.astype(np.uint8).reshape(n_ord, 1),
                          np.ones(n_ord, np.int32)),
        "o_totalprice": total.astype(np.float32),
        "o_orderdate": odate.astype(np.int32),
        "o_orderpriority": _choice_strings(rng, n_ord, PRIORITIES, 15),
        "o_clerk": _tagged(b"Clerk#", rng.integers(
            1, max(int(sf * 1000), 1) + 1, size=n_ord), 9, 15),
        "o_shippriority": np.zeros(n_ord, np.int32),
        "o_comment": _text(rng, n_ord, 19, 78, 79),
    }
    return {"n": size, "tables": {"customer": customer, "orders": orders,
                                  "lineitem": lineitem}}


def ingest(ctx, data, cfg, workdir):
    paths, device_bytes, stored = {}, 0, 0
    for name in TABLES:
        paths[name] = os.path.join(workdir, name)
        device_bytes += storeio.write_input(
            ctx, paths[name], data["tables"][name], data["n"][name])
        stored += storeio.stored_bytes(paths[name])
    # ``rows``: the input rows of one query, all three tables
    return {"tables": paths, "rows": sum(data["n"].values()),
            "device_bytes": device_bytes, "stored_bytes": stored}
