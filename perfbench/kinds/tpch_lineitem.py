"""Kind ``tpch_lineitem``: TPC-H's ``lineitem`` (spec v3, 1.4.1 and
4.2.3) at its 16 columns and dbgen's value domains, made with numpy from
the seed.  Where this differs from dbgen the configuration file lists it
under ``assumed``.  Dates are int32 days since 1970-01-01, decimals
float32, CHAR/VARCHAR columns ``(bytes, lengths)`` pairs."""

from __future__ import annotations

import os

import numpy as np

from perfbench import storeio

D_1992_01_01 = 8035          # STARTDATE
D_1998_08_02 = 10440         # ENDDATE (1998-12-31) - 151 days: last o_orderdate
D_1995_06_17 = 9298          # CURRENTDATE

SHIPINSTRUCT = (b"DELIVER IN PERSON", b"COLLECT COD", b"NONE",
                b"TAKE BACK RETURN")
SHIPMODE = (b"REG AIR", b"AIR", b"RAIL", b"SHIP", b"TRUCK", b"MAIL", b"FOB")


def rows(cfg, rehearse=False) -> int:
    return int(cfg["rehearse"]["rows"] if rehearse else cfg["rows"])


def _choice_strings(rng, n, values, width):
    table = np.zeros((len(values), width), np.uint8)
    lens = np.zeros(len(values), np.int32)
    for i, v in enumerate(values):
        table[i, :len(v)] = np.frombuffer(v, np.uint8)
        lens[i] = len(v)
    pick = rng.integers(0, len(values), size=n)
    return table[pick], lens[pick]


def generate(seed, cfg, rehearse=False):
    n = rows(cfg, rehearse)
    sf = float(cfg["scale_factor"])
    rng = np.random.default_rng([int(seed), 2])
    # orders: 1..7 lines each (4.2.3), until n rows, the last order cut
    n_orders = n // 4 + n // 16 + 8
    per = rng.integers(1, 8, size=n_orders)
    while per.sum() < n:
        per = np.concatenate([per, rng.integers(1, 8, size=n_orders)])
    first = np.cumsum(per) - per
    order_of = np.repeat(np.arange(len(per)), per)[:n]
    linenumber = (np.arange(n) - first[order_of] + 1).astype(np.int32)
    # dbgen's sparse order keys: 8 of every 32 values are used
    orderkey = (((order_of >> 3) << 5) | (order_of & 7)) + 1
    orderdate = rng.integers(D_1992_01_01, D_1998_08_02 + 1,
                             size=len(per))[order_of]

    n_part = max(int(sf * 200000), 1)
    n_supp = max(int(sf * 10000), 4)
    partkey = rng.integers(1, n_part + 1, size=n)
    supp_i = rng.integers(0, 4, size=n)
    suppkey = (partkey + supp_i * (n_supp // 4 + (partkey - 1) // n_supp)) \
        % n_supp + 1
    quantity = rng.integers(1, 51, size=n)
    # p_retailprice in cents (4.2.3): 90000 + (partkey/10 mod 20001)
    #                                 + 100 * (partkey mod 1000)
    retail_cents = 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)
    extendedprice = (quantity * retail_cents) / 100.0
    discount = rng.integers(0, 11, size=n) / 100.0
    tax = rng.integers(0, 9, size=n) / 100.0
    shipdate = orderdate + rng.integers(1, 122, size=n)
    commitdate = orderdate + rng.integers(30, 91, size=n)
    receiptdate = shipdate + rng.integers(1, 31, size=n)
    returned = receiptdate <= D_1995_06_17
    returnflag = np.where(returned,
                          np.where(rng.integers(0, 2, size=n) == 0,
                                   ord("R"), ord("A")),
                          ord("N")).astype(np.uint8)
    linestatus = np.where(shipdate > D_1995_06_17, ord("O"),
                          ord("F")).astype(np.uint8)
    shipinstruct = _choice_strings(rng, n, SHIPINSTRUCT, 25)
    shipmode = _choice_strings(rng, n, SHIPMODE, 10)
    clen = rng.integers(10, 44, size=n).astype(np.int32)
    comment = rng.integers(ord("a"), ord("z") + 1, size=(n, 44),
                           dtype=np.uint8)
    comment *= np.arange(44, dtype=np.int32)[None, :] < clen[:, None]
    one = np.ones(n, np.int32)
    cols = {
        "l_orderkey": orderkey.astype(np.int32),
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
        "l_shipinstruct": shipinstruct,
        "l_shipmode": shipmode,
        "l_comment": (comment, clen),
    }
    return {"n": n, "tables": {"lineitem": cols}}


def ingest(ctx, data, cfg, workdir):
    n = data["n"]
    path = os.path.join(workdir, "lineitem")
    nbytes = storeio.write_input(ctx, path, data["tables"]["lineitem"], n)
    return {"tables": {"lineitem": path}, "rows": n,
            "device_bytes": nbytes,
            "stored_bytes": storeio.stored_bytes(path)}
