"""Kind ``ssb``: the Star Schema Benchmark's five tables (O'Neil, O'Neil,
Chen, Revilak, "The Star Schema Benchmark", revision 3, 2009, sections 2
and 3; ``ssb-dbgen``) at their published columns, made with numpy from
the seed: the fact table ``lineorder`` and the dimensions ``date``,
``part``, ``supplier`` and ``customer``, every foreign key of the fact
resolving in its dimension.  Measures are integers (prices in cents, by
dbgen's retail-price formula, as ``tpch_lineitem`` has it); dates are
``yyyymmdd`` integers, the key of ``date``; CHAR/VARCHAR columns
``(bytes, lengths)`` pairs.  Where this differs from ``ssb-dbgen`` the
configuration file lists it under ``assumed``.

Each dimension is written with its key declared (``to_store(unique=)``),
dimensions first: a program that cannot hold a key fails there, before
the fact table is touched."""

from __future__ import annotations

import os

import numpy as np

from perfbench import storeio
from perfbench.kinds.tpch_lineitem import (D_1992_01_01, D_1998_08_02,
                                           SHIPMODE, _choice_strings)
from perfbench.kinds.tpch_q3_tables import (PRIORITIES, SEGMENTS, _phone,
                                            _tagged, _text)

# written in this order: the dimensions, then the fact
TABLES = ("date", "part", "supplier", "customer", "lineorder")
KEYS = {"date": "d_datekey", "part": "p_partkey", "supplier": "s_suppkey",
        "customer": "c_custkey"}
DATE_ROWS = 2556             # the published count, from 1992-01-01

# TPC-H's 25 nations (4.2.3), each with its region
NATIONS = (
    (b"ALGERIA", b"AFRICA"), (b"ARGENTINA", b"AMERICA"),
    (b"BRAZIL", b"AMERICA"), (b"CANADA", b"AMERICA"),
    (b"EGYPT", b"MIDDLE EAST"), (b"ETHIOPIA", b"AFRICA"),
    (b"FRANCE", b"EUROPE"), (b"GERMANY", b"EUROPE"), (b"INDIA", b"ASIA"),
    (b"INDONESIA", b"ASIA"), (b"IRAN", b"MIDDLE EAST"),
    (b"IRAQ", b"MIDDLE EAST"), (b"JAPAN", b"ASIA"),
    (b"JORDAN", b"MIDDLE EAST"), (b"KENYA", b"AFRICA"),
    (b"MOROCCO", b"AFRICA"), (b"MOZAMBIQUE", b"AFRICA"),
    (b"PERU", b"AMERICA"), (b"CHINA", b"ASIA"), (b"ROMANIA", b"EUROPE"),
    (b"SAUDI ARABIA", b"MIDDLE EAST"), (b"VIETNAM", b"ASIA"),
    (b"RUSSIA", b"EUROPE"), (b"UNITED KINGDOM", b"EUROPE"),
    (b"UNITED STATES", b"AMERICA"))
_MONTHS = (b"January", b"February", b"March", b"April", b"May", b"June",
           b"July", b"August", b"September", b"October", b"November",
           b"December")
_DAYS = (b"Monday", b"Tuesday", b"Wednesday", b"Thursday", b"Friday",
         b"Saturday", b"Sunday")
_SEASONS = (b"Winter", b"Spring", b"Summer", b"Fall", b"Christmas")


def sizes(cfg, rehearse=False):
    """{table: rows}: ``lineorder`` as the file says, the dimensions in
    the paper's proportions at scale factor 2 (a part for 30 lines, a
    customer for 200, a supplier for 3,000), never fewer than a query's
    filters need to keep some; ``date`` is the calendar."""
    n = int(cfg["rehearse"]["rows"] if rehearse else cfg["rows"])
    return {"date": DATE_ROWS, "part": max(n // 30, 200),
            "supplier": max(n // 3000, 40), "customer": max(n // 200, 100),
            "lineorder": n}


def _strings(values, pick, width):
    """``values[pick]`` as a (bytes, lengths) pair in a field of
    ``width``."""
    table = np.zeros((len(values), width), np.uint8)
    lens = np.zeros(len(values), np.int32)
    for i, v in enumerate(values):
        table[i, :len(v)] = np.frombuffer(v, np.uint8)
        lens[i] = len(v)
    return table[pick], lens[pick]


def _ymd(days):
    """int days since 1970-01-01 -> (year, month, day of month, day of
    year, weekday with Monday 0), each an int array."""
    d = np.asarray(days).astype("datetime64[D]")
    y = d.astype("datetime64[Y]")
    m = d.astype("datetime64[M]")
    return (y.astype(int) + 1970, m.astype(int) % 12 + 1,
            (d - m).astype(int) + 1, (d - y).astype(int) + 1,
            (np.asarray(days) + 3) % 7)


def datekey(days):
    """The ``yyyymmdd`` integer of a day number."""
    year, month, dom, _, _ = _ymd(days)
    return (year * 10000 + month * 100 + dom).astype(np.int32)


def _calendar():
    """``date``: one row a day from 1992-01-01, 17 columns, computed."""
    days = D_1992_01_01 + np.arange(DATE_ROWS)
    year, month, dom, doy, wd = _ymd(days)
    n = DATE_ROWS
    date = np.zeros((n, 18), np.uint8)
    dlen = np.zeros(n, np.int32)
    ym = np.zeros((n, 7), np.uint8)
    for i in range(n):
        s = b"%s %d, %d" % (_MONTHS[month[i] - 1], dom[i], year[i])
        date[i, :len(s)] = np.frombuffer(s, np.uint8)
        dlen[i] = len(s)
        ym[i] = np.frombuffer(b"%s%d" % (_MONTHS[month[i] - 1][:3],
                                         year[i]), np.uint8)
    last_dom = (datekey(days + 1) % 100 == 1)
    season = np.where(month == 12, 4, (month % 12) // 3)
    return {
        "d_datekey": datekey(days),
        "d_date": (date, dlen),
        "d_dayofweek": _strings(_DAYS, wd, 9),
        "d_month": _strings(_MONTHS, month - 1, 9),
        "d_year": year.astype(np.int32),
        "d_yearmonthnum": (year * 100 + month).astype(np.int32),
        "d_yearmonth": (ym, np.full(n, 7, np.int32)),
        "d_daynuminweek": (wd + 1).astype(np.int32),
        "d_daynuminmonth": dom.astype(np.int32),
        "d_daynuminyear": doy.astype(np.int32),
        "d_monthnuminyear": month.astype(np.int32),
        "d_weeknuminyear": ((doy - 1) // 7 + 1).astype(np.int32),
        "d_sellingseason": _strings(_SEASONS, season, 12),
        "d_lastdayinweekfl": (wd == 6).astype(np.int32),
        "d_lastdayinmonthfl": last_dom.astype(np.int32),
        "d_holidayfl": ((month == 12) & (dom == 25)).astype(np.int32),
        "d_weekdayfl": (wd < 5).astype(np.int32),
    }


def _hierarchy(rng, n):
    """``p_mfgr`` MFGR#1-5, ``p_category`` = mfgr + 1-5, ``p_brand1`` =
    category + 1-40: each level a prefix of the next."""
    m = rng.integers(1, 6, size=n)
    c = rng.integers(1, 6, size=n)
    b = rng.integers(1, 41, size=n)
    mfgr = np.zeros((n, 6), np.uint8)
    mfgr[:, :5] = np.frombuffer(b"MFGR#", np.uint8)
    mfgr[:, 5] = m + ord("0")
    cat = np.zeros((n, 7), np.uint8)
    cat[:, :6] = mfgr
    cat[:, 6] = c + ord("0")
    brand = np.zeros((n, 9), np.uint8)
    brand[:, :7] = cat
    two = b >= 10
    brand[:, 7] = np.where(two, b // 10, b) + ord("0")
    brand[:, 8] = np.where(two, b % 10 + ord("0"), 0)
    return ((mfgr, np.full(n, 6, np.int32)), (cat, np.full(n, 7, np.int32)),
            (brand, (8 + two).astype(np.int32)))


def _places(rng, n):
    """(city, nation, region, nation index) of ``n`` rows: a nation of
    TPC-H's 25, its region, and the city — the nation's first 9 letters,
    padded with blanks, and a digit."""
    pick = rng.integers(0, len(NATIONS), size=n)
    nation = _strings([nt for nt, _ in NATIONS], pick, 15)
    region = _strings([rg for _, rg in NATIONS], pick, 12)
    city = np.full((n, 10), ord(" "), np.uint8)
    prefix = np.minimum(nation[1], 9)
    keep = np.arange(9)[None, :] < prefix[:, None]
    city[:, :9] = np.where(keep, nation[0][:, :9], ord(" "))
    city[:, 9] = rng.integers(0, 10, size=n) + ord("0")
    return (city, np.full(n, 10, np.int32)), nation, region, pick


def generate(seed, cfg, rehearse=False):
    size = sizes(cfg, rehearse)
    n, n_part = size["lineorder"], size["part"]
    n_supp, n_cust = size["supplier"], size["customer"]
    rng = np.random.default_rng([int(seed), 5])

    # ---- the dimensions ---------------------------------------------------
    partkey = np.arange(1, n_part + 1)
    mfgr, category, brand = _hierarchy(rng, n_part)
    part = {
        "p_partkey": partkey.astype(np.int32),
        "p_name": _text(rng, n_part, 10, 22, 22),
        "p_mfgr": mfgr, "p_category": category, "p_brand1": brand,
        "p_color": _text(rng, n_part, 3, 11, 11),
        "p_type": _text(rng, n_part, 10, 25, 25),
        "p_size": rng.integers(1, 51, size=n_part).astype(np.int32),
        "p_container": _text(rng, n_part, 6, 10, 10),
    }
    suppkey = np.arange(1, n_supp + 1)
    city, nation, region, pick = _places(rng, n_supp)
    supplier = {
        "s_suppkey": suppkey.astype(np.int32),
        "s_name": _tagged(b"Supplier#", suppkey, 9, 25),
        "s_address": _text(rng, n_supp, 10, 25, 25),
        "s_city": city, "s_nation": nation, "s_region": region,
        "s_phone": _phone(rng, pick),
    }
    custkey = np.arange(1, n_cust + 1)
    city, nation, region, pick = _places(rng, n_cust)
    customer = {
        "c_custkey": custkey.astype(np.int32),
        "c_name": _tagged(b"Customer#", custkey, 9, 25),
        "c_address": _text(rng, n_cust, 10, 25, 25),
        "c_city": city, "c_nation": nation, "c_region": region,
        "c_phone": _phone(rng, pick),
        "c_mktsegment": _choice_strings(rng, n_cust, SEGMENTS, 10),
    }

    # ---- lineorder: orders of 1..7 lines until n rows, the last cut ------
    n_orders = n // 4 + n // 16 + 8
    per = rng.integers(1, 8, size=n_orders)
    while per.sum() < n:
        per = np.concatenate([per, rng.integers(1, 8, size=n_orders)])
    first = np.cumsum(per) - per
    order_of = np.repeat(np.arange(len(per)), per)[:n]
    # dbgen's sparse order keys: 8 of every 32 values are used
    orderkey = (((order_of >> 3) << 5) | (order_of & 7)) + 1
    oday = rng.integers(D_1992_01_01, D_1998_08_02 + 1, size=len(per))
    o_cust = rng.integers(1, n_cust + 1, size=len(per))
    o_prio = rng.integers(0, len(PRIORITIES), size=len(per))
    lo_part = rng.integers(1, n_part + 1, size=n)
    quantity = rng.integers(1, 51, size=n)
    # p_retailprice in cents (TPC-H 4.2.3)
    retail = 90000 + (lo_part // 10) % 20001 + 100 * (lo_part % 1000)
    extended = quantity * retail
    discount = rng.integers(0, 11, size=n)
    tax = rng.integers(0, 9, size=n)
    revenue = extended * (100 - discount) // 100
    total = np.bincount(order_of, minlength=len(per),
                        weights=revenue * (100 + tax) // 100)
    lineorder = {
        "lo_orderkey": orderkey.astype(np.int32),
        "lo_linenumber": (np.arange(n) - first[order_of] + 1)
        .astype(np.int32),
        "lo_custkey": o_cust[order_of].astype(np.int32),
        "lo_partkey": lo_part.astype(np.int32),
        "lo_suppkey": rng.integers(1, n_supp + 1, size=n).astype(np.int32),
        "lo_orderdate": datekey(oday)[order_of],
        "lo_orderpriority": _strings(PRIORITIES, o_prio[order_of], 15),
        "lo_shippriority": (np.full((n, 1), ord("0"), np.uint8),
                            np.ones(n, np.int32)),
        "lo_quantity": quantity.astype(np.int32),
        "lo_extendedprice": extended.astype(np.int32),
        "lo_ordtotalprice": total[order_of].astype(np.int32),
        "lo_discount": discount.astype(np.int32),
        "lo_revenue": revenue.astype(np.int32),
        "lo_supplycost": (retail * 6 // 10).astype(np.int32),
        "lo_tax": tax.astype(np.int32),
        "lo_commitdate": datekey(oday[order_of]
                                 + rng.integers(30, 91, size=n)),
        "lo_shipmode": _choice_strings(rng, n, SHIPMODE, 10),
    }
    return {"n": size, "tables": {
        "date": _calendar(), "part": part, "supplier": supplier,
        "customer": customer, "lineorder": lineorder}}


def ingest(ctx, data, cfg, workdir):
    import jax
    paths, device_bytes, stored = {}, 0, 0
    for name in TABLES:
        paths[name] = os.path.join(workdir, name)
        cols, n = data["tables"][name], data["n"][name]
        pd = storeio.to_device(ctx, cols, n)
        device_bytes += int(sum(x.nbytes for x in jax.tree.leaves(pd.batch)))
        unique = {"unique": [KEYS[name]]} if name in KEYS else {}
        ctx.from_pdata(pd).to_store(paths[name], **unique)
        del pd
        stored += storeio.stored_bytes(paths[name])
    # ``rows``: the five tables' (a query's FROM list names four of them)
    return {"tables": paths, "rows": sum(data["n"].values()),
            "device_bytes": device_bytes, "stored_bytes": stored}
