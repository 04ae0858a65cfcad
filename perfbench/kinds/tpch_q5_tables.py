"""Kind ``tpch_q5_tables``: the six TPC-H tables Q5 joins (spec v3,
1.4.1 and 4.2.3): ``customer``, ``orders`` and ``lineitem`` exactly as
kind ``tpch_q3_tables`` makes them, and ``supplier``, ``nation`` and
``region`` beside them at their 7, 4 and 3 columns, made with numpy from
the seed.  Every ``l_suppkey`` is a supplier (dbgen's formula already
draws it over SF x 10,000 of them), ``s_nationkey`` and ``c_nationkey``
are uniform over the 25 nations, and ``nation`` and ``region`` are the
specification's fixed rows.  Where this differs from dbgen the
configuration file lists it under ``assumed``.

The five tables with a key are written with it declared
(``to_store(unique=)``), the dimensions first: a program that cannot
hold a key fails there, before the fact table is touched."""

from __future__ import annotations

import os

import numpy as np

from perfbench import storeio
from perfbench.kinds import tpch_q3_tables
from perfbench.kinds.ssb import NATIONS, _strings
from perfbench.kinds.tpch_q3_tables import _phone, _tagged, _text

# written in this order: the dimensions, then orders and the fact
TABLES = ("region", "nation", "supplier", "customer", "orders", "lineitem")
KEYS = {"region": "r_regionkey", "nation": "n_nationkey",
        "supplier": "s_suppkey", "customer": "c_custkey",
        "orders": "o_orderkey"}
# the specification's regions, in key order (4.2.3)
REGIONS = (b"AFRICA", b"AMERICA", b"ASIA", b"EUROPE", b"MIDDLE EAST")


def sizes(cfg, rehearse=False):
    """{table: rows}: the Q3 tables, one supplier for every SF x 10,000
    (the range ``l_suppkey`` is drawn over, at any ``rows``), 25 nations
    and 5 regions."""
    size = tpch_q3_tables.sizes(cfg, rehearse)
    size.update(supplier=max(int(float(cfg["scale_factor"]) * 10000), 4),
                nation=len(NATIONS), region=len(REGIONS))
    return size


def generate(seed, cfg, rehearse=False):
    data = tpch_q3_tables.generate(seed, cfg, rehearse)
    size = sizes(cfg, rehearse)
    n_supp = size["supplier"]
    rng = np.random.default_rng([int(seed), 4])

    suppkey = np.arange(1, n_supp + 1)
    s_nation = rng.integers(0, len(NATIONS), size=n_supp)
    supplier = {
        "s_suppkey": suppkey.astype(np.int32),
        "s_name": _tagged(b"Supplier#", suppkey, 9, 25),
        "s_address": _text(rng, n_supp, 10, 40, 40),
        "s_nationkey": s_nation.astype(np.int32),
        "s_phone": _phone(rng, s_nation),
        "s_acctbal": (rng.integers(-99999, 1000000, size=n_supp) / 100.0)
        .astype(np.float32),
        "s_comment": _text(rng, n_supp, 25, 100, 101),
    }
    n_nat = len(NATIONS)
    nation = {
        "n_nationkey": np.arange(n_nat, dtype=np.int32),
        "n_name": _strings([nt for nt, _ in NATIONS], np.arange(n_nat), 25),
        "n_regionkey": np.asarray([REGIONS.index(rg) for _, rg in NATIONS],
                                  np.int32),
        "n_comment": _text(rng, n_nat, 31, 114, 152),
    }
    n_reg = len(REGIONS)
    region = {
        "r_regionkey": np.arange(n_reg, dtype=np.int32),
        "r_name": _strings(REGIONS, np.arange(n_reg), 25),
        "r_comment": _text(rng, n_reg, 31, 115, 152),
    }
    data["tables"].update(supplier=supplier, nation=nation, region=region)
    data["n"] = size
    return data


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
    # ``rows``: the input rows of one query, all six tables
    return {"tables": paths, "rows": sum(data["n"].values()),
            "device_bytes": device_bytes, "stored_bytes": stored}
