"""A store knows which columns are a key.

``to_store(path, unique=[...])`` verifies the declaration over the rows it
writes — the check ``kernels.hash_join(right_unique=True)`` makes of its
right side in every run: the 64-bit key hashes sorted, adjacent pairs
compared — and refuses the write where it does not hold.  ``meta.json``
carries it, ``Catalog.register_store`` reads it, ``register_columns``
takes and verifies the same argument, the catalog's fingerprint includes
it; an append to a keyed store is refused; a store without the field
reads as before and has no key."""

import json
import os

import numpy as np
import pytest

from dryad_tpu import sql
from dryad_tpu.api.dataset import Context
from dryad_tpu.io.store import (StoreKeyError, append_store, build_meta,
                                read_store, store_meta)


def _dim(n=600, seed=2):
    rng = np.random.default_rng(seed)
    return {"d_k": rng.permutation(n).astype(np.int32) * 3 + 1,
            "d_g": (np.arange(n) % 7).astype(np.int32),
            "d_name": [b"name%d" % (i % 11) for i in range(n)]}


def test_a_verified_key_is_in_the_manifest_and_the_catalog(devices8,
                                                            tmp_path):
    path = str(tmp_path / "dim")
    Context().from_columns(_dim()).to_store(path, unique=["d_k"])
    meta = store_meta(path)
    assert meta["unique"] == ["d_k"]
    with open(os.path.join(path, "meta.json")) as f:
        assert json.load(f)["unique"] == ["d_k"]
    cat = sql.Catalog().register_store("dim", path)
    assert cat.get("dim").unique == ("d_k",)
    assert cat.get("dim").meta()["unique"] == ["d_k"]
    # round trip of the catalog's own serialization
    again = sql.Catalog.from_json(cat.to_json())
    assert again.get("dim").unique == ("d_k",)
    assert again.fingerprint() == cat.fingerprint()


def test_a_store_without_the_field_reads_as_before(devices8, tmp_path):
    ctx = Context()
    keyed, plain = str(tmp_path / "keyed"), str(tmp_path / "plain")
    ctx.from_columns(_dim()).to_store(keyed, unique=["d_k"])
    ctx.from_columns(_dim()).to_store(plain)
    assert "unique" not in store_meta(plain)
    assert "unique" not in build_meta({"a": {"kind": "dense",
                                             "dtype": "int32",
                                             "shape": []}}, [1], ["0"])
    a = sql.Catalog().register_store("dim", keyed)
    b = sql.Catalog().register_store("dim", plain)
    assert b.get("dim").unique is None and "unique" not in b.get("dim").meta()
    # the same rows, whichever way they were written
    ka, kb = ctx.from_store(keyed).collect(), ctx.from_store(plain).collect()
    assert sorted(ka["d_k"].tolist()) == sorted(kb["d_k"].tolist())
    # the key is part of what the catalog's fingerprint says
    b.get("dim").path = a.get("dim").path
    assert a.fingerprint() != b.fingerprint()


@pytest.mark.parametrize("keys,dup_col", [(["d_k"], "d_k"),
                                          (["d_name"], "d_name")],
                         ids=["int-key", "string-key"])
def test_a_duplicate_refuses_the_write(devices8, tmp_path, keys, dup_col):
    t = _dim()
    if dup_col == "d_k":
        t["d_k"][-1] = t["d_k"][0]       # on another partition of eight
    path = str(tmp_path / "dim")
    with pytest.raises(StoreKeyError, match="repeat"):
        Context().from_columns(t).to_store(path, unique=keys)
    assert not os.path.exists(path)      # refused before a byte is written


def test_a_key_of_two_columns(devices8, tmp_path):
    t = _dim()
    t["d_k"] = (np.arange(len(t["d_k"])) // 7).astype(np.int32)
    path = str(tmp_path / "dim")
    with pytest.raises(StoreKeyError):
        Context().from_columns(t).to_store(path, unique=["d_k"])
    Context().from_columns(t).to_store(path, unique=["d_k", "d_g"])
    assert store_meta(path)["unique"] == ["d_k", "d_g"]


def test_a_key_that_names_no_column(devices8, tmp_path):
    with pytest.raises(StoreKeyError, match="no column"):
        Context().from_columns(_dim()).to_store(str(tmp_path / "d"),
                                                unique=["nope"])


def test_padding_rows_do_not_count_as_duplicates(devices8, tmp_path):
    """600 rows over eight partitions leave capacity unused: the zeros
    there are no rows."""
    t = _dim()
    t["d_k"][0] = 0
    Context().from_columns(t).to_store(str(tmp_path / "d"), unique=["d_k"])


def test_an_append_to_a_keyed_store_is_refused(devices8, tmp_path):
    ctx = Context()
    path = str(tmp_path / "dim")
    ctx.from_columns(_dim()).to_store(path, unique=["d_k"])
    before = store_meta(path)
    more = read_store(path, ctx.mesh)
    with pytest.raises(StoreKeyError, match="append"):
        append_store(path, more)
    assert store_meta(path) == before
    plain = str(tmp_path / "plain")
    ctx.from_columns(_dim()).to_store(plain)
    assert append_store(plain, more) == 1       # as before


def test_register_columns_takes_and_verifies_the_same_argument(devices8):
    t = _dim()
    cat = sql.Catalog().register_columns("dim", t, unique=["d_k"])
    assert cat.get("dim").unique == ("d_k",)
    plain = sql.Catalog().register_columns("dim", t)
    assert plain.get("dim").unique is None
    assert plain.fingerprint() != cat.fingerprint()
    t["d_k"][5] = t["d_k"][6]
    with pytest.raises(StoreKeyError, match="repeat"):
        sql.Catalog().register_columns("dim", t, unique=["d_k"])
    with pytest.raises(StoreKeyError, match="no column"):
        sql.Catalog().register_columns("dim", _dim(), unique=["x"])
