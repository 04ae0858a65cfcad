"""The device -> host fetch of a PData that is about to be stored
(exec/data.fetch_partitions, behind io/store.write_store, append_store and
the remote writers): whatever it is handed, the store it writes is, file by
file and checksum by checksum, the one the plain path writes — a device
slice and a blocking ``np.asarray`` a column a partition, kept here as the
reference.

Small sizes on the suite's virtual CPU devices, with the chunk made small
enough that every column crosses in several chunks; every case is its own
parametrised test so that each counts."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dryad_tpu import make_mesh, native
from dryad_tpu.data.columnar import Batch, StringColumn
from dryad_tpu.exec import data as xdata
from dryad_tpu.exec.data import PData, fetch_partitions, put_batch
from dryad_tpu.io import store
from dryad_tpu.obs import trace

CAP = 1000


@pytest.fixture(autouse=True)
def _small_chunks(monkeypatch):
    # 4 KiB a chunk: 32 rows of the 90-byte column, 1024 of an int32 lane
    monkeypatch.setattr(xdata, "_FETCH_CHUNK_BYTES", 4096)
    yield
    trace.install(None)


def _host_batch(nparts, counts, seed=7):
    """String + dense + dense-with-trailing-shape columns, rows past a
    partition's count filled with junk that must never reach a file."""
    rng = np.random.default_rng(seed)
    cols = {
        "payload": StringColumn(
            rng.integers(0, 256, (nparts, CAP, 90), dtype=np.uint8),
            rng.integers(0, 91, (nparts, CAP), dtype=np.int32)),
        "key": StringColumn(
            rng.integers(0, 256, (nparts, CAP, 10), dtype=np.uint8),
            np.full((nparts, CAP), 10, np.int32)),
        "flag": StringColumn(
            rng.integers(65, 70, (nparts, CAP, 1), dtype=np.uint8),
            np.ones((nparts, CAP), np.int32)),
        "qty": rng.standard_normal((nparts, CAP)).astype(np.float32),
        "id": rng.integers(-2**31, 2**31 - 1, (nparts, CAP), dtype=np.int32),
        "vec": rng.integers(-9, 9, (nparts, CAP, 3), dtype=np.int32),
        "half": rng.standard_normal((nparts, CAP, 5)).astype(np.float16),
        "wide": rng.standard_normal((nparts, CAP, 600)).astype(np.float32),
    }
    return Batch(cols, np.asarray(counts, np.int32))


def _pdata(ndev, counts, nparts=None, **kw):
    nparts = nparts or ndev
    mesh = make_mesh(jax.devices()[:ndev])
    return PData(put_batch(_host_batch(nparts, counts, **kw), mesh), nparts)


def _plain_segments(batch, p, n):
    """The parent's fetch: per column ``np.asarray(v[p])[:n]``."""
    segs = []
    for k in sorted(batch.columns):
        v = batch.columns[k]
        if isinstance(v, StringColumn):
            segs.append(np.ascontiguousarray(np.asarray(v.data[p])[:n]))
            segs.append(np.ascontiguousarray(np.asarray(v.lengths[p])[:n]))
        else:
            segs.append(np.ascontiguousarray(np.asarray(v[p])[:n]))
    return segs


def _plain_write(path, pd, compression=None):
    """A store from the plain segments, through the same writer, checksum
    and manifest functions."""
    os.makedirs(path)
    counts = np.asarray(pd.counts).tolist()
    segments = [_plain_segments(pd.batch, p, n) for p, n in enumerate(counts)]
    native.write_files([store._part_path(path, p) for p in range(pd.nparts)],
                       segments, compress=(compression == "gzip"))
    schema = store.pdata_schema(pd)
    sums, leaves, _ = store.part_checksums(schema, counts, segments)
    meta = store.build_meta(schema, counts, sums, compression=compression,
                            capacity=pd.capacity, leaf_checksums=leaves)
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f, indent=1)
    return segments


def _assert_same_store(a, b):
    assert sorted(os.listdir(a)) == sorted(os.listdir(b))
    for name in sorted(os.listdir(a)):
        with open(os.path.join(a, name), "rb") as fa, \
                open(os.path.join(b, name), "rb") as fb:
            if name == "meta.json":     # a manifest is its content
                assert json.load(fa) == json.load(fb)
            else:
                assert fa.read() == fb.read(), name


# counts: full, empty, not a multiple of any chunk, one short of capacity
COUNTS4 = [CAP, 0, 37, CAP - 1]


@pytest.mark.parametrize("compression", [None, "gzip"])
@pytest.mark.parametrize("ndev,counts", [(1, [777]), (1, [CAP]), (1, [0]),
                                         (4, COUNTS4),
                                         (4, [1, 2, 3, 4])])
def test_written_store_equals_the_plain_path(tmp_path, ndev, counts,
                                             compression):
    pd = _pdata(ndev, counts)
    got, ref = str(tmp_path / "got"), str(tmp_path / "ref")
    assert store.write_store(got, pd, compression=compression) == sum(counts)
    _plain_write(ref, pd, compression)
    _assert_same_store(got, ref)
    # and the program reads back what was written, checksums verified
    back = store.read_store(got, make_mesh(jax.devices()[:ndev]))
    assert np.asarray(back.counts).tolist() == counts
    for p, n in enumerate(counts):
        for a, b in zip(_plain_segments(back.batch, p, n),
                        _plain_segments(pd.batch, p, n)):
            assert np.array_equal(a, b)


def test_two_partitions_a_device(tmp_path):
    """Eight partitions on four devices: a shard holds two."""
    counts = [CAP, 0, 37, CAP - 1, 5, 64, 33, 999]
    pd = _pdata(4, counts, nparts=8)
    got, ref = str(tmp_path / "got"), str(tmp_path / "ref")
    store.write_store(got, pd)
    _plain_write(ref, pd)
    _assert_same_store(got, ref)


def test_host_leaves_are_sliced_in_place(tmp_path):
    """A PData that never was on a device stores the same bytes."""
    host = PData(_host_batch(4, COUNTS4), 4)
    got, ref = str(tmp_path / "got"), str(tmp_path / "ref")
    store.write_store(got, host)
    _plain_write(ref, _pdata(4, COUNTS4))
    _assert_same_store(got, ref)


@pytest.mark.parametrize("compression", [None, "gzip"])
def test_append_store_equals_the_plain_path(tmp_path, compression):
    first, more = _pdata(4, [5, 6, 7, 8]), _pdata(4, COUNTS4, seed=11)
    path = str(tmp_path / "s")
    store.write_store(path, first, compression=compression)
    assert store.append_store(path, more) == 1
    meta = store.store_meta(path)
    # the empty partition is skipped, the others land behind the first four
    kept = [(p, n) for p, n in enumerate(COUNTS4) if n]
    assert meta["counts"] == [5, 6, 7, 8] + [n for _, n in kept]
    for i, (p, n) in enumerate(kept):
        segs = _plain_segments(more.batch, p, n)
        sums, leaves, _ = store.part_checksums(meta["schema"], [n], [segs])
        assert meta["checksums"][4 + i] == sums[0]
        assert meta["leaf_checksums"][4 + i] == leaves[0]
        ref = str(tmp_path / f"ref{i}.bin")
        native.write_files([ref], [segs], compress=(compression == "gzip"))
        with open(ref, "rb") as fa, \
                open(store._part_path(path, 4 + i), "rb") as fb:
            assert fa.read() == fb.read()


@pytest.mark.parametrize("ndev,counts", [(1, [777]), (4, COUNTS4)])
def test_pieces_are_the_valid_rows(ndev, counts):
    """``fetch_partitions`` itself: per partition its pieces, end to end,
    are each leaf's valid rows; what moved is under the valid bytes and
    one chunk a leaf a partition."""
    pd = _pdata(ndev, counts)
    leaves = [pd.batch.columns["payload"].data,
              pd.batch.columns["payload"].lengths,
              pd.batch.columns["vec"], pd.batch.columns["qty"]]
    got = list(fetch_partitions(leaves, np.asarray(pd.counts)))
    assert len(got) == ndev
    for p, (pieces, moved, chunks) in enumerate(got):
        n = counts[p]
        want = b"".join(np.asarray(x[p])[:n].tobytes() for x in leaves)
        assert b"".join(np.ascontiguousarray(a).tobytes()
                        for a in pieces) == want
        valid = len(want)
        slack = sum(min(xdata._FETCH_CHUNK_BYTES + xdata._FETCH_LINK_WIDTH
                        * x.dtype.itemsize, x[p].nbytes) for x in leaves)
        assert valid <= moved + (0 if n else 1) <= valid + slack
        assert (chunks == 0) == (n == 0)


def test_nothing_compiled_depends_on_a_count():
    """One program a column shape: another count compiles nothing."""
    def leaves(pd):
        return [pd.batch.columns["key"].data, pd.batch.columns["id"]]

    def compiled(pd):
        return [xdata._fetch_chunk_program(
            xdata._fetch_chunk_rows(CAP, x.dtype.itemsize
                                    * xdata._row_elems(x)),
            xdata._leaves_wide(x), xdata._partition_sharding(x))._cache_size()
            for x in leaves(pd)]

    pd = _pdata(4, COUNTS4)
    list(fetch_partitions(leaves(pd), np.asarray(pd.counts)))
    before = compiled(pd)
    assert all(n >= 1 for n in before)
    pd2 = _pdata(4, [1, 999, 500, 63], seed=3)
    list(fetch_partitions(leaves(pd2), np.asarray(pd2.counts)))
    assert compiled(pd2) == before


@pytest.mark.parametrize("ndev,counts", [(1, [777]), (4, COUNTS4)])
def test_fetch_spans_of_one_write(tmp_path, ndev, counts):
    pd = _pdata(ndev, counts)
    events = []
    trace.install(events.append)
    path = str(tmp_path / "out")
    store.write_store(path, pd)
    spans = [e for e in events if e.get("event") == "span"]
    (write,) = [s for s in spans if s["name"] == "store.write"]
    fetches = sorted((s for s in spans if s["name"] == "store.fetch"),
                     key=lambda s: s["t0"])
    assert [s["attrs"]["partition"] for s in fetches] == list(range(ndev))
    assert {s["parent"] for s in fetches} == {write["span"]}
    # one after the other: perfbench sums them, so none may overlap
    for a, b in zip(fetches, fetches[1:]):
        assert a["t0"] + a["dur_s"] <= b["t0"] + 1e-6
    on_disk = sum(os.path.getsize(store._part_path(path, p))
                  for p in range(ndev))
    assert sum(s["attrs"]["bytes"] for s in fetches) == on_disk \
        == write["attrs"]["bytes"]
    for s, n in zip(fetches, counts):
        a = s["attrs"]
        assert (a["chunks"] > 0) == (n > 0)
        assert a["bytes"] <= a["moved_bytes"] + (0 if n else 1)
        # 11 leaves, one chunk each past the count at most (the wide
        # rows' padding included)
        assert a["moved_bytes"] <= a["bytes"] + 11 * (4096 + 512 * 4)


def test_schema_needs_no_device_slice():
    """``pdata_schema`` reads a dense column's dtype from the array."""
    pd = _pdata(1, [3])
    schema = store.pdata_schema(pd)
    assert schema["half"] == {"kind": "dense", "dtype": "float16",
                              "shape": [5]}
    assert schema["qty"] == {"kind": "dense", "dtype": "float32",
                             "shape": []}
    assert schema["key"] == {"kind": "str", "max_len": 10}
    bf = PData(Batch({"b": jnp.zeros((1, 4, 2), jnp.bfloat16)},
                     jnp.asarray([4], jnp.int32)), 1)
    assert store.pdata_schema(bf)["b"]["dtype"] == "bfloat16"


@pytest.mark.parametrize("cap,row_bytes", [
    (400_000, 6), (400_000, 4), (60_000, 25), (2_556, 18), (1000, 4),
    (12_000_000, 15), (8 << 20, 90), (1, 4), (0, 4)])
def test_chunk_rows_are_a_power_of_two(cap, row_bytes):
    """A column shorter than one chunk is cut by a power of two as well
    (the TPU compiler took 138-164 s over the wide reshape of 400,000
    rows; its chunks overlap instead), under the chunk's bytes."""
    rows = xdata._fetch_chunk_rows(cap, row_bytes)
    assert rows & (rows - 1) == 0 and 1 <= rows <= max(cap, 1)
    assert rows * row_bytes <= max(xdata._FETCH_CHUNK_BYTES, row_bytes)
    assert 2 * rows > min(max(cap, 1), xdata._FETCH_CHUNK_BYTES // row_bytes)
