"""The store's integrity digest (io/store.part_checksums over
native.digest_parts): the ``fnv64-blocks`` form — a partition's leaves in
file order, each cut into fixed blocks, every block 64-bit FNV-1a from the
basis, a leaf's digest FNV-1a over its block digests, a partition's over its
leaf digests — is a function of the bytes and the block size only, native
and numpy forms agree with a plain reading of that definition, every kind of
damage is refused with the partition's name, every writer's manifest
verifies, and a store of the first form (``fnv64``, one chain a partition)
still reads, verifies and is appended to as it was written.

Small arrays on the suite's virtual CPU devices, with the block made 64
bytes so that a column of a few KB is many blocks; every case is its own
parametrised test so that each counts."""

import json
import os
import shutil
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_s3 import _FakeS3, s3env  # noqa: E402,F401
from webhdfs_fake import FakeWebHdfs  # noqa: E402

from dryad_tpu import Context, make_mesh, native  # noqa: E402
from dryad_tpu.data.columnar import Batch, StringColumn  # noqa: E402
from dryad_tpu.exec.data import PData, put_batch  # noqa: E402
from dryad_tpu.io import store  # noqa: E402
from dryad_tpu.io.store import StoreIntegrityError  # noqa: E402
from dryad_tpu.obs import trace  # noqa: E402

BLOCK = 64
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "store_fnv64")


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(store, "CHECKSUM_BLOCK", BLOCK)
    yield
    trace.install(None)


# -- the definition, read plainly ---------------------------------------------


def _spec(leaves, block):
    """(partition digest, leaf digests) of one partition's leaves (bytes
    each) by the byte-at-a-time FNV-1a, straight from the definition."""
    def words(ds):
        return native._fnv_py(b"".join(d.to_bytes(8, "little") for d in ds))
    leaf = [words([native._fnv_py(b[i:i + block])
                   for i in range(0, len(b), block)]) for b in leaves]
    return words(leaf), leaf


SCHEMA = {"id": {"kind": "dense", "dtype": "int32", "shape": []},
          "key": {"kind": "str", "max_len": 10},
          "vec": {"kind": "dense", "dtype": "float32", "shape": [3]}}
ROWS = 101


def _leaves(rows=ROWS, seed=3):
    """One partition's leaves in file order: id, key data, key lengths, vec."""
    rng = np.random.default_rng(seed)
    return [rng.integers(-9, 9, rows).astype(np.int32),
            rng.integers(0, 256, (rows, 10), dtype=np.uint8),
            rng.integers(0, 11, rows).astype(np.int32),
            rng.standard_normal((rows, 3)).astype(np.float32)]


def _as_one_array_a_leaf(leaves):
    return leaves


def _as_odd_chunks(leaves):
    # cut anywhere: inside a block, on a block's edge, across two leaves
    flat = np.frombuffer(b"".join(a.tobytes() for a in leaves), np.uint8)
    cuts = [0, 7, 64, 65, 333, 404, 405, 1414, 1415, 2000, flat.size]
    return [flat[a:b].copy() for a, b in zip(cuts, cuts[1:])] \
        + [np.empty(0, np.uint8)]


def _as_one_blob(leaves):
    return [np.frombuffer(b"".join(a.tobytes() for a in leaves), np.uint8)]


def _through_fill_segments(leaves):
    # the remote adapters' read: one blob copied into the partition's arrays
    segs, _ = store._alloc_part_views(SCHEMA, ROWS)
    store.fill_segments(segs, store.segments_blob(leaves, None), "a test")
    return segs


@pytest.mark.parametrize("hand_over", [
    _as_one_array_a_leaf, _as_odd_chunks, _as_one_blob,
    _through_fill_segments], ids=lambda f: f.__name__.lstrip("_"))
def test_digest_is_of_the_bytes_not_of_how_they_arrive(hand_over):
    leaves = _leaves()
    want, want_leaves = _spec([a.tobytes() for a in leaves], BLOCK)
    sums, leaf_sums, ran = store.part_checksums(
        SCHEMA, [ROWS], [hand_over(leaves)])
    assert sums == ["%016x" % want]
    assert leaf_sums == [["%016x" % h for h in want_leaves]]
    assert ran["algo"] == "fnv64-blocks" and ran["threads"] >= 1
    assert ran["blocks"] == sum(-(-a.nbytes // BLOCK) for a in leaves)


@pytest.mark.parametrize("nbytes", [0, 1, BLOCK - 1, BLOCK, BLOCK + 1,
                                    5 * BLOCK + 17])
def test_native_numpy_and_definition_agree_around_the_block(nbytes):
    rng = np.random.default_rng(nbytes)
    # two partitions, so that leaves and partitions keep apart in the call
    parts = [[rng.integers(0, 256, nbytes, dtype=np.uint8),
              rng.integers(0, 256, 2 * BLOCK, dtype=np.uint8)],
             [rng.integers(0, 256, 3, dtype=np.uint8),
              rng.integers(0, 256, nbytes, dtype=np.uint8)]]
    sizes = [[a.nbytes for a in segs] for segs in parts]
    assert native.available()
    got = native.digest_parts(parts, sizes, BLOCK)
    assert got[:2] == native._digest_parts_np(parts, sizes, BLOCK)[:2]
    for p, segs in enumerate(parts):
        want, want_leaves = _spec([a.tobytes() for a in segs], BLOCK)
        assert (got[0][p], got[1][p]) == (want, want_leaves)
    assert got[2]["blocks"] == 2 * -(-nbytes // BLOCK) + 2 + 1


def test_many_blocks_on_many_threads_agree_with_one():
    # enough blocks that the native call starts every worker it may
    rng = np.random.default_rng(5)
    a = rng.integers(0, 256, 3_000_001, dtype=np.uint8)
    cut = [a[:1_234_567], a[1_234_567:]]
    sums, leaves, ran = native.digest_parts([cut], [[2_000_000, 1_000_001]],
                                            4096)
    assert ran["blocks"] == 489 + 245
    assert 1 <= ran["threads"] <= max(1, len(os.sched_getaffinity(0)))
    want, want_leaves = _spec([a[:2_000_000].tobytes(),
                               a[2_000_000:].tobytes()], 4096)
    assert (sums, leaves) == ([want], [want_leaves])
    # a few blocks start no thread (the streamed path's small chunks)
    assert native.digest_parts([[a[:8192]]], [[8192]], 4096)[2] \
        == {"blocks": 2, "threads": 1}


def test_bytes_that_are_not_the_leaves_are_refused():
    with pytest.raises(ValueError, match="partition 1"):
        native.digest_parts([[np.zeros(8, np.uint8)],
                             [np.zeros(7, np.uint8)]], [[8], [8]], BLOCK)


# -- damage -------------------------------------------------------------------

COUNTS = [150, 90]


def _pdata(counts=COUNTS, seed=9, cap=200):
    """Two int32 columns of one size (so that two leaves can change places),
    a string column and a wide one, rows past the count junk."""
    rng = np.random.default_rng(seed)
    n = len(counts)
    cols = {"a": rng.integers(-2**31, 2**31 - 1, (n, cap), dtype=np.int32),
            "b": rng.integers(-2**31, 2**31 - 1, (n, cap), dtype=np.int32),
            "key": StringColumn(
                rng.integers(0, 256, (n, cap, 10), dtype=np.uint8),
                np.full((n, cap), 10, np.int32)),
            "wide": rng.standard_normal((n, cap, 7)).astype(np.float32)}
    mesh = make_mesh(jax.devices()[:n])
    return PData(put_batch(Batch(cols, np.asarray(counts, np.int32)), mesh),
                 n), mesh


def _flip_a_bit_in_every_block(data, n):
    for off in range(0, len(data), BLOCK):
        bad = bytearray(data)
        bad[min(off + 5, len(data) - 1)] ^= 0x10
        yield bytes(bad)


def _swap_two_blocks(data, n):
    # blocks 1 and 2 of leaf "a" (4 n bytes: more than three blocks)
    yield (data[:BLOCK] + data[2 * BLOCK:3 * BLOCK] + data[BLOCK:2 * BLOCK]
           + data[3 * BLOCK:])


def _swap_two_leaves(data, n):
    # "a" and "b": the same size, one after the other
    yield data[4 * n:8 * n] + data[:4 * n] + data[8 * n:]


def _truncate_the_last_block(data, n):
    yield data[:-3]


def _grow_the_file(data, n):
    yield data + b"\0"


@pytest.mark.parametrize("damage", [
    _flip_a_bit_in_every_block, _swap_two_blocks, _swap_two_leaves,
    _truncate_the_last_block, _grow_the_file],
    ids=lambda f: f.__name__.lstrip("_"))
def test_damage_is_refused_with_the_partitions_name(tmp_path, damage):
    pd, mesh = _pdata()
    path = str(tmp_path / "s")
    store.write_store(path, pd)
    part = store._part_path(path, 1)
    with open(part, "rb") as f:
        good = f.read()
    store.read_store(path, mesh)                    # reads as written
    cases = 0
    for bad in damage(good, COUNTS[1]):
        assert bad != good
        with open(part, "wb") as f:
            f.write(bad)
        with pytest.raises(StoreIntegrityError, match="partition 1 of"):
            store.read_store(path, mesh)
        cases += 1
    assert cases >= 1
    with open(part, "wb") as f:
        f.write(good)
    store.read_store(path, mesh)
    # what verify=False has always meant
    with open(part, "wb") as f:
        f.write(bytes(len(good)))
    store.read_store(path, mesh, verify=False)


def test_a_damaged_leaf_is_named_by_its_column(tmp_path):
    pd, mesh = _pdata()
    path = str(tmp_path / "s")
    store.write_store(path, pd)
    part = store._part_path(path, 0)
    with open(part, "r+b") as f:
        f.seek(8 * COUNTS[0] + 3)                   # inside "key"'s bytes
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 1]))
    with pytest.raises(StoreIntegrityError, match="column 'key', leaf 2"):
        store.read_store(path, mesh)


def test_segments_shorter_than_the_manifest_says_are_refused(tmp_path):
    pd, mesh = _pdata()
    path = str(tmp_path / "s")
    store.write_store(path, pd)
    meta = store.store_meta(path)
    segs, _ = store._alloc_part_views(meta["schema"], COUNTS[1])
    native.read_files([store._part_path(path, 1)], [segs])
    assert store.verify_checksums(path, meta, [segs],
                                  partitions=[1])["algo"] == "fnv64-blocks"
    segs[-1] = segs[-1][:-1]                        # the last block short
    with pytest.raises(StoreIntegrityError, match="partition 1 of"):
        store.verify_checksums(path, meta, [segs], partitions=[1])


def test_a_manifest_whose_leaf_digests_disagree_is_refused(tmp_path):
    pd, mesh = _pdata()
    path = str(tmp_path / "s")
    store.write_store(path, pd)
    meta = store.store_meta(path)
    meta["leaf_checksums"][1][0] = "0" * 16
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f)
    with pytest.raises(StoreIntegrityError, match="partition 1 of"):
        store.read_store(path, mesh)


# -- spans --------------------------------------------------------------------


def _spans(events, name):
    return [e for e in events
            if e.get("event") == "span" and e["name"] == name]


def test_spans_say_which_form_ran(tmp_path):
    pd, mesh = _pdata()
    path = str(tmp_path / "s")
    events = []
    trace.install(events.append)
    store.write_store(path, pd)
    store.read_store(path, mesh)
    (cs,), (vf,) = _spans(events, "store.checksum"), \
        _spans(events, "store.verify")
    on_disk = sum(os.path.getsize(store._part_path(path, p))
                  for p in range(2))
    for sp in (cs, vf):
        a = sp["attrs"]
        assert a["algo"] == "fnv64-blocks" and a["bytes"] == on_disk
        assert a["blocks"] >= on_disk // BLOCK and a["threads"] >= 1


# -- a store of the first form ------------------------------------------------


def _old_store(tmp_path):
    path = str(tmp_path / "old")
    shutil.copytree(FIXTURE, path)
    return path


def test_a_store_the_parent_wrote_reads_verified(tmp_path):
    """tests/fixtures/store_fnv64 was written by ``write_store`` of the
    commit before the block form (format 3, ``fnv64``, one chain)."""
    path = _old_store(tmp_path)
    meta = store.store_meta(path)
    assert (meta["format_version"], meta["checksum_algo"]) == (3, "fnv64")
    assert "leaf_checksums" not in meta
    events = []
    trace.install(events.append)
    pd = store.read_store(path, make_mesh(jax.devices()[:2]))
    (vf,) = _spans(events, "store.verify")
    assert vf["attrs"]["algo"] == "fnv64" and vf["attrs"]["threads"] == 1
    assert vf["attrs"]["bytes"] == 814 + 506
    ids = np.asarray(pd.batch.columns["id"])
    assert ids[0, :37].tolist() == list(range(37))
    assert ids[1, :23].tolist() == list(range(40, 63))
    # the chain still guards it
    with open(store._part_path(path, 1), "r+b") as f:
        f.seek(500)
        f.write(b"\xff")
    with pytest.raises(StoreIntegrityError, match="partition 1 of"):
        store.read_store(path, make_mesh(jax.devices()[:2]))


def test_append_keeps_the_form_the_store_was_written_in(tmp_path):
    path = _old_store(tmp_path)
    old = store.store_meta(path)
    rng = np.random.default_rng(2)
    mesh = make_mesh(jax.devices()[:2])
    cols = {"id": np.arange(1000, 1080, dtype=np.int32).reshape(2, 40),
            "key": StringColumn(
                rng.integers(0, 256, (2, 40, 10), dtype=np.uint8),
                np.full((2, 40), 10, np.int32)),
            "qty": rng.standard_normal((2, 40)).astype(np.float32)}
    more = PData(put_batch(Batch(cols, np.asarray([40, 11], np.int32)),
                           mesh), 2)
    assert store.append_store(path, more) == 1
    meta = store.store_meta(path)
    assert (meta["format_version"], meta["checksum_algo"]) == (3, "fnv64")
    assert "leaf_checksums" not in meta and "checksum_block" not in meta
    assert meta["checksums"][:2] == old["checksums"]
    for p in (2, 3):                                # one chain a partition
        segs, _ = store._alloc_part_views(meta["schema"], meta["counts"][p])
        native.read_files([store._part_path(path, p)], [segs])
        assert meta["checksums"][p] \
            == "%016x" % native.checksum_segments(segs)
    pd = store.read_store(path, make_mesh(jax.devices()[:4]))
    assert np.asarray(pd.counts).tolist() == [37, 23, 40, 11]


def test_append_to_the_block_form_carries_leaf_digests(tmp_path):
    pd, mesh = _pdata()
    path = str(tmp_path / "s")
    store.write_store(path, pd)
    more, _ = _pdata(counts=[8, 199], seed=10)
    store.append_store(path, more)
    meta = store.store_meta(path)
    assert meta["format_version"] == 4
    assert meta["checksum_block"] == BLOCK
    assert len(meta["leaf_checksums"]) == 4
    assert all(len(leaves) == 5 for leaves in meta["leaf_checksums"])
    store.read_store(path, make_mesh(jax.devices()[:4]))


def test_a_store_keeps_its_block_size_when_the_default_moves(tmp_path,
                                                             monkeypatch):
    pd, mesh = _pdata()
    path = str(tmp_path / "s")
    store.write_store(path, pd)
    monkeypatch.setattr(store, "CHECKSUM_BLOCK", 4096)
    store.read_store(path, mesh)
    more, _ = _pdata(counts=[8, 199], seed=10)
    store.append_store(path, more)
    assert store.store_meta(path)["checksum_block"] == BLOCK
    store.read_store(path, make_mesh(jax.devices()[:4]))


# -- every writer's manifest verifies through read_store ----------------------


def _table(n=500):
    return {"v": np.arange(n, dtype=np.int32),
            "s": [f"row{i:04d}" for i in range(n)]}


def _assert_verifies(path, leaves=True):
    """The manifest is of the block form, and a read with verification on
    ran it over every byte."""
    meta = store.store_meta(path)
    assert meta["checksum_algo"] == "fnv64-blocks"
    assert meta["format_version"] == 4 and meta["checksum_block"] == BLOCK
    assert len(meta["checksums"]) == meta["npartitions"]
    if leaves:      # v: one leaf; s: data then lengths
        assert [len(x) for x in meta["leaf_checksums"]] \
            == [3] * meta["npartitions"]
    else:
        assert meta["leaf_checksums"] is None
    events = []
    ctx = Context()                 # a new Context takes the sink: it first
    trace.install(events.append)
    back = ctx.from_store(path).collect()
    trace.install(None)
    assert sorted(np.asarray(back["v"]).tolist()) == list(range(500))
    (vf,) = _spans(events, "store.verify")
    assert vf["attrs"]["algo"] == "fnv64-blocks"
    assert vf["attrs"]["bytes"] == sum(meta["bytes"])
    return meta


@pytest.mark.parametrize("compression", [None, "gzip"])
def test_write_store_verifies(tmp_path, compression):
    path = str(tmp_path / "s")
    Context().from_columns(_table()).to_store(path, compression=compression)
    _assert_verifies(path)


def test_append_store_verifies(tmp_path):
    path = str(tmp_path / "s")
    ctx = Context()
    ctx.from_columns(_table(300)).to_store(path)
    rest = {k: v[300:] for k, v in _table().items()}
    store.append_store(path, ctx.from_columns(rest)._materialize())
    _assert_verifies(path)


def test_write_chunks_to_store_verifies(tmp_path):
    src, path = str(tmp_path / "src"), str(tmp_path / "s")
    Context().from_columns(_table()).to_store(src)
    Context().read_store_stream(src, chunk_rows=64).to_store(path)
    meta = _assert_verifies(path)
    assert meta["npartitions"] > 1                  # a partition a chunk


def test_s3_writer_verifies(s3env):  # noqa: F811
    Context().from_columns(_table()).to_store("s3://bkt/digest/t1")
    _assert_verifies("s3://bkt/digest/t1")
    # and a damaged object is refused
    key = next(k for k in _FakeS3.objects if k.endswith("part-00000.bin"))
    blob = bytearray(_FakeS3.objects[key])
    blob[9] ^= 0x40
    _FakeS3.objects[key] = bytes(blob)
    with pytest.raises(StoreIntegrityError, match="partition 0 of"):
        Context().from_store("s3://bkt/digest/t1").collect()


@pytest.fixture()
def hdfs():
    s = FakeWebHdfs(block_size=4096)
    yield s
    s.close()


def test_webhdfs_writer_verifies(hdfs):
    Context().from_columns(_table()).to_store(hdfs.url + "/digest/t1")
    _assert_verifies(hdfs.url + "/digest/t1")


def test_webhdfs_chunk_writer_verifies(hdfs):
    Context().from_columns(_table()).to_store(hdfs.url + "/digest/src")
    (Context().read_store_stream(hdfs.url + "/digest/src", chunk_rows=64)
     .to_store(hdfs.url + "/digest/t2"))
    _assert_verifies(hdfs.url + "/digest/t2")


def test_a_manifest_without_leaf_digests_verifies(tmp_path):
    """The cluster writer's allgather carries one digest a partition: its
    manifest records no leaf digests, and readers verify by the partition
    digest alone."""
    path = str(tmp_path / "s")
    Context().from_columns(_table()).to_store(path)
    meta = store.store_meta(path)
    meta["leaf_checksums"] = None
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f)
    _assert_verifies(path, leaves=False)
    with open(store._part_path(path, 0), "r+b") as f:
        f.write(b"\x7f")
    with pytest.raises(StoreIntegrityError, match="partition 0 of"):
        Context().from_store(path).collect()


# -- the library is the one dryad_io.cpp describes -----------------------------

NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                          "native")


def _native_copy(tmp_path, without=None):
    """A copy of native/ (never the real library); ``without`` renames an
    entry point away, as in a library older than this module."""
    d = str(tmp_path / "native")
    os.makedirs(d)
    shutil.copy(os.path.join(NATIVE_DIR, "Makefile"), d)
    with open(os.path.join(NATIVE_DIR, "dryad_io.cpp")) as f:
        src = f.read()
    if without:
        assert without in src
        src = src.replace(without, without + "_of_another_time")
    with open(os.path.join(d, "dryad_io.cpp"), "w") as f:
        f.write(src)
    return d


def test_a_library_older_than_its_source_is_rebuilt(tmp_path):
    d = _native_copy(tmp_path, without="dryad_digest_parts")
    subprocess.run(["make", "-C", d, "-s"], check=True, capture_output=True)
    so = os.path.join(d, "libdryad_io.so")
    stale = os.path.getmtime(so) - 100
    os.utime(so, (stale, stale))
    # the source moves on; the library on disk is now the older one
    shutil.copy(os.path.join(NATIVE_DIR, "dryad_io.cpp"), d)
    lib = native._open_library(d)
    assert os.path.getmtime(so) != stale
    assert all(hasattr(lib, name) for name in native._SIGNATURES)
    # nothing to do the second time: the library stays as it is
    built = os.path.getmtime(so)
    t0 = time.perf_counter()
    native._open_library(d)
    assert os.path.getmtime(so) == built
    assert time.perf_counter() - t0 < 5.0


def test_a_library_that_lacks_an_entry_point_raises(tmp_path, monkeypatch):
    d = _native_copy(tmp_path, without="dryad_digest_parts")
    with pytest.raises(RuntimeError, match="lacks dryad_digest_parts"):
        native._open_library(d)
    # ... out of every call, not only the first: no caller is handed the
    # numpy form in silence
    monkeypatch.setattr(native, "_NATIVE_DIR", d)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_error", None)
    for _ in range(2):
        with pytest.raises(RuntimeError, match="lacks dryad_digest_parts"):
            native.digest_parts([[np.zeros(4, np.uint8)]], [[4]], BLOCK)
        with pytest.raises(RuntimeError, match="lacks dryad_digest_parts"):
            native.available()


def test_no_library_and_no_toolchain_is_the_numpy_form(tmp_path):
    d = str(tmp_path / "empty")
    os.makedirs(d)                  # no Makefile: make fails, no library
    assert native._open_library(d) is None
