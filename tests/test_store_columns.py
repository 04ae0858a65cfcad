"""A read of some columns (io/store.read_parts(columns=...)): only the named
columns' leaves are allocated, fetched — from their byte ranges of the
partition file — verified by their own leaf digests, stacked and put on the
device.

* a subset read equals the whole read restricted to the names (dense,
  string and wide columns, several partitions, verbatim and re-blocked,
  a given capacity), in-core and streamed;
* the local ranged read asks for the kept leaves' byte ranges and nothing
  else, natively and through the numpy fallback; the remote targets ask for
  one range a kept leaf;
* a damaged kept column fails the read by partition and column, a damaged
  unread column does not — and does fail the whole read;
* a store that cannot be read in part (gzip, the ``fnv64`` form, a manifest
  without leaf digests) is read whole, verified whole and handed back at
  the named columns;
* ``columns=None`` and ``columns=`` every name make the parent's one native
  call (pinned);
* names are checked, the partitioning claim survives iff its keys are kept,
  and the ``store.read`` span says what was read of what is stored.

Every case is its own parametrised test so that each counts."""

import json
import os
import shutil
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_s3 import _FakeS3, s3env  # noqa: E402,F401
from webhdfs_fake import FakeWebHdfs  # noqa: E402

from dryad_tpu import Context, make_mesh, native  # noqa: E402
from dryad_tpu.data.columnar import Batch, StringColumn  # noqa: E402
from dryad_tpu.exec import ooc  # noqa: E402
from dryad_tpu.exec.data import PData, put_batch  # noqa: E402
from dryad_tpu.io import store  # noqa: E402
from dryad_tpu.io.s3 import S3Client  # noqa: E402
from dryad_tpu.io.store import StoreIntegrityError  # noqa: E402
from dryad_tpu.io.webhdfs import WebHdfsClient  # noqa: E402
from dryad_tpu.obs import trace  # noqa: E402
from dryad_tpu.utils.config import JobConfig  # noqa: E402

BLOCK = 64
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures", "store_fnv64")
COUNTS = [150, 90]
# file order: a, b, key data, key lengths, wide
ROW_BYTES = {"a": 4, "b": 4, "key": 10 + 4, "wide": 28}
SUBSETS = [["a"], ["key"], ["wide", "a"], ["b", "key"],
           ["key", "wide", "a"]]


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    monkeypatch.setattr(store, "CHECKSUM_BLOCK", BLOCK)
    yield
    trace.install(None)


def _ids(cols):
    return "+".join(cols)


def _pdata(counts=COUNTS, seed=9, cap=200):
    """Two int32 columns, a string column and a wide one over two
    partitions, rows past the count junk."""
    rng = np.random.default_rng(seed)
    n = len(counts)
    cols = {"wide": rng.standard_normal((n, cap, 7)).astype(np.float32),
            "a": rng.integers(-2**31, 2**31 - 1, (n, cap), dtype=np.int32),
            "key": StringColumn(
                rng.integers(0, 256, (n, cap, 10), dtype=np.uint8),
                rng.integers(0, 11, (n, cap)).astype(np.int32)),
            "b": rng.integers(-2**31, 2**31 - 1, (n, cap), dtype=np.int32)}
    mesh = make_mesh(jax.devices()[:n])
    return PData(put_batch(Batch(cols, np.asarray(counts, np.int32)), mesh),
                 n), mesh


@pytest.fixture()
def written(tmp_path):
    pd, mesh = _pdata()
    path = str(tmp_path / "s")
    store.write_store(path, pd, partitioning={"kind": "hash",
                                              "keys": ["a", "b"]})
    return path, mesh


def _host(pd):
    """A PData's columns as host arrays, a string column as a pair."""
    out = {}
    for k, v in pd.batch.columns.items():
        out[k] = ((np.asarray(v.data), np.asarray(v.lengths))
                  if isinstance(v, StringColumn) else np.asarray(v))
    return out, np.asarray(pd.counts).tolist()


def _arrays(col):
    """A column's arrays: one, or a string column's two."""
    return (col,) if isinstance(col, np.ndarray) else tuple(col)


def _assert_same_columns(got, whole, cols):
    got_cols, got_counts = _host(got)
    whole_cols, whole_counts = _host(whole)
    # the manifest's column order, however the caller listed the names
    assert list(got_cols) == [k for k in whole_cols if k in cols]
    assert got_counts == whole_counts
    for k in got_cols:
        for g, w in zip(_arrays(got_cols[k]), _arrays(whole_cols[k])):
            assert g.dtype == w.dtype and np.array_equal(g, w), k


def _flip(path, p, offset):
    with open(store._part_path(path, p), "r+b") as f:
        f.seek(offset)
        byte = f.read(1)
        f.seek(-1, os.SEEK_CUR)
        f.write(bytes([byte[0] ^ 1]))


def _spans(events, name):
    return [e["attrs"] for e in events
            if e.get("event") == "span" and e["name"] == name]


# -- (a) a subset read is the whole read at the names --------------------------


@pytest.mark.parametrize("ndev", [2, 1, 4],
                         ids=["verbatim", "reblock-1", "reblock-4"])
@pytest.mark.parametrize("cols", SUBSETS, ids=_ids)
def test_a_subset_read_is_the_whole_read_at_the_names(written, cols, ndev):
    path, _ = written
    mesh = make_mesh(jax.devices()[:ndev])
    _assert_same_columns(store.read_store(path, mesh, columns=cols),
                         store.read_store(path, mesh), cols)


@pytest.mark.parametrize("ndev", [2, 4], ids=["verbatim", "reblock-4"])
def test_a_subset_read_takes_a_capacity(written, ndev):
    path, _ = written
    mesh = make_mesh(jax.devices()[:ndev])
    got = store.read_store(path, mesh, capacity=256, columns=["key", "b"])
    assert got.capacity == 256
    _assert_same_columns(got, store.read_store(path, mesh, capacity=256),
                         ["key", "b"])


def test_read_parts_hands_back_the_named_columns_only(written):
    path, _ = written
    meta = store.store_meta(path)
    segs, cols = store.read_parts(path, meta, [1, 0], columns=["wide", "key"])
    w_segs, w_cols = store.read_parts(path, meta, [1, 0])
    for got, whole, n in zip(cols, w_cols, (COUNTS[1], COUNTS[0])):
        assert sorted(got) == ["key", "wide"]
        assert np.array_equal(got["wide"], whole["wide"])
        assert got["wide"].shape == (n, 7)
        assert np.array_equal(got["key"][0], whole["key"][0])
        assert np.array_equal(got["key"][1], whole["key"][1])
    # the arrays that were read: key's bytes, key's lengths, wide — no others
    assert [[s.nbytes for s in part] for part in segs] == [
        [10 * n, 4 * n, 28 * n] for n in (COUNTS[1], COUNTS[0])]
    assert [len(part) for part in w_segs] == [5, 5]


@pytest.mark.parametrize("cols", [["a"], ["key", "wide"]], ids=_ids)
def test_a_streamed_subset_is_the_whole_stream_at_the_names(written, cols):
    path, _ = written
    got = ooc.ChunkSource.from_store(path, 64, columns=cols)
    whole = ooc.ChunkSource.from_store(path, 64)
    assert list(got.schema) == [k for k in whole.schema if k in cols]
    assert got.fingerprint != whole.fingerprint
    assert got.fingerprint == ooc.ChunkSource.from_store(
        path, 64, columns=list(reversed(cols))).fingerprint
    # naming every column is the whole store: one source, one fingerprint
    assert ooc.ChunkSource.from_store(
        path, 64, columns=list(whole.schema)).fingerprint == whole.fingerprint
    chunks = list(zip(got, whole))
    assert len(chunks) == 3 + 2                    # 150 and 90 rows by 64
    for g, w in chunks:
        assert g.n == w.n and sorted(g.cols) == sorted(cols)
        for k in cols:
            for x, y in zip(_arrays(g.cols[k]), _arrays(w.cols[k])):
                assert np.array_equal(x, y)


def test_from_store_streams_the_named_columns_past_the_threshold(written):
    path, _ = written
    ctx = Context(config=JobConfig(ooc_auto_stream_rows=1))
    got = ctx.from_store(path, columns=["b", "key"]).collect()
    whole = ctx.from_store(path).collect()
    assert sorted(got) == ["b", "key"] and len(got["b"]) == sum(COUNTS)
    assert np.array_equal(got["b"], whole["b"])
    assert list(got["key"]) == list(whole["key"])


# -- (b) which bytes are asked for ---------------------------------------------


def _record_read_files(monkeypatch):
    calls = []
    orig = native.read_files

    def read_files(*args, **kw):
        calls.append((args, kw))
        return orig(*args, **kw)
    monkeypatch.setattr(native, "read_files", read_files)
    return calls


def _kept_ranges(meta, p, cols):
    return [(leaf.offset, leaf.nbytes)
            for leaf in store.part_layout(meta["schema"], meta["counts"][p])
            if leaf.column in cols]


@pytest.mark.parametrize("library", ["native", "numpy"])
@pytest.mark.parametrize("cols", SUBSETS, ids=_ids)
def test_the_local_read_asks_for_the_kept_ranges_only(written, cols, library,
                                                      monkeypatch):
    path, mesh = written
    whole = store.read_store(path, mesh)
    if library == "numpy":
        monkeypatch.setattr(native, "_load", lambda: None)
    calls = _record_read_files(monkeypatch)
    got = store.read_store(path, mesh, columns=cols)
    meta = store.store_meta(path)
    ((args, kw),) = calls                       # ONE call for all partitions
    paths, segments = args
    assert paths == [store._part_path(path, p) for p in (0, 1)]
    assert kw == {"offsets": [[off for off, _ in _kept_ranges(meta, p, cols)]
                              for p in (0, 1)]}
    assert [[s.nbytes for s in segs] for segs in segments] == [
        [nb for _, nb in _kept_ranges(meta, p, cols)] for p in (0, 1)]
    asked = sum(s.nbytes for segs in segments for s in segs)
    assert asked == sum(ROW_BYTES[k] for k in cols) * sum(COUNTS) \
        < sum(meta["bytes"])
    _assert_same_columns(got, whole, cols)


def test_the_ranged_native_read_refuses_a_file_that_ends_in_a_range(tmp_path):
    p = str(tmp_path / "f")
    with open(p, "wb") as f:
        f.write(bytes(range(100)))
    a, b = np.empty(10, np.uint8), np.empty(4, np.uint8)
    native.read_files([p], [[a, b]], offsets=[[5, 90]])
    assert a.tolist() == list(range(5, 15)) and b.tolist() == [90, 91, 92, 93]
    with pytest.raises(IOError):
        native.read_files([p], [[a, b]], offsets=[[5, 98]])
    with pytest.raises(ValueError, match="plain read only"):
        native.read_files([p], [[a]], compress=True, offsets=[[0]])


def test_s3_reads_one_range_a_kept_leaf(s3env, monkeypatch):  # noqa: F811
    pd, mesh = _pdata()
    path = "s3://bkt/cols/t"
    store.write_store(path, pd)
    meta = store.store_meta(path)
    whole = store.read_store(path, mesh)
    asked = []
    orig = S3Client.get_object

    def get_object(self, bucket, key, rng=None):
        if key.endswith(".bin"):
            asked.append((int(key[-9:-4]), rng))
        return orig(self, bucket, key, rng)
    monkeypatch.setattr(S3Client, "get_object", get_object)
    got = store.read_store(path, mesh, columns=["key", "b"])
    assert asked == [(p, (off, off + nb - 1)) for p in (0, 1)
                     for off, nb in _kept_ranges(meta, p, ["key", "b"])]
    _assert_same_columns(got, whole, ["key", "b"])
    # a damaged kept leaf is refused there too
    (key,) = [k for k in _FakeS3.objects if k.endswith("part-00001.bin")]
    raw = bytearray(_FakeS3.objects[key])
    raw[4 * COUNTS[1] + 2] ^= 1                         # inside "b"
    _FakeS3.objects[key] = bytes(raw)
    store.read_store(path, mesh, columns=["key", "a"])
    with pytest.raises(StoreIntegrityError,
                       match="partition 1 of .*column 'b', leaf 1"):
        store.read_store(path, mesh, columns=["key", "b"])


@pytest.fixture()
def hdfs():
    s = FakeWebHdfs(block_size=4096)
    yield s
    s.close()


def test_webhdfs_reads_one_range_a_kept_leaf(hdfs, monkeypatch):
    pd, mesh = _pdata()
    path = hdfs.url + "/cols/t"
    store.write_store(path, pd)
    meta = store.store_meta(path)
    whole = store.read_store(path, mesh)
    asked = []
    orig = WebHdfsClient.open

    def open_(self, p, offset=0, length=None):
        if p.endswith(".bin"):
            asked.append((int(p[-9:-4]), offset, length))
        return orig(self, p, offset=offset, length=length)
    monkeypatch.setattr(WebHdfsClient, "open", open_)
    got = store.read_store(path, mesh, columns=["wide"])
    assert asked == [(p, off, nb) for p in (0, 1)
                     for off, nb in _kept_ranges(meta, p, ["wide"])]
    _assert_same_columns(got, whole, ["wide"])
    # and the ranged stream takes the kept leaves of its layout
    del asked[:]
    monkeypatch.setattr(ooc.ChunkSource, "RANGED_STREAM_MIN_BYTES", 0)
    chunks = list(ooc.ChunkSource.from_store(path, 100, columns=["a", "key"]))
    assert [c.n for c in chunks] == [100, 50, 90]
    assert all(sorted(c.cols) == ["a", "key"] for c in chunks)
    lay = {p: [leaf for leaf in store.part_layout(meta["schema"], COUNTS[p])
               if leaf.column in ("a", "key")] for p in (0, 1)}
    assert sorted(asked) == sorted(
        (p, leaf.offset + s * leaf.row_bytes, (e - s) * leaf.row_bytes)
        for p, s, e in [(0, 0, 100), (0, 100, 150), (1, 0, 90)]
        for leaf in lay[p])
    with pytest.raises(KeyError, match="no column 'nope'"):
        next(store.iter_part_chunks(path, meta, 0, 10, columns=["nope"]))


# -- (c) damage ----------------------------------------------------------------

# the first byte of each column's first leaf in partition 1 (90 rows)
OFFSET = {"a": 0, "b": 4 * 90, "key": 8 * 90, "wide": 22 * 90}
LEAF = {"a": 0, "b": 1, "key": 2, "wide": 4}


@pytest.mark.parametrize("damaged", sorted(OFFSET))
def test_damage_fails_the_reads_that_name_the_column(written, damaged):
    path, mesh = written
    _flip(path, 1, OFFSET[damaged] + 7)
    others = [k for k in OFFSET if k != damaged]
    store.read_store(path, mesh, columns=others)          # unread: no matter
    store.read_store(path, mesh, columns=others[:1])
    where = f"partition 1 of .*column '{damaged}', leaf {LEAF[damaged]}"
    with pytest.raises(StoreIntegrityError, match=where):
        store.read_store(path, mesh, columns=[damaged])
    with pytest.raises(StoreIntegrityError, match=where):
        store.read_store(path, mesh, columns=[damaged, others[0]])
    with pytest.raises(StoreIntegrityError, match=where):
        store.read_store(path, mesh)                      # the whole read
    # what verify=False has always meant
    store.read_store(path, mesh, verify=False, columns=[damaged])


def test_a_string_columns_lengths_are_verified_too(written):
    path, mesh = written
    _flip(path, 0, 8 * 150 + 10 * 150 + 5)                # key's length lane
    store.read_store(path, mesh, columns=["a", "wide"])
    with pytest.raises(StoreIntegrityError,
                       match="partition 0 of .*column 'key', leaf 3"):
        store.read_store(path, mesh, columns=["key"])


@pytest.mark.parametrize("change", ["truncated", "grown"])
def test_a_file_of_another_size_is_refused_whatever_is_named(written, change):
    path, mesh = written
    with open(store._part_path(path, 1), "r+b") as f:
        if change == "truncated":
            f.truncate(os.path.getsize(f.name) - 1)       # wide's last byte
        else:
            f.seek(0, os.SEEK_END)
            f.write(b"\0")
    with pytest.raises(StoreIntegrityError, match="partition 1 of .*file "
                       "truncated or tampered"):
        store.read_store(path, mesh, columns=["a"])


def test_leaf_digests_that_disagree_with_the_bytes_are_refused(written):
    path, mesh = written
    meta = store.store_meta(path)
    meta["leaf_checksums"][0][LEAF["wide"]] = "0" * 16
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f)
    store.read_store(path, mesh, columns=["a", "key"])
    with pytest.raises(StoreIntegrityError, match="column 'wide', leaf 4"):
        store.read_store(path, mesh, columns=["wide"])


# -- (d) stores that cannot be read in part ------------------------------------


def _gzip_store(tmp_path):
    pd, mesh = _pdata()
    path = str(tmp_path / "gz")
    store.write_store(path, pd, compression="gzip")
    return path, mesh, ["key", "b"], "a"


def _no_leaf_digests_store(tmp_path):
    pd, mesh = _pdata()
    path = str(tmp_path / "noleaf")
    store.write_store(path, pd)
    meta = store.store_meta(path)
    meta["leaf_checksums"] = None               # the cluster writer's manifest
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f)
    return path, mesh, ["key", "b"], "a"


def _fnv64_store(tmp_path):
    path = str(tmp_path / "old")
    shutil.copytree(FIXTURE, path)
    schema = store.store_meta(path)["schema"]
    assert len(schema) >= 2
    names = sorted(schema)
    return path, make_mesh(jax.devices()[:2]), names[1:], names[0]


@pytest.mark.parametrize("make", [_gzip_store, _no_leaf_digests_store,
                                  _fnv64_store],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_store_that_cannot_be_read_in_part_is_read_whole(tmp_path, make,
                                                           monkeypatch):
    path, mesh, cols, unread = make(tmp_path)
    meta = store.store_meta(path)
    whole = store.read_store(path, mesh)
    calls = _record_read_files(monkeypatch)
    events = []
    trace.install(events.append)
    got = store.read_store(path, mesh, columns=cols)
    trace.install(None)
    _assert_same_columns(got, whole, cols)
    # read whole: the parent's call, every leaf of every partition
    ((args, kw),) = calls
    assert kw == {"compress": meta.get("compression") == "gzip"}
    assert [len(segs) for segs in args[1]] == [
        len(store.part_layout(meta["schema"]))] * 2
    # verified whole: every stored byte digested, no leaf count
    stored = sum(meta["bytes"])
    (rd,), (fr,), (vf,) = (_spans(events, n) for n in (
        "store.read", "store.file_read", "store.verify"))
    assert fr["bytes"] == vf["bytes"] == rd["bytes"] == rd["bytes_stored"] \
        == stored
    assert "leaves" not in vf
    assert (rd["columns"], rd["columns_stored"]) == (len(cols),
                                                     len(meta["schema"]))
    # ... so damage in a column nobody named still fails the read
    layout = store.part_layout(meta["schema"], meta["counts"][0])
    (leaf,) = [x for x in layout if x.column == unread and not x.str_part]
    raw_path = store._part_path(path, 0)
    if meta.get("compression") == "gzip":
        import gzip
        with gzip.open(raw_path, "rb") as f:
            raw = bytearray(f.read())
        raw[leaf.offset] ^= 1
        with gzip.open(raw_path, "wb") as f:
            f.write(bytes(raw))
    else:
        _flip(path, 0, leaf.offset)
    with pytest.raises(StoreIntegrityError, match="partition 0 of"):
        store.read_store(path, mesh, columns=cols)
    # unverified, the plain stores are read in part after all
    if meta.get("compression") != "gzip":
        del calls[:]
        store.read_store(path, mesh, columns=cols, verify=False)
        assert "offsets" in calls[0][1]


def test_verify_checksums_refuses_a_subset_without_leaf_digests(tmp_path):
    path, _, _, _ = _no_leaf_digests_store(tmp_path)
    meta = store.store_meta(path)
    segs, _ = store._alloc_part_views(meta["schema"], COUNTS[0], [0])
    native.read_files([store._part_path(path, 0)], [segs], offsets=[[0]])
    with pytest.raises(ValueError, match="no leaf digests"):
        store.verify_checksums(path, meta, [segs], partitions=[0], leaves=[0])


# -- (e) the whole read is the parent's ----------------------------------------


@pytest.mark.parametrize("columns", [None, ["a", "b", "key", "wide"],
                                     ["wide", "key", "b", "a", "a"]],
                         ids=["none", "all", "all-reordered"])
def test_the_whole_read_makes_the_parents_one_native_call(written, columns,
                                                          monkeypatch):
    path, mesh = written
    calls = _record_read_files(monkeypatch)
    allocs = []
    orig_empty = np.empty

    def empty(shape, dtype=float, **kw):
        allocs.append((tuple(np.atleast_1d(shape).tolist()),
                       np.dtype(dtype).name))
        return orig_empty(shape, dtype, **kw)
    monkeypatch.setattr(store.np, "empty", empty)
    events = []
    trace.install(events.append)
    pd = store.read_store(path, mesh, columns=columns)
    trace.install(None)
    monkeypatch.setattr(store.np, "empty", orig_empty)
    # the call, its arguments and the allocations before it, as PR 32 left
    # them: literals, not a reading of the layout
    ((args, kw),) = calls
    assert kw == {"compress": False}
    assert args[0] == [os.path.join(path, "part-00000.bin"),
                       os.path.join(path, "part-00001.bin")]
    want = [[((n,), "int32"), ((n,), "int32"), ((n, 10), "uint8"),
             ((n,), "int32"), ((n, 7), "float32")] for n in COUNTS]
    assert [[(s.shape, s.dtype.name) for s in segs]
            for segs in args[1]] == want
    # (what follows the read allocates too: the digest's outputs, the stack)
    assert allocs[:10] == want[0] + want[1]
    assert all(len(shape) == 1 or shape[:2] == (2, 200)
               for shape, _ in allocs[10:])
    assert list(pd.batch.columns) == ["a", "b", "key", "wide"]
    (rd,), (vf,) = _spans(events, "store.read"), _spans(events,
                                                        "store.verify")
    assert rd["bytes"] == rd["bytes_stored"] == 50 * sum(COUNTS)
    assert (rd["columns"], rd["columns_stored"]) == (4, 4)
    assert "leaves" not in vf and vf["bytes"] == rd["bytes"]


# -- (f) names, the claim, the span --------------------------------------------


@pytest.mark.parametrize("read", ["read_parts", "read_store", "from_store",
                                  "ChunkSource.from_store"])
@pytest.mark.parametrize("columns,error,says", [
    (["a", "nope"], KeyError, "no column 'nope'"),
    (["zz", "a", "aa"], KeyError, "no column 'aa', 'zz'"),
    ([], ValueError, "a read of no column")], ids=["unknown", "two-unknown",
                                                   "empty"])
def test_names_are_checked_by_name(written, read, columns, error, says,
                                   monkeypatch):
    path, mesh = written
    meta = store.store_meta(path)
    calls = _record_read_files(monkeypatch)
    with pytest.raises(error, match=says):
        if read == "read_parts":
            store.read_parts(path, meta, [0], columns=columns)
        elif read == "read_store":
            store.read_store(path, mesh, columns=columns)
        elif read == "from_store":
            Context().from_store(path, columns=columns)
        else:
            ooc.ChunkSource.from_store(path, 64, columns=columns)
    assert not calls                              # before any byte is read


@pytest.mark.parametrize("columns,kept", [
    (None, True), (["a", "b"], True), (["b", "wide", "a"], True),
    (["a", "key"], False), (["b"], False), (["wide"], False)],
    ids=lambda v: _ids(v) if isinstance(v, list) else str(v))
def test_the_partitioning_claim_survives_iff_its_keys_are_kept(written,
                                                               columns, kept):
    path, mesh = written
    ds = Context(mesh=mesh).from_store(path, columns=columns)
    part = ds.node.partitioning
    assert (part.kind, part.keys) == (("hash", ("a", "b")) if kept
                                      else ("none", ()))
    # and never across another mesh size, as before
    other = Context(mesh=make_mesh(jax.devices()[:4]))
    assert other.from_store(path, columns=columns).node.partitioning.kind \
        == "none"


@pytest.mark.parametrize("cols", SUBSETS, ids=_ids)
def test_the_spans_say_what_was_read_of_what_is_stored(written, cols):
    path, mesh = written
    events = []
    trace.install(events.append)
    store.read_store(path, mesh, columns=cols)
    trace.install(None)
    (rd,), (fr,), (vf,), (st,) = (_spans(events, n) for n in (
        "store.read", "store.file_read", "store.verify", "store.stack"))
    read = sum(ROW_BYTES[k] for k in cols) * sum(COUNTS)
    assert rd["columns"] == len(cols) and rd["columns_stored"] == 4
    assert rd["bytes"] == fr["bytes"] == vf["bytes"] == read
    assert rd["bytes_stored"] == 50 * sum(COUNTS)
    assert rd["partitions"] == fr["files"] == 2
    n_leaves = sum(2 if k == "key" else 1 for k in cols)
    assert vf["leaves"] == 2 * n_leaves and vf["algo"] == "fnv64-blocks"
    # the stack pads the kept columns only ([2, 200] of capacity)
    assert st["bytes"] == sum(ROW_BYTES[k] for k in cols) * 2 * 200 + 2 * 4
