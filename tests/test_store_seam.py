"""The seam around the store's format (io/store.py): ONE layout
(``part_layout``) that says where every leaf of a partition file lies, ONE
writer (``StoreWriter``) behind every way of writing a store, three byte
targets under it that know names and bytes only — and nothing else in the
package that knows the format.

* the layout against what is on disk, and its row width against the cost
  analyzer's (analysis/domain.py), column kind by column kind;
* every writer — ``write_store`` to a local path, the fake S3 and the fake
  WebHDFS, ``write_chunks_to_store`` local and WebHDFS, ``write_store`` then
  ``append_store`` — puts down the layout's bytes and a manifest that is
  ``part_checksums`` + ``build_meta`` of exactly those bytes;
* the bytes of a fixed tiny store are pinned to literals taken at the
  parent of the PR that made the seam (PR 32);
* the imports point one way.

Every case is its own parametrised test so that each counts."""

import ast
import hashlib
import json
import os
import pathlib
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from test_s3 import _FakeS3, s3env  # noqa: E402,F401
from webhdfs_fake import FakeWebHdfs  # noqa: E402

from dryad_tpu import make_mesh  # noqa: E402
from dryad_tpu.analysis import domain  # noqa: E402
from dryad_tpu.data.columnar import Batch, StringColumn  # noqa: E402
from dryad_tpu.exec import ooc  # noqa: E402
from dryad_tpu.exec.data import PData, put_batch  # noqa: E402
from dryad_tpu.io import store  # noqa: E402

PKG = pathlib.Path(__file__).resolve().parent.parent / "dryad_tpu"

# a string column, a scalar dense column and a [rows, k] dense column
SCHEMA = {"name": {"kind": "str", "max_len": 6},
          "n": {"kind": "dense", "dtype": "int32", "shape": []},
          "v": {"kind": "dense", "dtype": "float32", "shape": [3]}}
ROWS = 50


def _rows(lo=0, hi=ROWS):
    """Rows [lo, hi) as host columns (name -> array | (data, lengths)), a
    function of the row number only."""
    i = np.arange(lo, hi, dtype=np.int32)
    return {"name": (((i[:, None] * 7 + np.arange(6)) % 251).astype(np.uint8),
                     (i % 7).astype(np.int32)),
            "n": i * 3 - 11,
            "v": (i[:, None] * 0.5 + np.arange(3)).astype(np.float32)}


def _leaf_bytes(cols, lo, hi):
    """Rows [lo, hi) of host columns as a partition file holds them: the
    columns sorted, a string's data then its lengths — written out here
    by hand, the layout's reference."""
    return [cols["n"][lo:hi].tobytes(), cols["name"][0][lo:hi].tobytes(),
            cols["name"][1][lo:hi].tobytes(), cols["v"][lo:hi].tobytes()]


def _pdata(cols, ndev=2, junk=3):
    """The rows cut evenly over ``ndev`` partitions, in order, with
    ``junk`` rows of capacity past every count."""
    n = len(cols["n"])
    cuts = np.linspace(0, n, ndev + 1).astype(int)
    counts = np.diff(cuts)
    cap = int(counts.max()) + junk

    def stack(a):
        out = np.full((ndev, cap) + a.shape[1:], 77, a.dtype)
        for p in range(ndev):
            out[p, :counts[p]] = a[cuts[p]:cuts[p + 1]]
        return out
    batch = Batch({"name": StringColumn(stack(cols["name"][0]),
                                        stack(cols["name"][1])),
                   "n": stack(cols["n"]), "v": stack(cols["v"])},
                  counts.astype(np.int32))
    return PData(put_batch(batch, make_mesh(jax.devices()[:ndev])), ndev)


# -- (a) the layout ------------------------------------------------------------


def test_the_layout_slices_a_part_file_into_its_columns(tmp_path):
    path = str(tmp_path / "s")
    store.write_store(path, _pdata(_rows()))
    meta = store.store_meta(path)
    assert meta["schema"] == SCHEMA
    lo = 0
    for p, n in enumerate(meta["counts"]):
        with open(store._part_path(path, p), "rb") as f:
            raw = f.read()
        layout = store.part_layout(meta["schema"], n)
        assert [(leaf.column, leaf.str_part) for leaf in layout] == [
            ("n", None), ("name", 0), ("name", 1), ("v", None)]
        assert [leaf.row_bytes for leaf in layout] == [4, 6, 4, 12]
        assert layout[-1].offset + layout[-1].nbytes == len(raw) \
            == meta["bytes"][p]
        for leaf, want in zip(layout, _leaf_bytes(_rows(), lo, lo + n)):
            assert raw[leaf.offset:leaf.offset + leaf.nbytes] == want, leaf
            # and rows [s, e) of the leaf lie where the layout says
            s, e = 3 * leaf.row_bytes, 11 * leaf.row_bytes
            assert raw[leaf.offset + s:leaf.offset + e] == want[s:e]
        lo += n


@pytest.mark.parametrize("spec", [
    {"kind": "str", "max_len": 1}, {"kind": "str", "max_len": 90},
    {"kind": "dense", "dtype": "int32", "shape": []},
    {"kind": "dense", "dtype": "uint8", "shape": []},
    {"kind": "dense", "dtype": "float16", "shape": [5]},
    {"kind": "dense", "dtype": "float32", "shape": [2, 3]},
    {"kind": "dense", "dtype": "bfloat16", "shape": [4]},
    {"kind": "dense", "dtype": "int64"}],
    ids=lambda s: "-".join(str(v) for v in s.values()))
def test_row_width_is_the_cost_analyzers(spec):
    """io/ no longer asks analysis/ for a row's width: the two arithmetics
    are held equal here, alone and beside other columns."""
    for schema in ({"c": spec}, {**SCHEMA, "c": spec}):
        assert store.schema_row_bytes(schema) == domain.schema_row_bytes(
            domain.schema_from_store_schema(schema))
        assert store.schema_row_bytes(schema) == sum(
            leaf.row_bytes for leaf in store.part_layout(schema, 9))
        assert [leaf.nbytes for leaf in store.part_layout(schema, 9)] == [
            9 * leaf.row_bytes for leaf in store.part_layout(schema)]


# -- (b) every writer writes the same store ------------------------------------


@pytest.fixture()
def hdfs():
    s = FakeWebHdfs(block_size=4096)
    yield s
    s.close()


def _chunks(cols, chunk_rows=16):
    n = len(cols["n"])
    for s in range(0, n, chunk_rows):
        e = min(s + chunk_rows, n)
        yield ooc.HChunk({"name": (cols["name"][0][s:e], cols["name"][1][s:e]),
                          "n": cols["n"][s:e], "v": cols["v"][s:e]}, e - s)


def _write(writer, path):
    cols = _rows()
    if writer == "write_store":
        store.write_store(path, _pdata(cols))
    elif writer == "write_chunks_to_store":
        chunks = list(_chunks(cols))
        ooc.write_chunks_to_store(path, iter(chunks),
                                  ooc.chunk_schema(chunks[0]))
    else:
        half = ROWS // 2
        store.write_store(path, _pdata(_rows(0, half)))
        assert store.append_store(path, _pdata(_rows(half, ROWS))) == 1


def _raw_part(path, meta, p, fakes):
    """Partition p's bytes as the writer's target holds them."""
    if path.startswith("s3://"):
        (key,) = [k for k in _FakeS3.objects
                  if k.endswith(f"{meta['generation']}/part-{p:05d}.bin")]
        return _FakeS3.objects[key]
    if path.startswith("hdfs://"):
        (key,) = [k for k in fakes.files
                  if k.endswith(f"/eq/part-{p:05d}.bin")]
        return fakes.files[key]
    with open(store._part_path(path, p), "rb") as f:
        return f.read()


@pytest.mark.parametrize("writer,where", [
    ("write_store", "local"), ("write_store", "s3"), ("write_store", "hdfs"),
    ("write_chunks_to_store", "local"), ("write_chunks_to_store", "hdfs"),
    ("write_store+append_store", "local")])
def test_every_writer_writes_the_same_store(writer, where, tmp_path,
                                            s3env, hdfs):  # noqa: F811
    path = {"local": str(tmp_path / "eq"), "s3": "s3://bkt/seam/eq",
            "hdfs": hdfs.url + "/seam/eq"}[where]
    _write(writer, path)
    meta = store.store_meta(path)
    assert meta["schema"] == SCHEMA and sum(meta["counts"]) == ROWS
    assert meta["npartitions"] == len(meta["counts"]) > 1

    # the bytes each partition's target holds are the layout's, row for row
    raws, lo = [], 0
    for p, n in enumerate(meta["counts"]):
        raws.append(_raw_part(path, meta, p, hdfs))
        assert raws[-1] == b"".join(_leaf_bytes(_rows(), lo, lo + n)), p
        lo += n
    # and the manifest is part_checksums + build_meta of exactly those bytes
    sums, leaves, _ = store.part_checksums(
        SCHEMA, meta["counts"],
        [[np.frombuffer(raw, np.uint8)] for raw in raws])
    want = store.build_meta(SCHEMA, meta["counts"], sums,
                            leaf_checksums=leaves)
    for key in ("schema", "counts", "bytes", "checksum_algo",
                "checksum_block", "checksums", "leaf_checksums"):
        assert meta[key] == want[key], key
    assert list(meta) == list(want)                  # key order included

    # read back, verified, equal to the same rows in the same order
    back = store.read_store(path, make_mesh(jax.devices()[:2]))
    counts = np.asarray(back.counts).tolist()
    assert sum(counts) == ROWS

    def rows_of(a):
        return np.concatenate([np.asarray(a[p])[:c]
                               for p, c in enumerate(counts)])
    cols = _rows()
    name = back.batch.columns["name"]
    assert np.array_equal(rows_of(name.data), cols["name"][0])
    assert np.array_equal(rows_of(name.lengths), cols["name"][1])
    assert np.array_equal(rows_of(back.batch.columns["n"]), cols["n"])
    assert np.array_equal(rows_of(back.batch.columns["v"]), cols["v"])


# -- (c) the format pin ---------------------------------------------------------

# sha256[:16] of every file of the store below, taken at the parent of PR 32
# (43f804b); the manifest with its ``native_io`` line (a fact about the
# machine, not the format) taken out
PINNED = {
    None: {"meta.json": "83bc5085ced03e3a",
           "part-00000.bin": "b1ee2e59004676ba",
           "part-00001.bin": "8339868a2b39f9aa"},
    "gzip": {"meta.json": "25e2d782f3fdf058",
             "part-00000.bin": "ce2afd9195a4f866",
             "part-00001.bin": "1d56f940bf960b91"},
}


def _pin_pdata():
    cap, counts = 8, [5, 3]
    i = np.arange(2 * cap, dtype=np.int32).reshape(2, cap)
    cols = {
        "name": StringColumn(
            ((i[..., None] * 7 + np.arange(6)) % 251).astype(np.uint8),
            (i % 7).astype(np.int32)),
        "n": i * 3 - 11,
        "v": (i[..., None] * 0.5 + np.arange(3)).astype(np.float32),
    }
    mesh = make_mesh(jax.devices()[:2])
    return PData(put_batch(Batch(cols, np.asarray(counts, np.int32)), mesh),
                 2)


@pytest.mark.parametrize("compression", [None, "gzip"])
def test_the_format_is_the_parents_byte_for_byte(tmp_path, compression):
    path = str(tmp_path / "s")
    store.write_store(path, _pin_pdata(),
                      partitioning={"kind": "hash", "keys": ["n"]},
                      compression=compression)
    got = {}
    for name in sorted(os.listdir(path)):
        with open(os.path.join(path, name), "rb") as f:
            raw = f.read()
        if name == "meta.json":
            meta = json.loads(raw)
            assert raw == json.dumps(meta, indent=1).encode()
            meta.pop("native_io")
            raw = json.dumps(meta, indent=1).encode()
        got[name] = hashlib.sha256(raw).hexdigest()[:16]
    assert got == PINNED[compression]


# -- (d) the imports point one way ---------------------------------------------


def _imports(path):
    """Every module name a file imports, at any depth."""
    names = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module)
            names.update(f"{node.module}.{a.name}" for a in node.names)
    return names


@pytest.mark.parametrize("module", sorted(
    p.name for p in (PKG / "io").glob("*.py")))
def test_io_does_not_import_analysis(module):
    assert not [m for m in _imports(PKG / "io" / module)
                if m.startswith("dryad_tpu.analysis")]


@pytest.mark.parametrize("module", ["s3_store.py", "webhdfs.py", "s3.py"])
def test_a_byte_target_does_not_import_the_format(module):
    assert not [m for m in _imports(PKG / "io" / module)
                if m.startswith("dryad_tpu.io.store")]


def test_only_the_store_spells_the_formats_names():
    """The manifest's builder, the digest, segment order and the blob
    encoding are called by name in io/store.py alone."""
    private = ("build_meta", "part_checksums", "chunk_segments",
               "segments_blob")
    for path in sorted(PKG.rglob("*.py")):
        if path == PKG / "io" / "store.py":
            continue
        nodes = list(ast.walk(ast.parse(path.read_text())))
        names = {n.id for n in nodes if isinstance(n, ast.Name)} \
            | {n.attr for n in nodes if isinstance(n, ast.Attribute)} \
            | {m.rsplit(".", 1)[-1] for m in _imports(path)}
        assert not names & set(private), path
