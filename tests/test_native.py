"""Native IO engine tests (build + pack + parallel file IO + store v2)."""

import os

import numpy as np
import pytest

from dryad_tpu import native


def test_native_builds():
    assert native.available(), "native engine failed to build"


def test_pack_lines_matches_python():
    buf = b"hello world\nsecond line\r\nthird\n\nlast-no-newline"
    data, lens = native.pack_lines(buf, max_len=16)
    expect = [b"hello world", b"second line", b"third", b"", b"last-no-newline"]
    assert len(data) == len(expect)
    for i, e in enumerate(expect):
        assert bytes(data[i][: lens[i]]) == e


def test_pack_lines_fallback_matches_native():
    """The pure-Python fallback must split ONLY on \\n (with CRLF trim),
    like dryad_pack_lines — not on \\x0b/\\x0c/\\x1c-\\x1e/lone \\r the way
    bytes.splitlines does (ADVICE r1)."""
    buf = (b"plain\n"
           b"vt\x0bmid\n"        # \x0b must NOT split
           b"ff\x0cmid\n"        # \x0c must NOT split
           b"fs\x1c\x1d\x1emid\n"
           b"lone\rcr\n"         # lone \r mid-line must NOT split
           b"crlf\r\n"
           b"tail")
    from dryad_tpu.native import pack_lines

    native_res = pack_lines(buf, max_len=32)
    # force the fallback path
    import dryad_tpu.native as nat
    orig = nat._load
    nat._load = lambda: None
    try:
        fb_res = pack_lines(buf, max_len=32)
    finally:
        nat._load = orig
    assert len(native_res[0]) == len(fb_res[0])
    for (d1, l1), (d2, l2) in zip(zip(*native_res), zip(*fb_res)):
        assert bytes(d1[:l1]) == bytes(d2[:l2])
    assert bytes(fb_res[0][1][: fb_res[1][1]]) == b"vt\x0bmid"
    assert bytes(fb_res[0][4][: fb_res[1][4]]) == b"lone\rcr"


def test_pack_lines_truncation():
    data, lens = native.pack_lines(b"abcdefghij\nxy", max_len=4)
    assert bytes(data[0][: lens[0]]) == b"abcd"
    assert bytes(data[1][: lens[1]]) == b"xy"


def test_pack_bytes_list():
    items = [b"aa", b"", b"cccc", b"longer-than-max"]
    data, lens = native.pack_bytes_list(items, max_len=8, capacity=8)
    assert bytes(data[0][:2]) == b"aa"
    assert lens[1] == 0
    assert bytes(data[3][: lens[3]]) == b"longer-t"


def test_parallel_file_roundtrip(tmp_path):
    rng = np.random.RandomState(0)
    paths, segs = [], []
    arrays = []
    for i in range(6):
        a = rng.randint(0, 255, (100 + i, 8), dtype=np.uint8)
        b = rng.randn(50 + i).astype(np.float32)
        paths.append(str(tmp_path / f"f{i}.bin"))
        segs.append([a, b])
        arrays.append((a, b))
    native.write_files(paths, segs)
    out_segs = []
    for i in range(6):
        out_segs.append([np.empty_like(arrays[i][0]),
                         np.empty_like(arrays[i][1])])
    native.read_files(paths, out_segs)
    for (a, b), (a2, b2) in zip(arrays, out_segs):
        np.testing.assert_array_equal(a, a2)
        np.testing.assert_array_equal(b, b2)


def test_read_missing_file_raises(tmp_path):
    with pytest.raises(IOError):
        native.read_files([str(tmp_path / "nope.bin")],
                          [[np.empty(4, np.uint8)]])


def test_fingerprint_stable():
    def digest(data, block=4):
        return native.digest_parts(
            [[np.frombuffer(data, np.uint8)]], [[len(data)]], block)[0][0]
    a = digest(b"hello")
    assert a == digest(b"hello")
    assert a != digest(b"hellp")
    assert a != digest(b"hello", block=3)
    # one block of one leaf: FNV-1a of the bytes, of that digest's eight
    # bytes, and of that one's again
    h = native._fnv_py(b"hello")
    assert h == 0xa430d84680aabd0b            # the published test vector
    assert digest(b"hello", block=8) == native._fnv_words(
        [native._fnv_words([h])])


def test_read_text_native(tmp_path):
    from dryad_tpu import Context
    p = tmp_path / "t.txt"
    p.write_bytes(b"the quick fox\njumps over\nthe lazy dog\n" * 50)
    ctx = Context()
    out = (ctx.read_text(str(p))
           .split_words("line", out_capacity=4096)
           .group_by(["line"], {"n": ("count", None)})
           .collect())
    got = {k.decode(): int(v) for k, v in zip(out["line"], out["n"])}
    assert got == {"the": 100, "quick": 50, "fox": 50, "jumps": 50,
                   "over": 50, "lazy": 50, "dog": 50}


def test_compact_rows_native_matches_fallback():
    rng = np.random.RandomState(0)
    n, L = 1_000, 12
    data = rng.randint(0, 255, (n, L), np.uint8)
    lens = rng.randint(0, L + 1, n).astype(np.int32)
    lens[5] = 0
    packed, offs = native.compact_rows(data, lens)
    assert offs[-1] == lens.sum() == len(packed)
    import dryad_tpu.native as nat
    orig = nat._load
    nat._load = lambda: None
    try:
        p2, o2 = native.compact_rows(data, lens)
    finally:
        nat._load = orig
    assert p2 == packed and np.array_equal(o2, offs)
    rows = native.unpack_rows(data, lens)
    for i in range(0, n, 97):
        assert rows[i] == bytes(data[i, : lens[i]])
