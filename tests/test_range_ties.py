"""The range exchange under duplicate-heavy keys (ISSUE 34).

The range destination compares the WHOLE sort key (every sort lane of
every ``order_by`` key, each in its direction) and cuts a run of equal
keys by the rows' global input position (parallel/shuffle.range_dest), so
no key law can unbalance the partitions, and — with the stable local
sort — ``order_by`` equals the oracle's stable sort row for row.

Four CPU devices, seeded data, every result against ``dryad_tpu/oracle``
(``Context(local_debug=True)``) or numpy.  The sampler is held to 512
samples a partition so that the splitters are real samples (4,096 rows a
partition here), not the whole input.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dryad_tpu import Context, make_mesh
from dryad_tpu.data.columnar import Batch
from dryad_tpu.parallel import shuffle
from dryad_tpu.utils.config import JobConfig
from tests.utils import assert_same_rows

N = 16384
P = 4


@pytest.fixture(scope="module")
def run():
    """(ctx on four devices, its event list, the oracle's ctx)."""
    events = []
    ctx = Context(mesh=make_mesh(jax.devices()[:P]),
                  config=JobConfig(range_samples_per_partition=512),
                  event_log=events.append)
    return ctx, events, Context(local_debug=True)


def _rand_keys(rng, n, width=10):
    return [bytes(r) for r in rng.integers(0, 256, (n, width), np.uint8)]


def _hot(rng, n, hot_keys, shares):
    """``hot_keys[i]`` at ``shares[i]`` of the rows, the rest random
    10-byte keys, in random order."""
    keys = _rand_keys(rng, n)
    u = rng.random(n)
    lo = 0.0
    for k, s in zip(hot_keys, shares):
        for i in np.nonzero((u >= lo) & (u < lo + s))[0]:
            keys[i] = k
        lo += s
    return keys


def _law(name, seed=0):
    """columns, order_by keys of one key law."""
    rng = np.random.default_rng([seed, len(name)])
    ids = np.arange(N, dtype=np.int32)
    if name == "one_key_40pct":
        keys = _hot(rng, N, [b"\x80hot-key-0"], [0.4])
        return {"key": keys, "id": ids}, [("key", False)]
    if name == "hot_keys_share_4_bytes":
        # four hot keys equal in their first sort lane (bytes 0-3), in
        # BOTH later lanes' orders: a split on the first lane alone
        # would cut them by position and break the global order
        hot = [b"SAME" + t for t in (b"\x00\x00zzzz", b"\x00\x01aaaa",
                                     b"\xff\xfe0000", b"\xff\xffAAAA")]
        keys = _hot(rng, N, hot, [0.2, 0.2, 0.2, 0.2])
        return {"key": keys, "id": ids}, [("key", False)]
    if name == "two_keys_first_hot_second_desc":
        k = np.where(rng.random(N) < 0.6, 7,
                     rng.integers(0, 50, N)).astype(np.int32)
        v = rng.integers(-1000, 1000, N).astype(np.int32)
        return {"k": k, "v": v, "id": ids}, [("k", False), ("v", True)]
    if name == "descending_primary":
        keys = _hot(rng, N, [b"mid-hot-ke"], [0.45])
        return {"key": keys, "id": ids}, [("key", True)]
    if name == "every_key_equal":
        return {"key": [b"only-a-key"] * N, "id": ids}, [("key", False)]
    if name == "zipf_1.5":
        table = _rand_keys(rng, 1024)
        p = np.arange(1, 1025) ** -1.5
        r = rng.choice(1024, size=N, p=p / p.sum())
        return {"key": [table[i] for i in r], "id": ids}, [("key", False)]
    if name == "float_key_with_a_heavy_zero":
        f = np.where(rng.random(N) < 0.5, 0.0,
                     rng.standard_normal(N)).astype(np.float32)
        return {"f": f, "id": ids}, [("f", False)]
    raise ValueError(name)


LAWS = ["one_key_40pct", "hot_keys_share_4_bytes",
        "two_keys_first_hot_second_desc", "descending_primary",
        "every_key_equal", "zipf_1.5", "float_key_with_a_heavy_zero"]


def _dataset(ctx, cols):
    return ctx.from_columns(cols, str_max_len=10)


def _settled(events, label):
    done = [e for e in events if e.get("event") == "stage_done"
            and e["label"] == label and not e["overflow"]]
    assert done, f"no settled {label} stage"
    return done[-1]


@pytest.mark.parametrize("law", LAWS)
def test_order_by_equals_the_oracle_and_is_balanced(run, law):
    """(a)-(e): correct under every key law — row for row the oracle's
    stable sort, so equal keys keep their input order across partition
    boundaries — and balanced: no partition above 1.1 x its share, none
    empty, the capacity scale settled at 2 at most."""
    ctx, events, dbg = run
    cols, keys = _law(law)
    del events[:]
    got = _dataset(ctx, cols).order_by(keys).collect()
    exp = _dataset(dbg, cols).order_by(keys).collect()
    assert_same_rows(got, exp, ordered=True)
    e = _settled(events, "orderby")
    rows = e["rows"]
    assert sum(rows) == N and min(rows) > 0
    assert max(rows) * P / N <= 1.1, rows
    assert e["scale"] <= 2
    # what the trace says of the exchange: lanes compared (the key's
    # lanes and the position), rows the tiebreak placed
    lanes = {"key": 3, "k": 1, "v": 1, "f": 1}
    assert e["range_lanes"] == sum(lanes[k] for k, _ in keys) + 1
    if law == "every_key_equal":
        assert e["tie_rows"] == N
    if law == "one_key_40pct":
        assert 0.4 * N * 0.9 <= e["tie_rows"] <= 0.4 * N * 1.1


def _partitions(pd):
    """Per partition, the valid rows of every column as host bytes."""
    counts = np.asarray(pd.counts)
    out = []
    for p in range(pd.nparts):
        leaves = [np.asarray(x)[p, :counts[p]].tobytes()
                  for x in jax.tree.leaves(pd.batch.columns)]
        out.append((int(counts[p]), leaves))
    return out


@pytest.mark.parametrize("law", ["one_key_40pct", "zipf_1.5"])
def test_same_input_gives_the_same_partitions_byte_for_byte(run, law):
    """(f): the tiebreak is a function of the input alone — a stage run
    again (the system's fault tolerance is re-execution) places every
    row where the first run did."""
    ctx, _events, _dbg = run
    cols, keys = _law(law)
    first = _partitions(_dataset(ctx, cols).order_by(keys)._materialize())
    again = _partitions(_dataset(ctx, cols).order_by(keys)._materialize())
    assert first == again
    assert all(n > 0 for n, _ in first)


def test_range_partition_keeps_its_contract(run):
    """(g): ``range_partition(keys)``: every row once, partition p holds
    only keys <= those of partition p+1 (a tie may straddle), balanced
    under a heavy key; rows are not reordered within a source."""
    ctx, events, _dbg = run
    cols, _ = _law("two_keys_first_hot_second_desc")
    del events[:]
    pd = _dataset(ctx, cols).range_partition(["k", "v"])._materialize()
    counts = np.asarray(pd.counts)
    k = np.asarray(pd.batch.columns["k"])
    v = np.asarray(pd.batch.columns["v"])
    ids = np.asarray(pd.batch.columns["id"])
    parts = [(k[p, :counts[p]], v[p, :counts[p]], ids[p, :counts[p]])
             for p in range(P)]
    assert sorted(np.concatenate([i for _, _, i in parts]).tolist()) \
        == list(range(N))
    assert max(counts) * P / N <= 1.1 and min(counts) > 0
    for (ka, va, _), (kb, vb, _) in zip(parts, parts[1:]):
        hi = max(zip(ka.tolist(), va.tolist()))
        lo = min(zip(kb.tolist(), vb.tolist()))
        assert hi <= lo
    e = _settled(events, "rangepartition")
    assert e["range_lanes"] == 3 and e["scale"] <= 2


def test_order_by_after_order_by_still_skips_its_exchange(run):
    """(g): the elimination of planner.py (ascending prefix of a range
    claim) holds with ties straddling partitions: the second sort plans
    no exchange and the result is the oracle's."""
    ctx, _events, dbg = run
    cols, _ = _law("two_keys_first_hot_second_desc")

    def q(c):
        return (_dataset(c, cols).order_by([("k", False), ("v", False)])
                .order_by([("k", False)]))
    assert q(ctx).explain().count("=>range") == 1
    assert_same_rows(q(ctx).collect(), q(dbg).collect(), ordered=True)
    # a descending first sort claims nothing, so the second keeps its own
    plan = (_dataset(ctx, cols).order_by([("k", True)])
            .order_by([("k", False)]).explain())
    assert plan.count("=>range") == 2


def test_order_among_equal_keys_is_input_order(run):
    """(h): what the suite pins about ties (tests/test_kernels.py: equal
    keys keep their original row order through the sort) now holds over
    the whole mesh: ids ascend within every run of equal keys."""
    ctx, _events, _dbg = run
    cols, keys = _law("zipf_1.5")
    got = _dataset(ctx, cols).order_by(keys).collect()
    key, ids = got["key"], np.asarray(got["id"])
    same = np.asarray([a == b for a, b in zip(key, key[1:])])
    assert same.sum() > N // 2
    assert np.all(np.diff(ids)[same] > 0)


# -- the rule itself (parallel/shuffle.range_dest), against numpy ----------

def _np_dest(rows, bounds):
    """Number of splitters <= the row, tuples compared lexicographically."""
    return np.asarray([sum(tuple(b) <= tuple(r) for b in bounds.tolist())
                       for r in rows.tolist()], np.int32)


@pytest.mark.parametrize("lanes,position", [(1, False), (1, True),
                                            (3, False), (3, True)])
def test_range_dest_is_the_lexicographic_rule(lanes, position):
    rng = np.random.default_rng(lanes + 10 * position)
    n, width = 2000, lanes + position
    # few distinct values a lane, so ties reach every lane
    rows = rng.integers(0, 4, (n, width)).astype(np.uint32)
    bounds = np.unique(rng.integers(0, 4, (5, width)).astype(np.uint32),
                       axis=0)
    key_lanes = [jnp.asarray(rows[:, k]) for k in range(lanes)]
    pos = jnp.asarray(rows[:, lanes]) if position else None
    dest, tie = shuffle.range_dest(key_lanes, jnp.asarray(bounds), pos)
    assert np.array_equal(np.asarray(dest), _np_dest(rows, bounds))
    want_tie = (rows[:, None, :lanes] == bounds[None, :, :lanes]
                ).all(axis=2).any(axis=1)
    assert np.array_equal(np.asarray(tie), want_tie)


def test_range_dest_on_a_prefix_of_the_lanes_keeps_equal_prefixes_together():
    """The streamed paths (exec/ooc.py, runtime/stream_plan.py) hand the
    one rule the one lane they sample: rows equal in it share a
    destination, whatever their later lanes."""
    rng = np.random.default_rng(5)
    first = rng.integers(0, 6, 500).astype(np.uint32)
    later = rng.integers(0, 1 << 30, 500).astype(np.uint32)
    bounds = jnp.asarray(np.asarray([[2], [2], [4]], np.uint32))
    dest, _ = shuffle.range_dest([jnp.asarray(first), jnp.asarray(later)],
                                 bounds)
    dest = np.asarray(dest)
    assert np.array_equal(dest, np.searchsorted([2, 2, 4], first,
                                                side="right"))


def test_range_key_lanes_bake_the_direction_in():
    b = Batch({"k": jnp.asarray([3, -1, 7], jnp.int32),
               "s": jnp.asarray([1.5, -2.0, 0.0], jnp.float32)},
              jnp.asarray(3, jnp.int32))
    asc = shuffle.range_key_lanes(b, [("k", False), ("s", False)])
    mixed = shuffle.range_key_lanes(b, [("k", False), ("s", True)])
    assert len(asc) == len(mixed) == 2
    assert np.array_equal(np.argsort(np.asarray(asc[0])), [1, 0, 2])
    assert np.array_equal(np.asarray(asc[0]), np.asarray(mixed[0]))
    assert np.array_equal(np.asarray(mixed[1]), ~np.asarray(asc[1]))
