"""The search phase of ``kernels.hash_join``'s general body alone, on the
chip: the old three ``jnp.searchsorted(method="sort")`` calls against the
merge-sort ranges and the slot owners' marks + running max
(``kernels._candidate_ranges``, ``kernels._slot_owners``); beside them the
running max alone, as the streamed Pallas pass the phase runs
(``pallas_kernels.prefix_max``) and as XLA's ``lax.cummax``, the one it
was chosen over.  Nothing a benchmark cell runs.

Shapes: TPC-H Q3's two joins at SF 2 — ``lineitem`` (12,000,000 rows of
capacity, 6.5 M valid) against the ``orders ⋈ customer`` batch (3,000,000,
292,000 valid) with 59,792 candidate pairs, and ``orders`` (3,000,000,
1.46 M valid) against ``customer`` (300,000, 60,000 valid) with 292,000 —
and a dense one, every slot live: 12,000,000 left rows each matching one
of 3,000,000 right rows.  ``out_capacity`` is the left side's capacity.

    python benchmarks/join_search_probe.py          # the chip, ~10 min
    python benchmarks/join_search_probe.py --scale 0.001 --reps 2

Writes ``chiprun_out/join_search_probe.json`` and prints a markdown table
of seconds a call (median of ``--reps``) of each phase and of the two
running maxes alone over the merged rows.  The new phase's output is
checked against the old one's: ``start`` / ``stop`` everywhere, the owners
at every slot below ``min(total, out_capacity)``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dryad_tpu.ops import kernels, pallas_kernels  # noqa: E402

# (name, left capacity, left valid, right capacity, right valid, pairs);
# pairs = None: every left row matches one right row
SHAPES = (("q3_lineitem", 12_000_000, 6_500_000, 3_000_000, 292_000, 59_792),
          ("q3_orders", 3_000_000, 1_460_000, 300_000, 60_000, 292_000),
          ("dense", 12_000_000, 12_000_000, 3_000_000, 3_000_000, None))
MAXES = {"pallas": pallas_kernels.prefix_max, "cummax": jax.lax.cummax}


def _timed(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))            # compile + warm
    secs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        secs.append(time.perf_counter() - t0)
    return statistics.median(secs)


def _inputs(m, m_valid, n, n_valid, pairs, seed):
    """The phase's inputs as hash_join builds them: ``rkey`` sorted with
    the sentinel past the valid rows, ``lh``, the left validity."""
    rng = np.random.default_rng(seed)
    rh = rng.integers(0, 0xFFFFFFFF, n_valid, dtype=np.uint32)
    lh = rng.integers(0, 0xFFFFFFFF, m, dtype=np.uint32)
    if pairs is None:
        rh = np.unique(rh)
        lh = rh[rng.integers(0, rh.size, m)]
    else:
        # one candidate each for ``pairs`` valid left rows
        hit = rng.choice(m_valid, pairs, replace=False)
        lh[hit] = rh[rng.integers(0, rh.size, pairs)]
    rkey = np.full(n, 0xFFFFFFFF, np.uint32)
    rkey[:rh.size] = np.sort(rh)
    lvalid = np.arange(m) < m_valid
    return jnp.asarray(rkey), jnp.asarray(lh), jnp.asarray(lvalid)


def old_phase(rkey, lh, lvalid, out_capacity):
    start = jnp.searchsorted(rkey, lh, side="left", method="sort")
    stop = jnp.searchsorted(rkey, lh, side="right", method="sort")
    mult = jnp.where(lvalid, stop - start, 0)
    cum = jnp.cumsum(mult)
    t = jnp.arange(out_capacity, dtype=jnp.int32)
    lid = jnp.searchsorted(cum, t, side="right", method="sort")
    return start, stop, jnp.minimum(lid, lh.shape[0] - 1).astype(jnp.int32)


def new_phase(rkey, lh, lvalid, out_capacity):
    start, stop = kernels._candidate_ranges(rkey, lh)
    mult = jnp.where(lvalid, stop - start, 0)
    cum = jnp.cumsum(mult)
    return start, stop, kernels._slot_owners(cum, mult, out_capacity)


def probe(name, m, m_valid, n, n_valid, pairs, reps, seed):
    rkey, lh, lvalid = _inputs(m, m_valid, n, n_valid, pairs, seed)
    oc = m
    old = jax.jit(old_phase, static_argnums=3)
    want = [np.asarray(x) for x in old(rkey, lh, lvalid, oc)]
    mult = np.where(np.asarray(lvalid), want[1] - want[0], 0)
    total = int(mult.sum())
    row = {"shape": name, "left": m, "right": n, "pairs": total,
           "old_s": _timed(old, (rkey, lh, lvalid, oc), reps)}
    live = min(total, oc)
    new = jax.jit(new_phase, static_argnums=3)
    got = [np.asarray(x) for x in new(rkey, lh, lvalid, oc)]
    assert np.array_equal(got[0], want[0]), (name, "start")
    assert np.array_equal(got[1], want[1]), (name, "stop")
    assert np.array_equal(got[2][:live], want[2][:live]), (name, "owners")
    row["new_s"] = _timed(new, (rkey, lh, lvalid, oc), reps)
    # the running max alone, over as many rows as the merge sorts
    x = jnp.asarray(np.random.default_rng(seed).integers(
        0, 1 << 30, n + m, dtype=np.int32))
    for tag, running_max in MAXES.items():
        row[f"{tag}_alone_s"] = _timed(jax.jit(running_max), (x,), reps)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=1.0,
                    help="multiply every row count (CPU rehearsals)")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/join_search_probe.json")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    rows = []
    for name, *counts in SHAPES:
        counts = [None if c is None else max(int(c * args.scale), 1)
                  for c in counts]
        rows.append(probe(name, *counts, args.reps, args.seed))
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "reps": args.reps, "rows": rows}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"platform {dev.platform} ({dev.device_kind}); seconds a call, "
          f"median of {args.reps}")
    print("| shape | left x right | pairs | old | new | prefix_max alone "
          "| lax.cummax alone |")
    for r in rows:
        print(f"| {r['shape']} | {r['left']} x {r['right']} | {r['pairs']} "
              f"| {r['old_s']:.4f} | {r['new_s']:.4f} "
              f"| {r['pallas_alone_s']:.4f} | {r['cummax_alone_s']:.4f} |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
