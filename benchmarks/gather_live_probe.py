"""The bounded packed gather alone, on the chip (ROADMAP S4: "measure the
packed gather alone").

``kernels._sort_carrying`` past ``_VALOPS_MAX_ELEMS`` is an index sort and
ONE gather of the stacked ``[cap, W]`` word matrix (``_gather_lanes``).
This times that gather branch — stack, gather, unstack — over a random
permutation (the order a hash sort gives), as the single ``jnp.take``
(``live=None``) and as the bounded gather (``_gather_live``: a loop of
chunks, or past three quarters live the whole take) at several chunk
sizes and live shares; beside them the stack and the unstack alone.  It is what ``kernels._GATHER_CHUNK`` was
chosen from; nothing a benchmark cell runs.

    python benchmarks/gather_live_probe.py            # the chip, ~5 min
    python benchmarks/gather_live_probe.py --caps 4096 --words 4 --reps 2

Writes ``chiprun_out/gather_live_probe.json`` and prints a markdown table:
seconds a call (median of ``--reps``), and ``full/take`` = the bounded
gather at ``live == cap`` over the single take.
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

from dryad_tpu.ops import kernels  # noqa: E402

# the last three straddle the share past which the whole take runs
SHARES = (1 / 128, 1 / 8, 1 / 2, 3 / 4, 7 / 8, 1.0)


def _timed(fn, args, reps: int) -> float:
    jax.block_until_ready(fn(*args))            # compile + warm
    secs = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        secs.append(time.perf_counter() - t0)
    return statistics.median(secs)


def _unstack(words):
    return [words[:, j] for j in range(words.shape[1])]


def probe(cap: int, W: int, chunks, reps: int, seed: int) -> dict:
    order = jnp.asarray(np.random.default_rng(seed).permutation(cap)
                        .astype(np.int32))
    base = jnp.arange(cap, dtype=jnp.uint32)
    lanes = tuple(base * jnp.uint32(2654435761) + jnp.uint32(j)
                  for j in range(W))
    want = np.asarray(lanes[W - 1])[np.asarray(order)]

    take = jax.jit(lambda ls, o: kernels._gather_lanes(list(ls), o))
    # what of the branch is no gather: the stack and the unstack alone
    relayout = jax.jit(lambda ls: _unstack(jax.lax.optimization_barrier(
        jnp.stack(ls, axis=1))))
    row = {"cap": cap, "W": W, "take_s": _timed(take, (lanes, order), reps),
           "relayout_s": _timed(relayout, (lanes,), reps),
           "chunks": {}}
    for chunk in chunks:
        kernels._GATHER_CHUNK = chunk           # read when the loop traces
        loop = jax.jit(lambda ls, o, n: kernels._gather_lanes(list(ls), o, n))
        by_share = {}
        for share in SHARES:
            live = max(int(cap * share), 1)
            n = jnp.asarray(live, jnp.int32)
            by_share[f"{share:.4f}"] = _timed(loop, (lanes, order, n), reps)
            got = np.asarray(loop(lanes, order, n)[W - 1])
            assert (got[:live] == want[:live]).all() and not got[live:].any()
        row["chunks"][str(chunk)] = by_share
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--caps", default="12000000,8388608")
    ap.add_argument("--words", default="4,8,16,26")
    ap.add_argument("--chunks", default="16384,65536,262144")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/gather_live_probe.json")
    args = ap.parse_args(argv)
    ints = lambda s: [int(x) for x in s.split(",")]  # noqa: E731
    chunks = ints(args.chunks)
    dev = jax.devices()[0]
    rows = []
    for cap in ints(args.caps):
        for W in ints(args.words):
            rows.append(probe(cap, W, chunks, args.reps, args.seed))
            print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    out = {"platform": dev.platform, "device_kind": dev.device_kind,
           "reps": args.reps, "rows": rows}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)

    full = f"{1.0:.4f}"
    print(f"platform {dev.platform} ({dev.device_kind}); seconds a call, "
          f"median of {args.reps}")
    print("| cap | W | take | stack+unstack | " + " | ".join(
        f"chunk {c}: " + " / ".join(f"{float(s):.3g}" for s in
                                    rows[0]["chunks"][str(c)])
        + " ; full/take" for c in chunks) + " |")
    for r in rows:
        cells = []
        for c in chunks:
            by = r["chunks"][str(c)]
            cells.append(" / ".join(f"{v:.4f}" for v in by.values())
                         + f" ; {by[full] / r['take_s']:.3f}")
        print(f"| {r['cap']} | {r['W']} | {r['take_s']:.4f} | "
              f"{r['relayout_s']:.4f} | "
              + " | ".join(cells) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
