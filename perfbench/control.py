#!/usr/bin/env python3
"""Read the program's numbers and the control's, at the cell's own size,
over several seeds in one process (set-up is long, a reading short):

    python3 perfbench/control.py --workload <name> --seeds 1,2,3 [--rehearse]

For each seed: data from the seed, ingest, one query through the cell's
own driver (a closed loop of one client is its own load), the numbers the
check compares for that answer; then the control put in the program's
place (``ref/<module>.control``: the reference with one guarantee broken
or in the next lower precision) and its numbers.  One JSON line a seed.
The benchmark's own runs do not run this; its readings set the limits in
the traffic files (PERF.md).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from perfbench import run as R
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--no-control", action="store_true")
    ap.add_argument("--no-program", action="store_true",
                    help="the control's numbers only (the program's come "
                         "from the runs themselves)")
    args = ap.parse_args(argv)
    cell, cfg, traffic, _m = R.resolve(args.workload)
    devices = R.find_devices(int(cell["chips"]), args.rehearse)
    if devices is None:
        return 1
    from dryad_tpu import Context, make_mesh
    from perfbench.spans import Spans
    kind = R._module("kinds", cfg["kind"])
    driver = R._module("drivers", traffic["driver"])
    refspec = traffic["reference"]
    ref = R._module("ref", refspec["module"])
    ctx = Context(mesh=make_mesh(devices))
    work_root = os.path.join(ROOT, ".perfbench_work")
    os.makedirs(work_root, exist_ok=True)
    for seed in [int(s) for s in args.seeds.split(",")]:
        workdir = tempfile.mkdtemp(prefix="control-", dir=work_root)
        try:
            t0 = time.time()
            data = kind.generate(seed, cfg, rehearse=args.rehearse)
            rec = {"workload": args.workload, "seed": seed,
                   "platform": devices[0].platform, "rows": data["n"]}
            if not args.no_program:
                state = kind.ingest(ctx, data, cfg, workdir)
                dstate = driver.prepare(ctx, state, traffic, workdir)
                t1 = time.time()
                ans = driver.query(ctx, dstate, 0, Spans(False))
                rec.update(setup_s=t1 - t0, query_s=time.time() - t1,
                           program=ref.check(ans, data, refspec,
                                             ctx.nparts))
                driver.release(ans)
                del state, dstate, ans
            if not args.no_control:
                rec["control"] = ref.check(
                    ref.control(data, refspec, ctx.nparts), data, refspec,
                    ctx.nparts)
            print(json.dumps(rec), flush=True)
            del data
        finally:
            shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
