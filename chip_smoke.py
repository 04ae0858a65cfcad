#!/usr/bin/env python3
"""Chip smoke: drive the store -> query -> store path once on the TPU.

    python chip_smoke.py                 # one chip: sort phase + sql phase
    python chip_smoke.py --chips 4       # four chips: the exchange phase only
    python chip_smoke.py --platform cpu --rows 65536   # CPU rehearsal

One process, no child that imports JAX.  Every phase goes through the
entry points a user calls — ``Context()`` (mesh over ``jax.devices()``)
-> stores -> ``Dataset`` / ``sql.query`` -> plan/ -> exec/ -> store or
``collect()`` — on data made with numpy from ``--seed``, and is checked
against plain numpy written here.  Any failure raises: nothing on this
path turns an error into a record.

stdout is one JSON object per line; the LAST line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it.  Without a TPU the script exits
non-zero before any phase and prints no result line (``--platform cpu``
exists only for the rehearsal and says ``"platform": "cpu"``).
Seconds printed here are set-up and run time of a smoke, not benchmark
results.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

KEY_LEN = 10            # TeraSort key bytes (apps/terasort.py)
SUM_RTOL = 1e-4         # f32 grouped sums vs the f64 numpy reference
N_REGIONS = 1000        # sql phase: groups
SQL = ("SELECT d.region, COUNT(*) AS n, SUM(f.amount) AS total, "
       "MAX(f.price) AS top "
       "FROM fact f JOIN dim d ON f.dkey = d.dkey "
       "WHERE d.active = 1 AND f.qty > 2 "
       "GROUP BY d.region")


def emit(**rec) -> None:
    print(json.dumps(rec), flush=True)


class Meter:
    """Compile seconds and persistent-cache hits/misses, from JAX's own
    monitoring events (every compile of the process, not only stage
    programs)."""

    def __init__(self):
        import jax.monitoring as mon
        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._on_event)
        mon.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _on_duration(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def snapshot(self):
        return (self.hits, self.misses, self.compiles, self.compile_s)


def run_phase(name, meter, events, fn, *args):
    """Run one phase and print its record.  No except: a failed phase
    ends the script with its traceback and a non-zero exit."""
    h0, m0, c0, s0 = meter.snapshot()
    del events[:]
    t0 = time.time()
    info = fn(*args)
    wall = time.time() - t0
    h1, m1, c1, s1 = meter.snapshot()
    emit(phase=name, ok=True, wall_s=round(wall, 3),
         compile_s=round(s1 - s0, 3),
         run_s=round(wall - (s1 - s0), 3),
         compiles=c1 - c0, persistent_cache_hits=h1 - h0,
         persistent_cache_misses=m1 - m0,
         stages=[{"label": e["label"], "compile_s": e["compile_s"],
                  "wall_s": e["wall_s"], "rows": int(sum(e["rows"]))}
                 for e in events if e.get("event") == "stage_done"],
         **info)


# -- data in, data out: numpy <-> stores, no per-row Python -----------------

def _to_device(ctx, columns, n):
    """numpy columns [n, ...] -> PData block-partitioned over the mesh.
    String columns arrive as (bytes [n, L] u8, lengths [n] i32)."""
    from dryad_tpu.data.columnar import Batch, StringColumn
    from dryad_tpu.exec.data import PData, put_batch
    parts = ctx.nparts
    if n % parts:
        raise ValueError(f"{n} rows do not divide over {parts} partitions")
    cap = n // parts

    def block(a):
        return a.reshape((parts, cap) + a.shape[1:])

    cols = {k: (StringColumn(block(v[0]), block(v[1]))
                if isinstance(v, tuple) else block(v))
            for k, v in columns.items()}
    batch = put_batch(Batch(cols, np.full((parts,), cap, np.int32)),
                      ctx.mesh)
    return PData(batch, parts)


def _write_input(ctx, path, columns, n) -> int:
    """Ingest through the public way in: from_pdata -> to_store.
    Returns the bytes the input held on the device."""
    import jax
    pd = _to_device(ctx, columns, n)
    ctx.from_pdata(pd).to_store(path)
    return int(sum(x.nbytes for x in jax.tree.leaves(pd.batch)))


def _read_output(ctx, path):
    """from_store -> host numpy, valid rows in partition order.  String
    columns come back as (bytes, lengths)."""
    from dryad_tpu.data.columnar import StringColumn
    pd = ctx.from_store(path).node.data
    counts = np.asarray(pd.counts)

    def valid(a):
        a = np.asarray(a)
        return np.concatenate([a[p, :counts[p]] for p in range(pd.nparts)])

    out = {}
    for k, v in pd.batch.columns.items():
        out[k] = ((valid(v.data), valid(v.lengths))
                  if isinstance(v, StringColumn) else valid(v))
    return out, counts


def _key_lanes(keys):
    """[n, 10] u8 -> (u64 of bytes 0-7, u16 of bytes 8-9), big-endian, so
    integer order is byte-lexicographic order."""
    hi = np.ascontiguousarray(keys[:, :8]).view(">u8").ravel()
    lo = np.ascontiguousarray(keys[:, 8:]).view(">u2").ravel()
    return hi, lo


def _compiled_texts(ctx):
    """HLO text of every stage program the executor compiled."""
    return [fn.as_text() for fn in ctx.executor._compile_cache.values()
            if hasattr(fn, "as_text")]


# -- phase: sort -------------------------------------------------------------

def _gen_terasort(rng, n):
    keys = rng.integers(ord(" "), ord("~") + 1, size=(n, KEY_LEN),
                        dtype=np.uint8)
    payload = rng.integers(0, 2**31, size=n, dtype=np.int32)
    return keys, payload


def _check_sorted(keys, payload, got):
    """Output keys equal np.sort of the input row for row; payloads
    follow their keys (within a run of equal keys any order is a sort)."""
    n = len(payload)
    out_keys, out_lens = got["key"]
    out_pay = got["payload"]
    if out_keys.shape != (n, KEY_LEN) or out_pay.shape != (n,):
        raise AssertionError(
            f"sort: output shape {out_keys.shape}/{out_pay.shape}, "
            f"expected {n} rows")
    if not (out_lens == KEY_LEN).all():
        raise AssertionError("sort: a key length changed")
    hi, lo = _key_lanes(keys)
    order = np.lexsort((lo, hi))
    ref_keys = keys[order]
    if not np.array_equal(out_keys, ref_keys):
        bad = int(np.argmax((out_keys != ref_keys).any(axis=1)))
        raise AssertionError(f"sort: keys differ from np.sort at row {bad}")
    ref_pay = payload[order]
    same_as_prev = (ref_keys[1:] == ref_keys[:-1]).all(axis=1)
    tie = np.zeros(n, bool)
    tie[1:] |= same_as_prev
    tie[:-1] |= same_as_prev
    if ((out_pay != ref_pay) & ~tie).any():
        raise AssertionError("sort: a payload left its key")
    if not np.array_equal(np.sort(out_pay[tie]), np.sort(ref_pay[tie])):
        raise AssertionError("sort: payloads of equal keys were lost")
    return int(tie.sum())


def phase_sort(ctx, n, seed, workdir):
    rng = np.random.default_rng([seed, 1])
    keys, payload = _gen_terasort(rng, n)
    src = os.path.join(workdir, "sort_in")
    dst = os.path.join(workdir, "sort_out")
    nbytes = _write_input(
        ctx, src, {"key": (keys, np.full(n, KEY_LEN, np.int32)),
                   "payload": payload}, n)
    t0 = time.time()
    ctx.from_store(src).order_by([("key", False)]).to_store(dst)
    query_s = time.time() - t0
    got, counts = _read_output(ctx, dst)
    ties = _check_sorted(keys, payload, got)
    return {"rows": n, "input_device_bytes": nbytes,
            "query_wall_s": round(query_s, 3),
            "out_partition_rows": counts.tolist(), "tied_rows": ties}


def _check_groups(what, got, key, ref_n, ref_sum, n_col="n", sum_col="s"):
    """Grouped COUNT and f32 SUM against numpy's (bincount over dense
    keys; sums in f64, to SUM_RTOL).  Returns (row order by key, keys
    present, largest relative error of a sum)."""
    present = np.flatnonzero(ref_n)
    order = np.argsort(got[key])
    if not np.array_equal(got[key][order], present):
        raise AssertionError(f"{what}: group keys differ from numpy")
    if not np.array_equal(got[n_col][order], ref_n[present]):
        raise AssertionError(f"{what}: COUNT differs from numpy")
    total = np.asarray(got[sum_col], np.float64)[order]
    rel = np.abs(total - ref_sum[present]) / np.abs(ref_sum[present])
    if not rel.max() <= SUM_RTOL:       # also catches NaN / inf
        raise AssertionError(
            f"{what}: SUM off by {rel.max():.3g} relative (> {SUM_RTOL})")
    return order, present, float(rel.max())


# -- phase: sql --------------------------------------------------------------

def phase_sql(ctx, n, n_dim, seed, workdir):
    from dryad_tpu import sql
    from dryad_tpu.ops.pallas_kernels import pallas_active
    rng = np.random.default_rng([seed, 2])
    fact = {"dkey": rng.integers(0, n_dim, size=n, dtype=np.int32),
            "amount": rng.random(n, dtype=np.float32) * np.float32(100),
            "qty": rng.integers(1, 10, size=n, dtype=np.int32),
            "price": rng.integers(0, 1_000_000, size=n, dtype=np.int32),
            "shipdate": rng.integers(0, 2**31, size=n, dtype=np.int32)}
    dim = {"dkey": rng.permutation(n_dim).astype(np.int32),
           "region": rng.integers(0, N_REGIONS, size=n_dim, dtype=np.int32),
           "active": (rng.random(n_dim) < 0.5).astype(np.int32)}
    fact_path = os.path.join(workdir, "fact")
    dim_path = os.path.join(workdir, "dim")
    nbytes = _write_input(ctx, fact_path, fact, n)
    nbytes += _write_input(ctx, dim_path, dim, n_dim)

    cat = sql.Catalog().register_store("fact", fact_path) \
        .register_store("dim", dim_path)
    t0 = time.time()
    got = sql.query(ctx, cat, SQL).collect()
    query_s = time.time() - t0

    # numpy reference (f64 sums)
    region_of = np.empty(n_dim, np.int32)
    active_of = np.empty(n_dim, np.int32)
    region_of[dim["dkey"]] = dim["region"]
    active_of[dim["dkey"]] = dim["active"]
    keep = (active_of[fact["dkey"]] == 1) & (fact["qty"] > 2)
    reg = region_of[fact["dkey"]][keep]
    ref_n = np.bincount(reg, minlength=N_REGIONS)
    ref_sum = np.bincount(reg, weights=fact["amount"][keep].astype(
        np.float64), minlength=N_REGIONS)
    ref_max = np.full(N_REGIONS, -1, np.int64)
    np.maximum.at(ref_max, reg, fact["price"][keep])
    order, present, rel = _check_groups("sql", got, "region", ref_n, ref_sum,
                                        sum_col="total")
    if not np.array_equal(got["top"][order], ref_max[present]):
        raise AssertionError("sql: MAX differs from numpy")

    mode = pallas_active()
    calls = sum(t.count("tpu_custom_call") for t in _compiled_texts(ctx))
    if ctx.mesh.devices.flat[0].platform == "tpu":
        if mode != "compiled":
            raise AssertionError(f"sql: pallas_active() is {mode!r} on a TPU")
        if not calls:
            raise AssertionError(
                "sql: no stage program holds a tpu_custom_call")
    return {"rows": n, "dim_rows": n_dim, "groups": int(len(present)),
            "rows_after_where": int(keep.sum()),
            "input_device_bytes": nbytes,
            "query_wall_s": round(query_s, 3),
            "sum_max_rel_err": rel, "sum_rtol": SUM_RTOL,
            "pallas_active": mode, "tpu_custom_calls": calls}


# -- phase: exchange over four chips ----------------------------------------

def phase_exchange(ctx, n, seed, workdir):
    """Sampled range exchange (the sort of phase 1) and hash exchange
    (group_by count + sum) over the mesh; checks the results, the
    collective in the compiled text, and that the data really spread."""
    parts = ctx.nparts
    rng = np.random.default_rng([seed, 3])
    keys, payload = _gen_terasort(rng, n)
    src = os.path.join(workdir, "x_sort_in")
    dst = os.path.join(workdir, "x_sort_out")
    nbytes = _write_input(
        ctx, src, {"key": (keys, np.full(n, KEY_LEN, np.int32)),
                   "payload": payload}, n)
    t0 = time.time()
    # cache() pins the result on the devices (ooc_restream_cache is off
    # in this phase's Context), so its placement can be inspected
    result = ctx.from_store(src).order_by([("key", False)]).cache()
    result.to_store(dst)
    sort_s = time.time() - t0
    shards = result.node.data.batch.columns["payload"].addressable_shards
    devices = sorted({str(s.device) for s in shards})
    if len(devices) != parts:
        raise AssertionError(
            f"exchange: output sits on {len(devices)} devices, not {parts}")
    got, counts = _read_output(ctx, dst)
    if (counts <= 0).any() or int(counts.sum()) != n:
        raise AssertionError(
            f"exchange: partition rows {counts.tolist()} (expected every "
            f"partition non-empty, {n} in all)")
    _check_sorted(keys, payload, got)
    del result, got
    sort_a2a = sum(t.count("all-to-all") for t in _compiled_texts(ctx))

    n_groups = max(parts, n // 16)
    gk = rng.integers(0, n_groups, size=n, dtype=np.int32)
    gv = rng.random(n, dtype=np.float32)
    gsrc = os.path.join(workdir, "x_group_in")
    nbytes_g = _write_input(ctx, gsrc, {"k": gk, "v": gv}, n)
    t0 = time.time()
    grouped = ctx.from_store(gsrc).group_by(
        ["k"], {"n": ("count", None), "s": ("sum", "v")}).cache()
    out = grouped.collect()
    group_s = time.time() - t0
    gcounts = np.asarray(grouped.node.data.counts)
    gshards = grouped.node.data.batch.columns["n"].addressable_shards
    if len({str(s.device) for s in gshards}) != parts:
        raise AssertionError("exchange: group output is not on every device")
    ref_n = np.bincount(gk, minlength=n_groups)
    ref_s = np.bincount(gk, weights=gv.astype(np.float64),
                        minlength=n_groups)
    _order, present, rel = _check_groups("exchange", out, "k", ref_n, ref_s)
    if (gcounts <= 0).any() or int(gcounts.sum()) != len(present):
        raise AssertionError(
            f"exchange: group partition rows {gcounts.tolist()} (expected "
            f"every partition non-empty, {len(present)} groups in all)")
    a2a = sum(t.count("all-to-all") for t in _compiled_texts(ctx))
    if parts > 1 and not (sort_a2a and a2a > sort_a2a):
        raise AssertionError(
            f"exchange: all-to-all in compiled text: sort {sort_a2a}, "
            f"group {a2a - sort_a2a}")
    return {"rows": n, "rows_per_chip": n // parts, "groups": len(present),
            "input_device_bytes": nbytes, "group_input_device_bytes":
            nbytes_g, "sort_wall_s": round(sort_s, 3),
            "group_wall_s": round(group_s, 3),
            "out_devices": devices,
            "sort_partition_rows": counts.tolist(),
            "group_partition_rows": gcounts.tolist(),
            "all_to_all_in_sort_text": sort_a2a,
            "all_to_all_in_group_text": a2a - sort_a2a,
            "sum_max_rel_err": rel}


# -- driver ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=1 << 24,
                    help="rows per phase input (per chip with --chips 4)")
    ap.add_argument("--dim-rows", type=int, default=1 << 16)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the exchange phase, on four chips")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="cpu: rehearsal only, reported as cpu")
    args = ap.parse_args(argv)

    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={args.chips}"
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = \
                (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()
    import jax

    devices = jax.devices()
    if devices[0].platform != args.platform:
        print(f"chip_smoke: JAX found {devices[0].platform!r} devices, "
              f"not {args.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    devices = devices[:args.chips]

    from dryad_tpu import Context, make_mesh, native
    from dryad_tpu.ops.pallas_kernels import pallas_active
    from dryad_tpu.utils.config import JobConfig

    meter = Meter()
    events: list = []
    # ooc_restream_cache off: the exchange phase pins its result on the
    # devices with cache() to look at where it landed
    ctx = Context(mesh=make_mesh(devices), event_log=events.append,
                  config=JobConfig(ooc_restream_cache=False))
    emit(config={"seed": args.seed, "rows": args.rows,
                 "dim_rows": args.dim_rows, "chips": args.chips},
         jax=jax.__version__, pallas_active=pallas_active(),
         native_io=native.available(),
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         compile_cache_dir_from_env="JAX_COMPILATION_CACHE_DIR"
         in os.environ)

    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        if args.chips == 4:
            run_phase("exchange", meter, events, phase_exchange, ctx,
                      args.rows * args.chips, args.seed, workdir)
        else:
            run_phase("sort", meter, events, phase_sort, ctx, args.rows,
                      args.seed, workdir)
            run_phase("sql", meter, events, phase_sql, ctx, args.rows,
                      args.dim_rows, args.seed, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    stats = devices[0].memory_stats() or {}
    emit(compile_s_total=round(meter.compile_s, 3), compiles=meter.compiles,
         persistent_cache_hits=meter.hits,
         persistent_cache_misses=meter.misses,
         peak_device_bytes=stats.get("peak_bytes_in_use"))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
