"""Kernel-smoke wiring: keep ``bench.py --smoke-kernels`` runnable as a
pytest so the kernel A/B rows (and the measured-slot wire arithmetic)
can't rot.  Its walls are whatever backend runs the test — never a
device number."""

import json

import numpy as np
import pytest


@pytest.mark.slow
def test_smoke_kernels_runs(tmp_path, monkeypatch):
    """bench.py --smoke-kernels end-to-end at toy size: every row
    present and finite, wire utilization improves wave-1 -> wave-2, and
    the trend record lands."""
    import bench

    monkeypatch.setenv("BENCH_KERNEL_ROWS", "8192")
    monkeypatch.setenv("BENCH_KERNEL_KLO", "2")
    monkeypatch.setenv("BENCH_KERNEL_KHI", "6")
    monkeypatch.setenv("BENCH_KERNEL_COPY_MB", "16")
    monkeypatch.setenv("BENCH_TREND_PATH", str(tmp_path / "trend.jsonl"))
    out = bench.smoke_kernels(
        out_path=str(tmp_path / "BENCH_kernels.json"), quiet=True)
    rows = out["rows"]
    for name in ("multikey_sort", "exchange_pack", "exchange_unpack",
                 "join_gather"):
        assert np.isfinite(rows[name]["new_s"]), name
        assert rows[name]["new_s"] >= 0, name
    wu = rows["wire_utilization_inmem"]
    assert wu["exchange_legs"] >= 2
    assert wu["wave2_measured_pct"] > wu["wave1_structural_pct"]
    trend = [json.loads(ln) for ln in
             open(tmp_path / "trend.jsonl").read().splitlines()]
    assert trend and trend[-1]["app"] == "bench-kernels"
