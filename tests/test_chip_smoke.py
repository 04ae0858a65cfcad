"""chip_smoke.py's CPU rehearsal form, in-process: the phases run through
the same code the chip run takes (small sizes, ``--platform cpu``), and
the contract's last line has its shape.  Without ``--platform cpu`` the
script must refuse a machine with no TPU before any phase."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _run(monkeypatch, capsys, argv):
    import chip_smoke

    # main() exports these for a fresh interpreter; keep the suite's own
    monkeypatch.setenv("JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS",
                                                       "cpu"))
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    rc = chip_smoke.main(argv)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(ln) for ln in lines]


@pytest.mark.parametrize("argv,phases", [
    (["--rows", "8192", "--dim-rows", "512"], ["sort", "sql"]),
    (["--chips", "4", "--rows", "2048"], ["exchange"]),
])
def test_chip_smoke_cpu_rehearsal(devices8, monkeypatch, capsys, argv,
                                  phases):
    rc, recs = _run(monkeypatch, capsys, ["--platform", "cpu"] + argv)
    assert rc == 0
    chips = 4 if "--chips" in argv else 1
    assert recs[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": recs[-1]["device"]["kind"],
        "count": chips}}
    done = [r for r in recs if "phase" in r]
    assert [r["phase"] for r in done] == phases
    for r in done:
        assert r["ok"] and r["compile_s"] >= 0 and r["stages"]
        assert r["rows"] == int(argv[argv.index("--rows") + 1]) * chips
    if chips == 4:
        x = done[0]
        assert len(x["out_devices"]) == 4
        assert sum(x["sort_partition_rows"]) == x["rows"]
        assert x["all_to_all_in_sort_text"] and x["all_to_all_in_group_text"]


def test_chip_smoke_refuses_a_machine_without_tpu(devices8, monkeypatch,
                                                  capsys):
    """The driver's form (no arguments) on the CPU backend: non-zero,
    before any phase, and no result line."""
    rc, recs = _run(monkeypatch, capsys, [])
    assert rc != 0
    assert recs == []
