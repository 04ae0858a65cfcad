"""Drive the rest of a run (everything after the harness's look for a
chip) with the timed path broken underneath, and see ``correct`` come out
false, once for each fault a cell can have:

* an answer altered where it is produced (every cell);
* half of the batch left out (every cell);
* the exchange between chips left out (the four-chip cell);
* a step that returns its state unchanged: the sort that sorts nothing
  (the sort cells).

The same runs unbroken come out true."""

import numpy as np
import pytest

from perfbench import run as R

CELLS = ["sort100_1chip", "tpch_q1_sf2", "sort100_4chip"]


def _run(cell, seed=2**31 + 7):
    return R.run_cell(cell, seed, 0.3, 0, rehearse=True)


@pytest.mark.parametrize("cell", CELLS)
def test_unbroken_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"]["platform"] == "cpu"
    assert {"rows_per_s", "query_s", "setup_s"} <= set(out["metrics"])
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("cell", ["sort100_1chip", "sort100_4chip"])
def test_altered_answer_in_a_written_store(cell, monkeypatch):
    from dryad_tpu.io import store
    orig = store.write_store

    def altered(path, pd, *a, **kw):
        orig(path, pd, *a, **kw)
        if "/out-" in path:
            fn = store._part_path(path, 0)
            with open(fn, "r+b") as f:       # first byte of the first key
                b = f.read(1)
                f.seek(0)
                f.write(bytes([b[0] ^ 0x5A]))
    monkeypatch.setattr(store, "write_store", altered)
    out = _run(cell)
    assert out["correct"] is False
    assert out["compared"]["rows_misplaced"]["value"] >= 1


def test_altered_answer_in_a_collected_result(monkeypatch):
    from dryad_tpu.api import dataset
    orig = dataset.pdata_to_host

    def altered(pd):
        out = orig(pd)
        if "sum_charge" in out:
            out["sum_charge"] = out["sum_charge"] * np.float32(1.001)
        return out
    monkeypatch.setattr(dataset, "pdata_to_host", altered)
    out = _run("tpch_q1_sf2")
    assert out["correct"] is False
    assert out["compared"]["agg_max_rel_err"]["value"] > 5e-4


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out(cell, monkeypatch):
    from dryad_tpu.api import dataset
    from dryad_tpu.data.columnar import Batch
    from dryad_tpu.exec.data import PData
    orig = dataset.Context.from_store

    def half(self, path, *a, **kw):
        ds = orig(self, path, *a, **kw)
        if "/out-" in path:
            return ds
        pd = ds.node.data
        cut = PData(Batch(pd.batch.columns, pd.batch.count // 2), pd.nparts)
        return self.from_pdata(cut)
    monkeypatch.setattr(dataset.Context, "from_store", half)
    out = _run(cell)
    assert out["correct"] is False


def test_exchange_between_chips_left_out(monkeypatch):
    import jax.numpy as jnp
    from dryad_tpu.parallel import shuffle

    def no_exchange(batch, key, bounds, out_capacity, **kw):
        z = jnp.zeros((), jnp.int32)
        return batch, z, z, z
    monkeypatch.setattr(shuffle, "range_exchange", no_exchange)
    out = _run("sort100_4chip")
    assert out["correct"] is False
    assert out["compared"]["rows_out_of_order"]["value"] >= 1


@pytest.mark.parametrize("cell", ["sort100_1chip", "sort100_4chip"])
def test_sort_that_returns_its_input_unchanged(cell, monkeypatch):
    from dryad_tpu.api import dataset
    monkeypatch.setattr(dataset.Dataset, "order_by",
                        lambda self, keys: self)
    out = _run(cell)
    assert out["correct"] is False
    assert out["compared"]["rows_out_of_order"]["value"] >= 1


def test_no_tpu_exits_non_zero_and_prints_no_result(capsys):
    assert R.main(["--workload", "sort100_1chip", "--seed", "1",
                   "--seconds", "1", "--trace", "0"]) == 1
    assert capsys.readouterr().out == ""
