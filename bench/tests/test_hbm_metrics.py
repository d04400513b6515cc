"""The two readers of the HBM train-step kernel, on a hand-made trace
summary: ``hbm_step_us`` sums the device time of the ops named
``train_step_hbm_pallas*`` per step, ``hbm_roofline`` puts the step's least
time over it; on a trace without that kernel (the parent, or the MNIST
cell's whole-block kernel) both find nothing and raise nothing."""
import contextlib
import io

import pytest

from bench import common, run, work
from bench.tests._runs import result


def _record(device_ops, steps=1000):
    w = work.window_work(steps=steps, merges=16_600, inserts=16_600, C=10,
                         S=4104, d=784, B=8)
    return {"kind": "train", "steps": steps, "work": w,
            "device": {"kind": "TPU v5 lite"},
            "trace": {"busy_s": 1.2, "window_s": 2.0,
                      "device_ops": device_ops}}


def test_hbm_step_us_and_roofline_by_hand(capsys):
    rec = _record([["while", 1.1], ["train_step_hbm_pallas.3", 0.9],
                   ["train_step_hbm_pallas.4", 0.1], ["copy.4", 0.05]])
    us = common.load_metric("hbm_step_us").read(rec)
    assert us == pytest.approx(1000.0)             # 1.0 s over 1,000 steps
    w = rec["work"]
    least_s = max(w["ops"] / 1000 / 197e12, w["bytes"] / 1000 / 819e9)
    share = common.load_metric("hbm_roofline").read(rec)
    assert share == pytest.approx(100.0 * least_s / 1e-3)
    assert 0.0 < share < 100.0
    assert "hbm_roofline: bound by bytes" in capsys.readouterr().out


@pytest.mark.parametrize("ops", [
    [["while", 1.1], ["train_step_pallas.3", 1.0]],   # the whole-block kernel
    [],
])
def test_hbm_readers_find_nothing_without_the_kernel(ops):
    rec = _record(ops)
    assert common.load_metric("hbm_step_us").read(rec) is None
    assert common.load_metric("hbm_roofline").read(rec) is None
    untraced = dict(rec, trace=None)
    assert common.load_metric("hbm_step_us").read(untraced) is None
    assert common.load_metric("hbm_roofline").read(untraced) is None


def _rehearse(*extra):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", "mnist8m-ovr-train", "--seed", "1",
                       "--seconds", "5", "--rehearse", *extra])
    assert rc == 0
    return result(buf.getvalue())


def test_mnist8m_cell_rehearses():
    """The cell at its rehearsal sizes on the CPU: correct, and its
    training metrics reported (the CPU path runs no HBM kernel, so the two
    readers above find nothing there)."""
    out = _rehearse()
    assert out["correct"], out["checks"]
    assert out["metrics"]["train_rows_per_s"]["value"] > 0.0
    traced = _rehearse("--trace", "1")
    assert traced["correct"], traced["checks"]
    assert traced["metrics"]["ckpt_write_ms"]["value"] >= 0.0
    assert "hbm_step_us" not in traced["metrics"]
