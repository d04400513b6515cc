"""The control (the reference in bfloat16 in the program's place) fails a
cell's comparison: at the tiny rehearsal sizes here, as at the cells' own
sizes on the chip (PERF.md gives those readings)."""
import pytest

from bench.tests._runs import control

CELLS = ("mnist-ovr-train", "mnist-ovr-serve")


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    read = control(cell, 3_000_000_019)
    assert read, "the control printed no numbers"
    assert any(v > lim for v, lim in read.values()), read
