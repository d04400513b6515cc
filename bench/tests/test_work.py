"""Required work of a training step, counted by hand at one small shape,
and the peaks table."""
import pytest

from bench import work
from bench.peaks import peaks


def test_step_insert_merge_counts_by_hand():
    # C=2 classes, S=4 slots, d=3 features, B=2 rows
    ops, byt = work.step_work(C=2, S=4, d=3, B=2)
    # per class: margin rows 2*4*(2*3+4)=80, margin 2*2*4=16,
    # Gram block 2*2*10=40, shrink 4 -> 140; two classes
    assert ops == 280
    # per class: bank 4*3 + alpha read and write 2*4 = 20 floats;
    # batch 2*3 floats once
    assert byt == (2 * 20 + 6) * 4
    assert work.insert_work(S=4, d=3) == (0.0, (3 + 8) * 4)
    # scoring 20*4, merged point 3*3, its kernel row 6*4
    assert work.merge_work(S=4, d=3) == (80 + 9 + 24, (8 + 3 + 16) * 4)


def test_window_work_adds_its_parts():
    w = work.window_work(steps=5, merges=3, inserts=7, C=2, S=4, d=3, B=2)
    assert w == {"ops": 5 * 280 + 3 * 113, "bytes": 5 * 184 + 7 * 44
                 + 3 * 108, "steps": 5}


def test_peaks_of_the_v5e_and_an_unknown_chip():
    pk = peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
