"""The trace reduction on a hand-made trace whose answers are known."""
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


def small_trace():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        return ProfileData.from_text_proto(f.read())


def test_op_names_lose_their_hlo_text():
    assert tr.short("%fusion.4 = f32[8]{0} fusion(f32[8] %p), kind=kLoop") \
        == "fusion.4"
    assert tr.short("jit_train_chunk") == "jit_train_chunk"
    assert tr.per_name_ns([(0, 2, "%a.1 = f32[] x"), (5, 6, "%a.1 = s32[] y")]
                          ) == {"a.1": 3}


def test_union_merges_overlaps_and_keeps_gaps():
    ev = [(0, 10, "a"), (5, 15, "b"), (20, 30, "a")]
    assert tr.union(ev) == [(0, 15), (20, 30)]
    assert tr.busy_ns(ev, 0, 40) == 25
    assert tr.busy_ns(ev, 8, 25) == 12
    assert tr.idle_gaps(ev, 0, 40) == [(15, 20), (30, 40)]


def test_summary_of_the_small_trace():
    s = tr.summarize(small_trace(), program="train_chunk", chips=1)
    assert s["window_s"] == pytest.approx(100e-6)
    # ops [10,25] + [26,40] + [62,90] + [95,96] us; chip 1 is not read
    assert s["busy_s"] == pytest.approx(58e-6)
    ops = dict(s["device_ops"])
    assert ops == pytest.approx({"fusion.1": 52e-6, "fusion.2": 10e-6,
                                 "copy.3": 1e-6})
    assert s["program_runs"] == 2
    assert s["program_s"] == pytest.approx(58e-6)
    assert s["program_gaps_s"] == pytest.approx([22e-6])
    # the checkpoint span covers most of the gap and is the shortest such
    assert s["program_gap_spans"] == ["save"]
    gaps = s["idle_gaps"]
    assert [g[0] for g in gaps[:2]] == ["save", "fit_stream"]
    assert [g[1] for g in gaps] == pytest.approx(
        [22e-6, 10e-6, 5e-6, 4e-6, 1e-6])


def test_two_chips_average_their_busy_time():
    s = tr.summarize(small_trace(), program="train_chunk", chips=2)
    assert s["busy_s"] == pytest.approx((58e-6 + 100e-6) / 2)


def test_dropped_device_events_are_found():
    ops = [(10, 20, "a"), (20, 40, "b"), (62, 70, "c")]
    assert tr.dropped_runs([(10, 40, "p"), (62, 5e7, "p")], ops) \
        == [(62, 5e7)]
    assert tr.dropped_runs([(10, 40, "p"), (80, 3e6, "p")], ops) \
        == [(80, 3e6)]
    # events lost at the start of a run, as a ring buffer loses them
    assert tr.dropped_runs([(0, 5e7, "p")], [(4.9e7, 5e7, "x")]) \
        == [(0, 5e7)]
    # a short run without operations of its own loses nothing that counts
    assert tr.dropped_runs([(10, 40, "p"), (50, 60, "p")], ops) == []
    assert tr.dropped_runs([(10, 40, "p"), (62, 70, "p")], ops) == []


def test_a_trace_that_dropped_device_events_is_refused():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        text = f.read()
    # the window runs on to 10 ms, and the second chunk program with it,
    # but its operation stops at 90 us
    text = text.replace(
        "events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }",
        "events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }")
    text = text.replace(
        "events { metadata_id: 1 offset_ps: 62000000 duration_ps: 28000000 }",
        "events { metadata_id: 1 offset_ps: 62000000 duration_ps: 9000000000 }")
    with pytest.raises(ValueError, match="dropped device events"):
        tr.summarize(ProfileData.from_text_proto(text), program="x")


def test_a_trace_without_the_window_span_is_refused():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "small_trace.pbtxt")) as f:
        text = f.read().replace('"bench_window"', '"elsewhere"')
    with pytest.raises(ValueError, match="bench_window"):
        tr.summarize(ProfileData.from_text_proto(text), program="x")


def test_cut_of_a_chip_trace():
    """A boundary between two chunk programs cut from a v5e trace: the
    reduction finds the chip, both programs, the 302.7 ms gap between them
    and the host work in it."""
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "chip_cut.pbtxt")) as f:
        s = tr.summarize(ProfileData.from_text_proto(f.read()),
                         program="train_chunk")
    assert s["window_s"] == pytest.approx(0.303317442)
    assert s["program_runs"] == 2
    assert s["program_gaps_s"] == pytest.approx([0.302717442])
    assert s["program_gap_spans"] == ["Transpose::Execute"]
    assert 0 < s["busy_s"] < 0.001
    assert s["device_ops"][0][0] == "while"
