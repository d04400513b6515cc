"""``repro.obs``: span trees across threads, the bounded ring and its drop
accounting, and the twin each span leaves in a profiler trace."""
import glob
import threading

import jax
import numpy as np
import pytest

from repro import obs


def _mine(root):
    r = obs.RING.records(root)
    return {n: i for i, n in enumerate(r["name"])}, r


def test_nesting_sets_parent_and_root():
    with obs.span("t.outer", rows=4) as outer:
        with obs.span("t.mid") as mid:
            with obs.span("t.inner") as inner:
                inner.set(bytes=12)
    at, r = _mine(outer.span_id)
    assert sorted(at) == ["t.inner", "t.mid", "t.outer"]
    assert r["parent_id"][at["t.outer"]] == 0
    assert r["parent_id"][at["t.mid"]] == outer.span_id
    assert r["parent_id"][at["t.inner"]] == mid.span_id
    assert set(r["root_id"]) == {outer.span_id}
    assert r["rows"][at["t.outer"]] == 4 and r["rows"][at["t.inner"]] == -1
    assert r["bytes"][at["t.inner"]] == 12
    # children lie inside their parents, on one thread
    for child, parent in (("t.mid", "t.outer"), ("t.inner", "t.mid")):
        assert r["start_ns"][at[parent]] <= r["start_ns"][at[child]]
        assert r["end_ns"][at[child]] <= r["end_ns"][at[parent]]
    assert set(r["thread"]) == {threading.current_thread().name}


def test_root_carries_to_a_worker_thread():
    with obs.span("t.call") as call:
        ctx = obs.context()

        def work():
            with obs.attach(ctx):
                with obs.span("t.work"):
                    pass
            with obs.span("t.alone"):     # detached again: its own root
                pass

        t = threading.Thread(target=work, name="t-worker")
        t.start()
        t.join(30)
        assert not t.is_alive()
    at, r = _mine(call.span_id)
    assert sorted(at) == ["t.call", "t.work"]
    assert r["parent_id"][at["t.work"]] == call.span_id
    assert r["thread"][at["t.work"]] == "t-worker"
    alone = obs.RING.records()
    i = np.flatnonzero(alone["name"] == "t.alone")[-1]
    assert alone["root_id"][i] == alone["span_id"][i]
    assert obs.context() is None


def test_discarded_span_leaves_no_record():
    with obs.span("t.kept") as kept:
        with obs.span("t.dropped") as d:
            d.discard()
    at, _ = _mine(kept.span_id)
    assert sorted(at) == ["t.kept"]


def test_record_across_threads_hangs_under_its_root():
    root = obs.new_root()
    out = {}

    def finish():
        obs.record("t.request", out["start"], obs.now_ns(),
                   root=root.root_id, ticket=7)

    out["start"] = obs.now_ns()
    t = threading.Thread(target=finish, name="t-finisher")
    t.start()
    t.join(30)
    assert not t.is_alive()
    r = obs.RING.records(root.root_id)
    assert list(r["name"]) == ["t.request"]
    assert r["parent_id"][0] == root.root_id
    assert r["ticket"][0] == 7 and r["thread"][0] == "t-finisher"
    assert r["start_ns"][0] == out["start"] <= r["end_ns"][0]


def test_ring_is_bounded_and_counts_drops():
    ring = obs.Ring(capacity=8)
    nbytes = ring.nbytes
    for i in range(5):
        ring.append("a", i, i + 1, i + 1, 0, 1, "t", {"k": i})
    for i in range(5, 12):
        ring.append("b", i, i + 1, i + 1, 0, 2, "t", {})
    got = ring.records()
    assert ring.dropped == 4 and ring.nbytes == nbytes
    assert list(got["start_ns"]) == list(range(4, 12))     # oldest first
    assert list(got["name"]) == ["a"] + ["b"] * 7
    # root 1 lost four of its records: its reader gets nothing, never a
    # biased part; root 2 lost none
    assert ring.records(1) is None
    assert list(ring.records(2)["start_ns"]) == list(range(5, 12))
    with pytest.raises(ValueError, match="attributes"):
        ring.append("c", 0, 1, 1, 0, 3, "t", dict(a=1, b=2, c=3, d=4))


def test_chain_moves_in_as_one_record_per_stage():
    ring = obs.Ring(capacity=2)
    names = ("c.one", "c.two", "c.three")
    ring.append_chain(names, (10, 11, 13, 16), 5, "t", {"ticket": 4})
    ring.append_chain(names[:1], (20, 21), 6, "t", {})
    got = ring.records(5)
    assert list(got["name"]) == list(names)
    assert list(got["start_ns"]) == [10, 11, 13]
    assert list(got["end_ns"]) == [11, 13, 16]
    assert set(got["ticket"]) == {4} and set(got["parent_id"]) == {5}
    assert len(set(got["span_id"])) == 3
    assert list(ring.records(6)["end_ns"]) == [21]
    assert list(ring.records(5)["span_id"]) == list(got["span_id"])
    # a slot holds one chain: two more push both out, four records
    ring.append_chain(names, (30, 31, 32, 33), 7, "t", {"ticket": 9})
    ring.append_chain(names, (40, 41, 42, 43), 7, "t", {"ticket": 9})
    assert ring.records(5) is None and ring.records(6) is None
    assert ring.dropped == 4
    assert len(ring.records(7)["name"]) == 6


def test_concurrent_appends_lose_nothing():
    """Threads stage records without the lock while others move them in:
    every record arrives once."""
    import sys
    ring = obs.Ring(capacity=1 << 16)
    n_threads, n_each = 8, 3000

    def work(t):
        for i in range(n_each):
            if i % 2:
                ring.append("s", i, i + 1, -1 - t * n_each - i, 0, t, "t",
                            {})
            else:
                ring.append_chain(("c",), (i, i + 1), t, "t", {"k": i})

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    got = ring.records()
    assert ring.dropped == 0 and len(got["name"]) == n_threads * n_each
    assert len(set(got["span_id"])) == n_threads * n_each
    for t in range(n_threads):
        mine = got["root_id"] == t
        assert sorted(got["start_ns"][mine]) == list(range(n_each))


def test_process_ring_capacity_and_size():
    assert obs.RING.capacity >= 1 << 20
    assert obs.RING.nbytes == obs.RING.capacity * 72


def test_span_matches_its_trace_twin(tmp_path):
    """A span's start and end in memory lie within 50 us of the host event
    its annotation leaves in the trace (trace times are relative to the
    session's ``profile_start_time``)."""
    jax.numpy.ones(2).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        spans = []
        for i in range(5):
            with obs.span("t.twin", k=i) as s:
                threading.Event().wait(0.002)
            spans.append(s)
    finally:
        jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    t0 = next(v for p in pd.planes for k, v in p.stats
              if k == "profile_start_time")
    twins = sorted((e.start_ns, e.end_ns) for p in pd.planes
                   for line in p.lines for e in line.events
                   if e.name == "t.twin")
    assert len(twins) == len(spans)
    for s, (a, b) in zip(spans, twins):
        assert abs(s.start_ns - (t0 + a)) < 50_000
        assert abs(s.end_ns - (t0 + b)) < 50_000
