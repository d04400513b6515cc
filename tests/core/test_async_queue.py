"""Continuous-batching serve queue + versioned model bank (ISSUE 7 gates):

  * ``AsyncBatchQueue`` labels are bitwise one direct ``predict_labels``
    call for ANY arrival pattern (randomized sizes, interleaved takes);
  * ``ModelBank`` versions are monotone, reads are atomic pairs, and the
    queue hot-swaps a newly published model without draining;
  * a warmed queue never recompiles on its first real submit (the PR 4
    jit-cache-key footgun, now a regression gate for both queues);
  * a dispatcher failure re-raises on the caller's thread — never a hang.
"""
import threading

import jax
import numpy as np
import pytest

from repro.core import (AsyncBatchQueue, BatchQueue, BSGDConfig, ModelBank,
                        MulticlassSVMConfig, default_buckets, export_model,
                        fit_multiclass, pad_bucket, predict_labels)
from repro.data import make_blobs_multiclass

N_CLASSES, DIM = 4, 8
X, Y = make_blobs_multiclass(jax.random.PRNGKey(0), 640, DIM,
                             n_classes=N_CLASSES, sep=2.5)
X = np.asarray(X, np.float32)
CFG = MulticlassSVMConfig.create(N_CLASSES, budget=16, lambda_=1e-3,
                                 gamma=0.5, batch_size=8)
MODEL = export_model(fit_multiclass(CFG, X, np.asarray(Y), epochs=1, seed=0),
                     0.5)


def test_pad_bucket_is_the_shared_rule():
    buckets = (8, 16, 32, 64)
    assert [pad_bucket(n, buckets) for n in (1, 8, 9, 16, 33, 64, 99)] == \
        [8, 8, 16, 16, 64, 64, 64]
    assert default_buckets(64, 8) == buckets
    # both queues derive their pad targets from it
    assert BatchQueue(MODEL, max_batch=64)._bucket_for(9) == 16
    with AsyncBatchQueue(MODEL, max_batch=64) as q:
        assert q.buckets == buckets


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_async_queue_bitwise_any_arrivals(seed, watchdog):
    """Randomized ragged arrivals (incl. empty and > max_batch requests),
    interleaved takes: labels bitwise one direct call."""
    watchdog(300)
    rng = np.random.default_rng(seed)
    sizes = [int(s) for s in rng.integers(0, 97, size=24)]
    with AsyncBatchQueue(MODEL, max_batch=64, min_bucket=8) as q:
        q.warmup()
        tickets, off = [], 0
        got = {}
        for i, s in enumerate(sizes):
            tickets.append(q.submit(X[off % 512:off % 512 + s]))
            off += s
            if i % 5 == 4:                    # interleave takes mid-stream
                tk = tickets[len(got)]        # earliest not-yet-taken ticket
                got[tk] = q.take(tk, timeout=60.0)
        q.drain(timeout=60.0)
        for t in tickets:
            if t not in got:
                got[t] = q.take(t, timeout=60.0)
        versions = dict(q.stats["versions"])
    ref_rows = np.concatenate(
        [X[o % 512:o % 512 + s] for o, s in
         zip(np.cumsum([0] + sizes[:-1]), sizes)]) if sum(sizes) else \
        np.zeros((0, DIM), np.float32)
    direct = np.asarray(predict_labels(MODEL, ref_rows))
    labels = np.concatenate([got[t] for t in tickets])
    assert (labels == direct).all()
    assert not versions                       # fixed model: no bank versions


@pytest.mark.parametrize("seed", [0, 1])
def test_async_never_more_dispatches_than_sync(seed, watchdog):
    """The ISSUE 9 regression gate: for the same submit-all-then-drain
    trace, waiter-gated dispatch must coalesce at least as well as the sync
    queue — MORE microbatches would mean the async path re-introduced a
    per-row trickle of dispatches, coalescing worse than the sync queue."""
    watchdog(300)
    from repro.core.predict import drive_trace, ragged_trace_sizes
    rng = np.random.default_rng(seed)
    sizes = ragged_trace_sizes(512, 64, rng)
    sync = drive_trace(MODEL, X[:512], sizes, max_batch=64, queue="sync")
    asyn = drive_trace(MODEL, X[:512], sizes, max_batch=64, queue="async")
    assert asyn["microbatches"] <= sync["microbatches"], \
        (asyn["microbatches"], sync["microbatches"])


def test_take_ungates_partial_batch(watchdog):
    """A live caller blocked in take() must not wait for a full max_batch:
    the waiter un-gates dispatch of whatever is pending."""
    watchdog(120)
    with AsyncBatchQueue(MODEL, max_batch=64) as q:
        q.warmup()
        t1 = q.submit(X[:5])                  # far below max_batch
        labels = q.take(t1, timeout=30.0)     # must dispatch, not hang
    assert (labels == np.asarray(predict_labels(MODEL, X[:5]))).all()


def test_async_queue_warmup_never_recompiles():
    """The warmed AOT-executable cache covers every bucket; real traffic
    adds no new compilations (the PR 4 static-arg cache-key footgun)."""
    with AsyncBatchQueue(MODEL, max_batch=64, min_bucket=8) as q:
        q.warmup()
        n_compiled = len(q._compiled)
        assert n_compiled == len(q.buckets)
        for s in (3, 9, 17, 64, 130):         # every bucket + wrap-around
            q.submit(X[:s])
        q.drain(timeout=60.0)
        assert len(q._compiled) == n_compiled


def test_sync_queue_warmup_never_recompiles():
    """Same gate for BatchQueue via the jit cache itself:
    ``predict_labels._cache_size()`` must not grow on first real submit."""
    q = BatchQueue(MODEL, max_batch=64, min_bucket=8)
    q.warmup()
    before = predict_labels._cache_size()
    t1 = q.submit(X[:37])
    q.drain()
    q.take(t1)
    assert predict_labels._cache_size() == before, \
        "warmed BatchQueue recompiled on its first real submit"


def test_model_bank_versioning_and_atomicity():
    bank = ModelBank()
    with pytest.raises(LookupError):
        bank.current()
    assert bank.version == 0
    with pytest.raises(TimeoutError):
        bank.wait(1, timeout=0.05)
    assert bank.publish(MODEL) == 1
    v, m = bank.current()
    assert v == 1 and m is MODEL
    # concurrent publishes: versions stay strictly monotone, reads always
    # see a consistent (version, model) pair — checked on the MAIN thread
    # over the reader's recorded (version, model) stream
    models = {v: export_model(
        fit_multiclass(CFG, X, np.asarray(Y), epochs=1, seed=v), 0.5)
        for v in range(2, 6)}
    seen, stop = [], threading.Event()

    def reader():
        while not stop.is_set():
            seen.append(bank.current())

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    for m in models.values():
        bank.publish(m)
    stop.set()
    t.join(5.0)
    assert bank.version == 5
    by_version = {1: MODEL, **models}
    last = 0
    for v, m in seen:
        assert v >= last, "version went backwards"
        assert m is by_version[v], f"torn read at version {v}"
        last = v
    # wait() returns once the version lands
    assert bank.wait(5, timeout=1.0)[0] == 5


def test_hot_swap_mid_stream_without_drain(watchdog):
    """A version published while the queue is live is picked up at the next
    microbatch — no drain, per-microbatch version consistency."""
    watchdog(300)
    model_b = export_model(
        fit_multiclass(CFG, X, np.asarray(Y), epochs=1, seed=99), 0.5)
    assert not np.array_equal(np.asarray(MODEL.alpha),
                              np.asarray(model_b.alpha))
    bank = ModelBank(MODEL)
    with AsyncBatchQueue(bank, max_batch=64) as q:
        q.warmup()
        t1 = q.submit(X[:100])
        q.drain(timeout=60.0)                 # phase 1 fully scored by v1
        bank.publish(model_b)                 # hot-swap, queue stays open
        t2 = q.submit(X[100:200])
        q.drain(timeout=60.0)
        l1, l2 = q.take(t1), q.take(t2)
        versions = dict(q.stats["versions"])
    assert (l1 == np.asarray(predict_labels(MODEL, X[:100]))).all()
    assert (l2 == np.asarray(predict_labels(model_b, X[100:200]))).all()
    assert set(versions) == {1, 2}, versions


def test_bank_queue_rejects_predict_fn():
    with pytest.raises(ValueError, match="ModelBank"):
        AsyncBatchQueue(ModelBank(MODEL), predict_fn=lambda xb: xb)


def test_dispatcher_error_surfaces_no_hang(watchdog):
    """A predict_fn that raises on the dispatcher thread fails take/drain
    and subsequent submits on the CALLER's thread — never a hang."""
    watchdog(120)

    def boom(xb):
        raise RuntimeError("device lost")

    q = AsyncBatchQueue(MODEL, max_batch=64, predict_fn=boom)
    t1 = q.submit(X[:10])
    with pytest.raises(RuntimeError, match="dispatcher failed"):
        q.drain(timeout=60.0)
    with pytest.raises(RuntimeError, match="dispatcher failed"):
        q.take(t1, timeout=60.0)
    with pytest.raises(RuntimeError, match="dispatcher failed"):
        q.submit(X[:5])
    q.close()


def test_async_queue_edge_requests(watchdog):
    watchdog(120)
    with AsyncBatchQueue(MODEL, max_batch=64) as q:
        t_empty = q.submit(X[:0])
        assert q.take(t_empty, timeout=10.0).shape == (0,)
        with pytest.raises(ValueError, match=r"\(n, dim\)"):
            q.submit(X[0])                    # 1-D row, not (n, dim)
        with pytest.raises(TimeoutError):
            q.take(999, timeout=0.05)         # unknown ticket times out
    with pytest.raises(RuntimeError, match="closed"):
        q.submit(X[:1])                       # after close()
    with pytest.raises(ValueError):
        AsyncBatchQueue(MODEL, max_batch=0)


# ---- overload protection: typed shedding, never hangs (DESIGN.md §16) ----


def test_submit_validates_rows(watchdog):
    """Malformed requests fail AT SUBMIT with a clear ValueError — never a
    shape blowup (or a silently poisoned score) inside a fused microbatch."""
    watchdog(120)
    with AsyncBatchQueue(MODEL, max_batch=64) as q:
        with pytest.raises(ValueError, match=r"\(n, dim\)"):
            q.submit(X[0])                          # 1-D
        with pytest.raises(ValueError, match="numeric"):
            q.submit(np.zeros((3, DIM), np.bool_))
        with pytest.raises(ValueError, match="numeric"):
            q.submit(np.array([["a"] * DIM]))
        with pytest.raises(ValueError, match="request dim"):
            q.submit(np.zeros((3, DIM + 1), np.float32))
        bad = X[:3].copy()
        bad[1, 2] = np.nan
        with pytest.raises(ValueError, match="non-finite"):
            q.submit(bad)
        bad[1, 2] = np.inf
        with pytest.raises(ValueError, match="non-finite"):
            q.submit(bad)
        t = q.submit(X[:3])                         # queue still healthy
        assert (q.take(t, timeout=30.0) ==
                np.asarray(predict_labels(MODEL, X[:3]))).all()
    from repro.core import BatchQueue
    bq = BatchQueue(MODEL, max_batch=64)
    with pytest.raises(ValueError, match="non-finite"):
        bq.submit(np.full((2, DIM), np.nan, np.float32))


def test_serve_timeout_is_typed_and_names_the_ticket(watchdog):
    watchdog(120)
    from repro.core import ServeTimeout
    with AsyncBatchQueue(MODEL, max_batch=64) as q:
        with pytest.raises(ServeTimeout, match="ticket 999") as ei:
            q.take(999, timeout=0.05)
        assert isinstance(ei.value, TimeoutError)   # old handlers still work
        assert "in flight" in str(ei.value)
        t = q.submit(X[:4])
        q.take(t, timeout=30.0)

    def slow(xb):
        import time as _t
        _t.sleep(0.5)
        return np.asarray(predict_labels(MODEL, xb))

    with AsyncBatchQueue(MODEL, max_batch=64, predict_fn=slow) as q:
        q.submit(X[:4])
        with pytest.raises(ServeTimeout, match="unresolved"):
            q.drain(timeout=0.05)
        q.drain(timeout=30.0)                       # still completes after


def test_queue_full_sheds_at_submit(watchdog):
    """max_pending bounds the buffer: the overflowing submit raises
    QueueFull IMMEDIATELY and leaves earlier tickets untouched."""
    watchdog(120)
    from repro.core import QueueFull
    with AsyncBatchQueue(MODEL, max_batch=64, max_pending=64) as q:
        t1 = q.submit(X[:40])                       # gate closed: stays pending
        with pytest.raises(QueueFull, match="max_pending=64"):
            q.submit(X[40:75])                      # 40 + 35 > 64
        t2 = q.submit(X[40:60])                     # 40 + 20 fits
        got1, got2 = q.take(t1, timeout=30.0), q.take(t2, timeout=30.0)
        direct = np.asarray(predict_labels(MODEL, X[:60]))
        assert (np.concatenate([got1, got2]) == direct).all()
        t3 = q.submit(X[:30])                       # buffer drained: open again
        q.take(t3, timeout=30.0)
    with pytest.raises(ValueError, match="max_pending"):
        AsyncBatchQueue(MODEL, max_batch=64, max_pending=8)


def test_deadline_sheds_undispatched_request(watchdog):
    """A request whose deadline expires before dispatch is shed: take raises
    ServeDeadline (typed, names the ticket), drain still completes, and
    surviving tickets resolve bitwise."""
    watchdog(120)
    import time as _t

    from repro.core import ServeDeadline
    with AsyncBatchQueue(MODEL, max_batch=64) as q:
        q.warmup()
        t_live = q.submit(X[:8])                    # no deadline
        t_dead = q.submit(X[8:16], deadline_s=0.01)
        _t.sleep(0.05)                              # expires while gated
        with pytest.raises(ServeDeadline, match=f"ticket {t_dead}") as ei:
            q.take(t_dead, timeout=30.0)
        assert isinstance(ei.value, TimeoutError)
        got = q.take(t_live, timeout=30.0)
        assert (got == np.asarray(predict_labels(MODEL, X[:8]))).all()
        q.drain(timeout=30.0)                       # shed rows never wedge it
        # a generous deadline is a no-op: the request resolves normally
        t_ok = q.submit(X[:16], deadline_s=60.0)
        assert (q.take(t_ok, timeout=30.0) ==
                np.asarray(predict_labels(MODEL, X[:16]))).all()
