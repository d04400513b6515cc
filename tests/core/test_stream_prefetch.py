"""Prefetched streaming gates (ISSUE 7): ``prefetch > 0`` changes wall-clock
only — the realized batch sequence, checkpoint cursors, kill-and-resume and
final states are BITWISE the sync path's, and a chunk source failing on the
stager thread surfaces on the main thread instead of hanging the trainer."""
import os

import jax
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro.core import (BSGDConfig, MulticlassSVMConfig, fit_multiclass_stream,
                        fit_stream)
from repro.data import ArrayChunks, make_blobs, make_blobs_multiclass

CFG = BSGDConfig(budget=16, lambda_=1e-4, gamma=0.5, batch_size=4)
MCFG = MulticlassSVMConfig(n_classes=3, binary=CFG)
DIM = 6


def _binary(n=200, seed=0):
    x, y = make_blobs(jax.random.PRNGKey(seed), n, DIM)
    return np.asarray(x), np.asarray(y)


def _leaves_equal(a, b):
    for name, la, lb in zip(a._fields, a, b):
        if la is None:
            assert lb is None
            continue
        assert np.array_equal(np.asarray(la), np.asarray(lb)), name


def test_prefetch_bitwise_binary(watchdog):
    """Ragged chunks (carry path) + 2 epochs: prefetch=1..3 all bitwise."""
    watchdog(300)
    x, y = _binary(n=197)
    src = ArrayChunks(x, y, 37)
    ref = fit_stream(CFG, src, epochs=2, seed=3)
    for depth in (1, 2, 3):
        got = fit_stream(CFG, src, epochs=2, seed=3, prefetch=depth)
        _leaves_equal(ref, got)


def test_prefetch_bitwise_libsvm_stream(tmp_path, watchdog):
    """A LIBSVM text stream, parsed chunk by chunk (on the stager with
    prefetch), trains bitwise the same with prefetch as without."""
    watchdog(300)
    from repro.data import LibsvmChunks, dump_libsvm
    x, y = _binary(n=197)
    path = str(tmp_path / "train.libsvm")
    dump_libsvm(path, x, y)
    ref = fit_stream(CFG, LibsvmChunks(path, 37, DIM), epochs=2, seed=3)
    got = fit_stream(CFG, LibsvmChunks(path, 37, DIM), epochs=2, seed=3,
                     prefetch=2)
    _leaves_equal(ref, got)


def test_prefetch_bitwise_multiclass(watchdog):
    watchdog(300)
    x, y = make_blobs_multiclass(jax.random.PRNGKey(1), 180, DIM, 3)
    x, y = np.asarray(x), np.asarray(y)
    src = ArrayChunks(x, y, 36)
    ref = fit_multiclass_stream(MCFG, src, epochs=1, seed=5)
    got = fit_multiclass_stream(MCFG, src, epochs=1, seed=5, prefetch=2)
    _leaves_equal(ref, got)


def test_prefetch_kill_and_resume_bitwise(tmp_path, watchdog):
    """Killed mid-epoch-2 under prefetch, resumed under prefetch: bitwise the
    uninterrupted SYNC run — cursor semantics are prefetch-invariant."""
    watchdog(300)
    x, y = _binary(n=230)
    src = ArrayChunks(x, y, 37)
    ref = fit_stream(CFG, src, epochs=2, seed=5)          # sync reference
    ck = os.path.join(tmp_path, "ck")
    fit_stream(CFG, src, epochs=2, seed=5, ckpt_dir=ck, ckpt_every=2,
               max_chunks=9, prefetch=2)                  # dies mid-epoch-2
    steps = ckpt.all_steps(ck)
    assert steps and max(steps) <= 9
    assert ckpt.load_metadata(ck, max(steps))["epoch"] == 1
    resumed = fit_stream(CFG, src, epochs=2, seed=5, ckpt_dir=ck,
                         ckpt_every=2, prefetch=2)
    _leaves_equal(ref, resumed)


def test_prefetch_epoch_boundary_resume(tmp_path, watchdog):
    """Killed exactly at an epoch boundary (checkpoint cursor = next epoch,
    chunk 0) and resumed with prefetch: bitwise."""
    watchdog(300)
    x, y = _binary(n=200)
    src = ArrayChunks(x, y, 40)                           # 5 even chunks
    ref = fit_stream(CFG, src, epochs=2, seed=9)
    ck = os.path.join(tmp_path, "ck")
    fit_stream(CFG, src, epochs=2, seed=9, ckpt_dir=ck, ckpt_every=5,
               max_chunks=5, prefetch=2)                  # dies after epoch 1
    meta = ckpt.load_metadata(ck, max(ckpt.all_steps(ck)))
    # boundary cursor convention: end of epoch 0, not (epoch 1, chunk 0)
    assert (meta["epoch"], meta["next_chunk"]) == (0, 5)
    resumed = fit_stream(CFG, src, epochs=2, seed=9, ckpt_dir=ck,
                         ckpt_every=5, prefetch=2)
    _leaves_equal(ref, resumed)


def test_stager_error_surfaces_on_main_thread(watchdog):
    """A source whose load() raises mid-epoch fails the fit_stream CALL (not
    a daemon thread) and leaves no live stager behind."""
    import threading

    watchdog(120)

    class Boom(ArrayChunks):
        def load(self, i):
            if len(getattr(self, "_loads", [])) >= 2:
                raise OSError("shard unreadable")
            self._loads = getattr(self, "_loads", []) + [i]
            return super().load(i)

    x, y = _binary(n=200)
    with pytest.raises(OSError, match="shard unreadable"):
        fit_stream(CFG, Boom(x, y, 40), epochs=1, seed=0, prefetch=2)
    # the stager wound down with the failure — nothing left running
    for _ in range(50):
        alive = [t.name for t in threading.enumerate()
                 if t.name.startswith(("chunk-stager", "prefetch"))]
        if not alive:
            break
        threading.Event().wait(0.1)
    assert not alive, f"hung worker threads: {alive}"


def test_prefetch_publishes_to_bank(watchdog):
    """fit_stream(bank=, publish_every=K) publishes monotone versions, the
    final model always lands, and snapshots survive the donated-state scan
    (copied out — later chunks must not corrupt an earlier snapshot)."""
    watchdog(300)
    from repro.core import ModelBank, predict_labels

    x, y = _binary(n=200)
    src = ArrayChunks(x, y, 40)                           # 5 chunks/epoch
    seen = []                                 # (version, model, alpha-copy)

    class Spy(ModelBank):
        def publish(self, model):
            v = super().publish(model)
            seen.append((v, model, np.asarray(model.alpha).copy()))
            return v

    bank = Spy()
    state = fit_stream(CFG, src, epochs=1, seed=2, bank=bank,
                       publish_every=2)
    assert bank.version >= 2                  # mid-run + final snapshots
    assert [v for v, _, _ in seen] == list(range(1, bank.version + 1))
    # every snapshot kept its publish-time bytes: the donated-state scan of
    # LATER chunks must not have invalidated an earlier snapshot's buffers
    for v, model, alpha_then in seen:
        np.testing.assert_array_equal(np.asarray(model.alpha), alpha_then)
    # the final published model is the final state's export
    from repro.core import export_model
    _, final = bank.current()
    direct = np.asarray(predict_labels(export_model(state, CFG.gamma), x))
    np.testing.assert_array_equal(np.asarray(predict_labels(final, x)),
                                  direct)
