"""A run whose timed path is broken underneath comes out not correct.

Each test skips the harness's look for a chip (rehearsal sizes, on the
CPU), plants one fault in the program the window drives, and runs the rest
of a run: set-up, window, reference comparison.  The faults are those a
cell can have on one chip: a step that returns its model unchanged while
its step counter runs on, half of the batch left out (the mean taken over
the rest), and an answer altered where it is produced (a served label).
"""
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from bench.tests._runs import rehearse, result


def _sound(cell, seed):
    out = result(rehearse(cell, seed))
    assert out["correct"], out["checks"]


# the chunk programs set-up runs before the window's (mnist-epochs.json)
SETUP_CHUNKS = 3


def _frozen(state, out):
    """The model left as it was, its step counter run on."""
    return state._replace(step=out.step)


@pytest.mark.parametrize("window_only", [False, True])
def test_state_left_unchanged(monkeypatch, window_only):
    """The fault in every chunk program, or in the window's alone."""
    from repro.core import multiclass
    orig = multiclass.train_chunk_multiclass
    calls = []

    def chunk(cfg, table, state, xc, yc, impl="auto"):
        calls.append(1)
        kept = jax.tree.map(jnp.copy, state)   # the program donates state
        out = orig(cfg, table, state, xc, yc, impl=impl)
        if window_only and len(calls) <= SETUP_CHUNKS:
            return out
        return _frozen(kept, out)

    monkeypatch.setattr(multiclass, "train_chunk_multiclass", chunk)
    out = result(rehearse("mnist-ovr-train", 3_000_000_021))
    assert not out["correct"], out["checks"]


def test_half_the_batch_left_out(monkeypatch):
    from repro.core import multiclass
    orig = multiclass.train_chunk_multiclass

    def half(cfg, table, state, xc, yc, impl="auto"):
        h = xc.shape[1] // 2
        return orig(cfg, table, state,
                    jnp.concatenate([xc[:, :h], xc[:, :h]], axis=1),
                    jnp.concatenate([yc[:, :h], yc[:, :h]], axis=1),
                    impl=impl)

    monkeypatch.setattr(multiclass, "train_chunk_multiclass", half)
    out = result(rehearse("mnist-ovr-train", 3_000_000_023))
    assert not out["correct"], out["checks"]


def test_answer_altered(monkeypatch):
    import importlib
    predict = importlib.import_module("repro.core.predict")
    orig = predict.predict_labels

    @partial(jax.jit, static_argnames=("impl",))
    def altered(model, x, *, impl="auto"):
        lab = orig(model, x, impl=impl)
        return lab.at[0].set((lab[0] + 1) % model.sv_x.shape[0])

    monkeypatch.setattr(predict, "predict_labels", altered)
    out = result(rehearse("mnist-ovr-serve", 3_000_000_027))
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["mnist-ovr-train", "mnist-ovr-serve"])
def test_sound_run_is_correct(cell):
    _sound(cell, 3_000_000_029)
