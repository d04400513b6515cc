"""Single-pass online evaluation: prequential (test-then-train) streaming.

The workload the paper's budget maintenance was designed for, made a
first-class driver: one pass over a chunk stream in which every chunk is
first SCORED by the current model (those predictions are the online
record — the model has never seen the rows) and then TRAINED on.  The
cumulative mistake count over the pass is the standard prequential error
(the regret readout of the online-learning literature: mistakes of the
online learner on an adversarially-revealed sequence); the per-chunk
accuracy trace localizes *where* a model loses it — e.g. right after a
drift point injected by ``data.stream.DriftChunks``.

Two deliberate contract points, pinned by tests/core/test_online.py:

  * chunks are visited in NATURAL order by default (``key=None``) — a
    shuffled pass would average any drift schedule away; pass a key only
    for stationary streams;
  * the whole pass is deterministic given the chunk source and seed: the
    driver introduces no randomness of its own (scoring is pure, training
    consumes batches in stream order), so two runs agree bitwise.

Each chunk trains through the class-axis engine's donated chunk program
(``multiclass.train_chunk_ovr``; a binary model is its one class) on its
batch-aligned prefix; the up-to-``batch_size - 1`` remainder rows of a
chunk are scored but not trained (chunk-grained prequential — benchmarks
size chunks as multiples of the batch so nothing is dropped).  A cold
binary model scores ``sign(0) = 0`` (the repo's ``predict`` convention)
and pays a full mistake on every first-chunk row — the same handicap for
every strategy, so matched comparisons are unaffected.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .bsgd import BSGDConfig, init_state, predict
from .multiclass import (MulticlassSVMConfig, init_multiclass_state,
                         ovr_targets, predict_multiclass, train_chunk_ovr)
from ..data.stream import iter_epoch


def prequential_stream(cfg, source, *, key=None, impl: str = "auto",
                       state=None, prefetch: int = 0, retry=None,
                       report=None, skip_chunks=()) -> dict:
    """One prequential pass: score each chunk, then train on it.

    ``cfg`` is a binary ``BSGDConfig`` (labels in {-1, +1}) or a
    ``MulticlassSVMConfig`` (integer class ids).  ``state`` continues from
    an existing model (e.g. a ``seed_codebook``-warm-started bank);  None
    starts cold.  ``retry``/``report``/``skip_chunks`` are the §16
    resilience knobs forwarded to ``iter_epoch`` (quarantined chunks are
    neither scored nor trained on).  Returns the final state plus the
    online record::

        {"state", "n_rows", "mistakes", "mistake_rate",   # cumulative
         "chunk_acc",                                     # per-chunk trace
         "chunk_mistakes"}
    """
    multi = isinstance(cfg, MulticlassSVMConfig)
    binary = cfg.binary if multi else cfg
    if not isinstance(binary, BSGDConfig):
        raise TypeError(f"cfg must be BSGDConfig or MulticlassSVMConfig, "
                        f"got {type(cfg).__name__}")
    table = binary.table()
    if state is None:
        state = (init_multiclass_state(cfg, source.dim) if multi
                 else init_state(binary, source.dim))
    # one chunk program for both, on the engine's stacked form: a binary
    # model is its one class, and its ±1 labels are that class's targets
    if multi:
        score, unstack = predict_multiclass, lambda st: st
        targets = lambda yc: ovr_targets(yc, cfg.n_classes, binary.dtype)
    else:
        score, unstack = predict, lambda st: jax.tree.map(lambda a: a[0], st)
        state = jax.tree.map(lambda a: a[None], state)
        targets = lambda yc: yc[None].astype(np.float32)
    bsz = binary.batch_size
    mistakes = 0
    n_rows = 0
    chunk_acc, chunk_mist = [], []
    for _, x, y in iter_epoch(source, key, prefetch=prefetch, retry=retry,
                              report=report, skip_chunks=skip_chunks):
        x = np.asarray(x, np.float32)
        y = np.asarray(y)
        # test ...
        pred = np.asarray(score(unstack(state), x, binary.gamma, impl=impl))
        wrong = int(np.sum(pred != y))
        mistakes += wrong
        n_rows += x.shape[0]
        chunk_mist.append(wrong)
        chunk_acc.append(round(1.0 - wrong / x.shape[0], 4))
        # ... then train on the batch-aligned prefix
        steps = x.shape[0] // bsz
        if steps:
            xc = x[:steps * bsz].reshape(steps, bsz, -1)
            tc = targets(y[:steps * bsz]).reshape(-1, steps, bsz)
            state = train_chunk_ovr(binary, table, state, xc,
                                    jnp.moveaxis(tc, 1, 0), impl=impl)
    state = jax.block_until_ready(unstack(state))
    return {"state": state, "n_rows": n_rows, "mistakes": mistakes,
            "mistake_rate": round(mistakes / max(n_rows, 1), 4),
            "chunk_acc": chunk_acc, "chunk_mistakes": chunk_mist}
