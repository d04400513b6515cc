"""Serving engine: fused predict cell, bank export, queue parity, resume.

The contracts pinned here (DESIGN.md §10):
  * the fused serve cell is decision-identical to the training-side
    predictors (binary sign and multiclass argmax);
  * export folds the active-count mask into alpha and quantizes only the
    bank — bf16 predictions match fp32 decisions on margin-separated rows;
  * the ``BatchQueue`` returns BITWISE the labels of one direct fused call
    on the same rows, for any arrival pattern (ragged tails, requests
    spanning microbatches, empty requests) — and its compiled-shape set is
    exactly its bucket list;
  * a mid-epoch ``fit_stream`` checkpoint serves identically to the
    in-memory model it snapshotted.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BSGDConfig, BatchQueue, MulticlassSVMConfig,
                        decision_function_multiclass, export_model, fit,
                        fit_multiclass, fit_multiclass_stream, fit_stream,
                        load_serve_model, predict, predict_labels,
                        predict_multiclass, predict_proba, serve_requests,
                        top_k_labels)
from repro.data import ArrayChunks, make_blobs, make_blobs_multiclass

GAMMA = 0.5


@pytest.fixture(scope="module")
def mc_model():
    cfg = MulticlassSVMConfig.create(5, budget=24, lambda_=1e-3, gamma=GAMMA,
                                     batch_size=8)
    x, y = make_blobs_multiclass(jax.random.PRNGKey(0), 640, 8, n_classes=5,
                                 sep=2.0)
    state = fit_multiclass(cfg, x, y, epochs=1, seed=0)
    return cfg, state, np.asarray(x), np.asarray(y)


@pytest.fixture(scope="module")
def bin_model():
    cfg = BSGDConfig(budget=16, lambda_=1e-3, gamma=GAMMA, batch_size=8)
    x, y = make_blobs(jax.random.PRNGKey(1), 320, 6, sep=2.0)
    state = fit(cfg, x, y, epochs=1, seed=0)
    return cfg, state, np.asarray(x), np.asarray(y)


def test_export_folds_count_mask_and_quantizes_bank_only(mc_model):
    cfg, state, _, _ = mc_model
    model = export_model(state, GAMMA, bank_dtype="bfloat16")
    assert model.sv_x.dtype == jnp.bfloat16
    assert model.alpha.dtype == jnp.float32          # fp32 accumulation
    assert not model.binary and model.n_classes == 5
    counts = np.asarray(model.count)
    alpha = np.asarray(model.alpha)
    for c in range(5):
        assert (alpha[c, counts[c]:] == 0).all()     # mask folded in
        np.testing.assert_array_equal(
            alpha[c, :counts[c]], np.asarray(state.alpha)[c, :counts[c]])


def test_binary_export_is_c1_bank(bin_model):
    cfg, state, x, _ = bin_model
    model = export_model(state, GAMMA)
    assert model.binary and model.sv_x.shape[0] == 1
    labels = np.asarray(predict_labels(model, x))
    assert labels.dtype == np.float32
    np.testing.assert_array_equal(labels, np.asarray(predict(state, x, GAMMA)))


def test_fused_serve_cell_matches_train_side_predict(mc_model):
    cfg, state, x, y = mc_model
    model = export_model(state, GAMMA)
    got = np.asarray(predict_labels(model, x))
    want = np.asarray(predict_multiclass(state, x, GAMMA))
    np.testing.assert_array_equal(got, want)
    assert (got == y.astype(np.int32)).mean() > 0.9  # the model is real


@pytest.mark.parametrize("k", [1, 2, 5])
def test_top_k_rank1_is_argmax_and_scores_sorted(mc_model, k):
    """top_k_labels: rank 1 bitwise == predict_labels; scores descend; every
    row's id set is k distinct valid classes; ids/scores agree with the
    training-side per-class decision functions."""
    cfg, state, x, _ = mc_model
    model = export_model(state, GAMMA)
    ids, vals = top_k_labels(model, x[:100], k=k)
    ids, vals = np.asarray(ids), np.asarray(vals)
    assert ids.shape == vals.shape == (100, k) and ids.dtype == np.int32
    np.testing.assert_array_equal(ids[:, 0],
                                  np.asarray(predict_labels(model, x[:100])))
    assert (np.diff(vals, axis=1) <= 0).all()            # best first
    assert ((ids >= 0) & (ids < 5)).all()
    assert all(len(set(r)) == k for r in ids)            # distinct classes
    scores = np.asarray(decision_function_multiclass(state, x[:100], GAMMA)).T
    np.testing.assert_allclose(np.take_along_axis(scores, ids, axis=1), vals,
                               rtol=1e-5, atol=1e-5)


def test_predict_proba_calibrated_softmax(mc_model):
    """Rows sum to 1, argmax == predict_labels, temperature reorders nothing
    but flattens confidence monotonically."""
    cfg, state, x, _ = mc_model
    model = export_model(state, GAMMA)
    probs = np.asarray(predict_proba(model, x[:100]))
    assert probs.shape == (100, 5)
    np.testing.assert_allclose(probs.sum(axis=1), 1.0, atol=1e-5)
    np.testing.assert_array_equal(
        probs.argmax(axis=1).astype(np.int32),
        np.asarray(predict_labels(model, x[:100])))
    hot = np.asarray(predict_proba(model, x[:100], temperature=10.0))
    np.testing.assert_array_equal(probs.argmax(axis=1), hot.argmax(axis=1))
    assert (hot.max(axis=1) <= probs.max(axis=1) + 1e-6).all()


def test_top_k_and_proba_reject_binary_and_bad_k(bin_model, mc_model):
    cfg, state, x, _ = bin_model
    bmodel = export_model(state, GAMMA)
    with pytest.raises(ValueError):
        top_k_labels(bmodel, x[:4])
    with pytest.raises(ValueError):
        predict_proba(bmodel, x[:4])
    _, mstate, mx, _ = mc_model
    mmodel = export_model(mstate, GAMMA)
    with pytest.raises(ValueError):
        top_k_labels(mmodel, mx[:4], k=6)                # > n_classes
    with pytest.raises(ValueError):
        top_k_labels(mmodel, mx[:4], k=0)
    with pytest.raises(ValueError):
        predict_proba(mmodel, mx[:4], temperature=0.0)   # NaN factory
    with pytest.raises(ValueError):
        predict_proba(mmodel, mx[:4], temperature=-1.0)  # reversed ranking


ARRIVALS = [
    [640],                                # one big request, spans microbatches
    [1] * 37,                             # tiny requests packed together
    [3, 50, 1, 0, 17, 120, 5, 200, 31],   # ragged mix with an empty request
    [63, 64, 65],                         # straddling the microbatch size
]


@pytest.mark.parametrize("sizes", ARRIVALS)
def test_queue_bitwise_parity_multiclass(mc_model, sizes):
    cfg, state, x, _ = mc_model
    model = export_model(state, GAMMA)
    direct = np.asarray(predict_labels(model, x))
    reqs, off = [], 0
    for s in sizes:
        reqs.append(x[off:off + s])
        off += s
    labels = serve_requests(model, reqs, max_batch=64)
    assert [l.shape[0] for l in labels] == sizes
    np.testing.assert_array_equal(np.concatenate(labels), direct[:off])


@pytest.mark.parametrize("sizes", ARRIVALS)
def test_queue_bitwise_parity_binary(bin_model, sizes):
    cfg, state, x, _ = bin_model
    sizes = [min(s, 40) for s in sizes]   # binary fixture has 320 rows
    model = export_model(state, GAMMA)
    direct = np.asarray(predict_labels(model, x))
    reqs, off = [], 0
    for s in sizes:
        reqs.append(x[off:off + s])
        off += s
    labels = serve_requests(model, reqs, max_batch=32, min_bucket=4)
    np.testing.assert_array_equal(np.concatenate(labels), direct[:off])


def test_queue_pads_to_buckets_only(mc_model):
    """Compiled-shape discipline: every microbatch is a bucket size, full
    microbatches run eagerly at submit, and pad rows are accounted."""
    cfg, state, x, _ = mc_model
    model = export_model(state, GAMMA)
    q = BatchQueue(model, max_batch=32, min_bucket=8)
    assert q.buckets == (8, 16, 32)
    t1 = q.submit(x[:70])                 # 2 full microbatches run now
    assert q.stats["microbatches"] == 2 and q._pending_rows == 6
    t2 = q.submit(x[70:75])               # still below a microbatch
    q.drain()                             # ragged 11 -> bucket 16
    assert q.stats["bucket_counts"] == {32: 2, 16: 1}
    assert q.stats["padded_rows"] == 5
    direct = np.asarray(predict_labels(model, x[:75]))
    np.testing.assert_array_equal(
        np.concatenate([q.take(t1), q.take(t2)]), direct)


def test_queue_take_before_drain_raises(mc_model):
    cfg, state, x, _ = mc_model
    q = BatchQueue(export_model(state, GAMMA), max_batch=64)
    t = q.submit(x[:3])
    with pytest.raises(KeyError, match="drain"):
        q.take(t)
    q.drain()
    assert q.take(t).shape == (3,)


def test_bf16_bank_matches_fp32_on_margin_separated_rows(mc_model):
    cfg, state, x, _ = mc_model
    fp32 = export_model(state, GAMMA)
    bf16 = export_model(state, GAMMA, bank_dtype="bfloat16")
    from repro.core import serve_scores

    scores = np.asarray(serve_scores(fp32, x))            # (C, n)
    top2 = np.sort(scores, axis=0)[-2:]
    margin = top2[1] - top2[0]
    sep = margin > 0.05                   # rows where fp32 decides clearly
    assert sep.mean() > 0.8               # the blobs are actually separated
    l32 = np.asarray(predict_labels(fp32, x))
    l16 = np.asarray(predict_labels(bf16, x))
    np.testing.assert_array_equal(l16[sep], l32[sep])


def test_bf16_bank_accuracy_not_below_fp32():
    """On a held-out split the bfloat16 bank labels at least as many rows
    correctly as the fp32 bank of the same model."""
    from repro.data import train_test_split
    cfg = MulticlassSVMConfig.create(5, budget=24, lambda_=1e-3, gamma=GAMMA,
                                     batch_size=8)
    x, y = make_blobs_multiclass(jax.random.PRNGKey(3), 960, 8, n_classes=5,
                                 sep=2.0)
    (xtr, ytr), (xte, yte) = train_test_split(x, y)
    state = fit_multiclass(cfg, xtr, ytr, epochs=1, seed=0)
    acc = {dt: float(np.mean(np.asarray(predict_labels(
        export_model(state, GAMMA, bank_dtype=dt), xte)) == np.asarray(yte)))
        for dt in (None, "bfloat16")}
    assert acc[None] > 0.8, acc
    assert acc["bfloat16"] >= acc[None], acc


@pytest.mark.parametrize("multiclass", [False, True])
def test_serving_midepoch_checkpoint_equals_inmemory(tmp_path, multiclass):
    """A killed streamed run's checkpoint serves bitwise like the in-memory
    model the kill returned (the train -> checkpoint -> export seam)."""
    ck = str(tmp_path / "ck")
    if multiclass:
        cfg = MulticlassSVMConfig.create(3, budget=12, lambda_=1e-3,
                                         gamma=GAMMA, batch_size=4)
        x, y = make_blobs_multiclass(jax.random.PRNGKey(2), 256, 5,
                                     n_classes=3, sep=2.0)
        source = ArrayChunks(np.asarray(x), np.asarray(y), chunk_rows=64)
        state = fit_multiclass_stream(cfg, source, epochs=1, seed=0,
                                      ckpt_dir=ck, ckpt_every=2, max_chunks=2)
    else:
        cfg = BSGDConfig(budget=12, lambda_=1e-3, gamma=GAMMA, batch_size=4)
        x, y = make_blobs(jax.random.PRNGKey(3), 256, 5, sep=2.0)
        source = ArrayChunks(np.asarray(x), np.asarray(y), chunk_rows=64)
        state = fit_stream(cfg, source, epochs=1, seed=0,
                           ckpt_dir=ck, ckpt_every=2, max_chunks=2)
    assert os.path.isdir(ck)              # the mid-epoch checkpoint exists
    from_ckpt = load_serve_model(ck, GAMMA)
    in_mem = export_model(state, GAMMA)
    np.testing.assert_array_equal(np.asarray(from_ckpt.sv_x),
                                  np.asarray(in_mem.sv_x))
    np.testing.assert_array_equal(np.asarray(from_ckpt.alpha),
                                  np.asarray(in_mem.alpha))
    xe = np.asarray(x)[:96]
    np.testing.assert_array_equal(np.asarray(predict_labels(from_ckpt, xe)),
                                  np.asarray(predict_labels(in_mem, xe)))


def test_load_serve_model_rejects_non_svm_checkpoint(tmp_path):
    from repro import checkpoint as ckpt

    d = str(tmp_path / "lm")
    ckpt.save(d, 1, {"params": {"w": jnp.ones((2, 2))}})
    with pytest.raises(ValueError, match="not an SVM training checkpoint"):
        load_serve_model(d, GAMMA)
    with pytest.raises(ValueError, match="no complete checkpoint"):
        load_serve_model(str(tmp_path / "empty"), GAMMA)


def test_queue_rejects_bad_geometry(mc_model):
    cfg, state, x, _ = mc_model
    model = export_model(state, GAMMA)
    with pytest.raises(ValueError, match="max_batch"):
        BatchQueue(model, max_batch=0)
    with pytest.raises(ValueError, match="min_bucket"):
        BatchQueue(model, max_batch=8, min_bucket=0)


def test_drive_trace_max_batch_one(mc_model):
    """The degenerate single-row-microbatch service still runs (regression:
    the trace generator crashed on max_batch=1)."""
    from repro.core import drive_trace, ragged_trace_sizes

    cfg, state, x, _ = mc_model
    model = export_model(state, GAMMA)
    rng = np.random.default_rng(0)
    sizes = ragged_trace_sizes(8, 1, rng)
    assert sizes == [1] * 8
    stats = drive_trace(model, x[:8], sizes, max_batch=1, min_bucket=1)
    assert stats["rows"] == 8 and stats["microbatches"] == 8


def test_load_serve_model_corrupt_manifest(tmp_path):
    from repro import checkpoint as ckpt

    d = str(tmp_path / "ck")
    ckpt.save(d, 1, {"state": jnp.zeros((2,))})
    with open(os.path.join(d, "step_00000001", "manifest.json"), "w") as f:
        f.write('{"leaves": {"trunc')
    with pytest.raises(ValueError, match="corrupt"):
        load_serve_model(d, GAMMA)
