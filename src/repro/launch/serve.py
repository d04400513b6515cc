"""Serving driver: LM prefill/decode, and the budgeted-SVM request server.

CPU-runnable with smoke configs; the same step functions are what the
dry-run lowers for the production mesh.  Two arms:

  * LM archs — prefill + batched decode with the exact cache (ring buffer
    for SWA archs); see ``serve``.
  * ``--arch svm_bsgd`` — the trained budgeted model as a scoring service
    (``serve_svm``): a ``core.predict.BatchQueue`` assembles request rows
    into bucket-padded microbatches and each microbatch runs the fused
    multiclass predict cell (one ``rbf_matrix`` launch against the exported
    bank, argmax on device).  ``--model`` points at a ``fit_stream`` /
    ``fit_multiclass_stream`` checkpoint directory (mid-epoch checkpoints
    serve fine); without it a small in-process model is trained first (the
    smoke/demo path).  ``--bank-dtype bfloat16`` serves the quantized bank.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm_360m --smoke \
        --batch 4 --prompt-len 32 --gen 32
    PYTHONPATH=src python -m repro.launch.serve --arch svm_bsgd --smoke
    PYTHONPATH=src python -m repro.launch.serve --arch svm_bsgd \
        --model ckpts/run1 --gamma 0.5 --bank-dtype bfloat16
    PYTHONPATH=src python -m repro.launch.serve --arch svm_bsgd --smoke --live

    ``--live`` is the train-while-serve arm (``serve_svm_live``): a
    background ``fit_multiclass_stream`` publishes versioned snapshots into
    a ``core.predict.ModelBank`` while an ``AsyncBatchQueue`` serves a
    ragged trace over the bank, hot-swapping models mid-trace.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import enable_compile_cache
from ..configs import get, get_smoke
from ..models import decode_step, init_cache, init_lm, prefill
from .mesh import make_host_mesh


def serve(cfg, *, batch: int = 4, prompt_len: int = 32, gen: int = 32,
          seed: int = 0, greedy: bool = True, verbose: bool = True):
    key = jax.random.PRNGKey(seed)
    params, _ = init_lm(key, cfg)
    toks = jax.random.randint(key, (batch, prompt_len), 0, cfg.vocab_size)

    t0 = time.time()
    jit_prefill = jax.jit(lambda p, t: prefill(cfg, p, t))
    logits, pf_cache = jit_prefill(params, toks)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    # decode cache sized for prompt + generation; copy prefill K/V in
    cache = init_cache(cfg, batch, prompt_len + gen + 1)

    # structural copy: prefill caches have seq dim = prompt_len; place at 0
    def place(dst, src):
        if src.shape == dst.shape:
            return src
        # pad the sequence dim (axis 1 for k/v/pos/ckv/krope)
        if src.ndim == dst.ndim and src.shape[0] == dst.shape[0]:
            return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                                (0,) * dst.ndim)
        return dst
    cache = jax.tree.map(place, cache, jax.tree.map(lambda x: x, pf_cache)) \
        if _cache_compatible(cache, pf_cache) else cache

    jit_decode = jax.jit(lambda p, c, t, i: decode_step(cfg, p, c, t, i))
    cur = jnp.argmax(logits, -1)[:, None].astype(jnp.int32) if greedy else toks[:, -1:]
    out_tokens = [cur]
    t0 = time.time()
    pos = prompt_len if _cache_compatible(cache, pf_cache) else 0
    for i in range(gen):
        logits_i, cache = jit_decode(params, cache, cur,
                                     jnp.int32(pos + i))
        cur = jnp.argmax(logits_i, -1)[:, None].astype(jnp.int32)
        out_tokens.append(cur)
    jax.block_until_ready(cur)
    t_decode = time.time() - t0
    toks_out = jnp.concatenate(out_tokens, axis=1)
    if verbose:
        print(f"[serve] prefill {batch}x{prompt_len}: {t_prefill*1e3:.1f} ms; "
              f"decode {gen} steps: {t_decode*1e3:.1f} ms "
              f"({t_decode/gen*1e3:.2f} ms/tok incl. dispatch)")
    return toks_out


def serve_svm(*, model_dir: str | None = None, gamma: float = 0.5,
              bank_dtype: str | None = None, n_classes: int = 8,
              budget: int = 64, dim: int = 16, train_rows: int = 2048,
              rows: int = 4096, max_batch: int = 256, min_bucket: int = 8,
              top_k: int | None = None, seed: int = 0,
              verbose: bool = True) -> dict:
    """Serve a budgeted SVM: batched request queue over the fused predict cell.

    Loads ``model_dir`` (any ``repro.checkpoint`` dir holding an ``SVMState``
    — what the streaming trainers write) or, without one, trains a small
    ``n_classes``-blob model in-process.  A deterministic request trace of
    ``rows`` total rows with ragged request sizes is pushed through a
    ``BatchQueue`` (``max_batch`` microbatches, power-of-two pad buckets) and
    the labels are checked bitwise against one direct ``predict_labels``
    call — the parity gate runs on every invocation, not just in tests.
    ``top_k`` additionally serves the k-best class ids + calibrated softmax
    probabilities for a sample of the trace (``core.predict.top_k_labels`` /
    ``predict_proba``) and re-asserts that rank 1 is bitwise the argmax
    labels.  Returns the stats dict (rows/sec, p50/p99 microbatch latency,
    bucket histogram, top-k sample when requested).
    """
    from ..core import (MulticlassSVMConfig, drive_trace, export_model,
                        fit_multiclass, load_serve_model, predict_labels,
                        predict_proba, ragged_trace_sizes, top_k_labels)
    from ..data import make_blobs_multiclass

    if model_dir:
        model = load_serve_model(model_dir, gamma, bank_dtype=bank_dtype)
        if verbose:
            print(f"[serve] loaded {model_dir}: C={model.n_classes} "
                  f"slots={model.sv_x.shape[1]} dim={model.sv_x.shape[2]} "
                  f"bank={model.sv_x.dtype} "
                  f"sv_count={np.asarray(model.count).tolist()}")
    else:
        cfg = MulticlassSVMConfig.create(
            n_classes, budget=budget, lambda_=1e-3, gamma=gamma, batch_size=8)
        x, y = make_blobs_multiclass(jax.random.PRNGKey(seed), train_rows,
                                     dim, n_classes=n_classes, sep=2.5)
        state = fit_multiclass(cfg, x, y, epochs=1, seed=seed)
        model = export_model(state, gamma, bank_dtype=bank_dtype)
        if verbose:
            print(f"[serve] trained in-process: C={n_classes} budget={budget} "
                  f"dim={dim} bank={model.sv_x.dtype}")

    dim = model.sv_x.shape[2]
    rng = np.random.default_rng(seed)
    req_x = rng.standard_normal((rows, dim)).astype(np.float32)
    result = drive_trace(model, req_x, ragged_trace_sizes(rows, max_batch, rng),
                         max_batch=max_batch, min_bucket=min_bucket)
    result.update(dim=dim, n_classes=model.n_classes)
    if top_k:
        n_sample = min(64, rows)
        ids, vals = top_k_labels(model, req_x[:n_sample], k=top_k)
        probs = predict_proba(model, req_x[:n_sample])
        direct = predict_labels(model, req_x[:n_sample])
        assert (np.asarray(ids[:, 0]) == np.asarray(direct)).all(), \
            "top-1 of top_k_labels diverged from predict_labels"
        p_np = np.asarray(probs)
        assert np.allclose(p_np.sum(axis=1), 1.0, atol=1e-5)
        result.update(top_k=int(top_k),
                      top1_prob_mean=round(float(p_np.max(axis=1).mean()), 4))
        if verbose:
            head = [(np.asarray(ids[i]).tolist(),
                     np.round(np.asarray(vals[i]), 3).tolist(),
                     round(float(p_np[i].max()), 3))
                    for i in range(min(3, n_sample))]
            print(f"[serve] top-{top_k} sample (ids, scores, p_top1): {head}; "
                  f"mean top-1 prob {result['top1_prob_mean']}; "
                  f"rank 1 == argmax labels (bitwise)")
    if verbose:
        print(f"[serve] {result['rows']} rows in {result['requests']} "
              f"requests -> "
              f"{result['microbatches']} microbatches "
              f"(buckets {result['bucket_counts']}, "
              f"{result['padded_rows']} pad rows)")
        print(f"[serve] {result['rows_per_s']} rows/s; batch latency "
              f"p50={result['p50_ms']} ms p99={result['p99_ms']} ms; "
              f"queue == direct predict (bitwise)")
    return result


def serve_svm_live(*, gamma: float = 0.5, bank_dtype: str | None = None,
                   n_classes: int = 4, budget: int = 32, dim: int = 16,
                   train_rows: int = 4096, chunk_rows: int = 512,
                   epochs: int = 2, publish_every: int = 2,
                   rows: int = 4096, max_batch: int = 64,
                   min_bucket: int = 8, seed: int = 0,
                   verbose: bool = True, faults=None, retry=None,
                   ckpt_dir: str | None = None, ckpt_every: int = 0,
                   max_restarts: int = 2, report=None) -> dict:
    """Train-while-serve: a background trainer hot-swaps the model mid-trace.

    The ``--live`` arm — the pipeline PR's end-to-end artifact as one driver:
    ``fit_multiclass_stream(bank=..., publish_every=...)`` runs on a
    background thread (prefetched chunk staging on its own worker),
    publishing an immutable ``ServeModel`` snapshot into a ``ModelBank``
    every K chunks, while the foreground replays a ragged request trace
    through an ``AsyncBatchQueue`` built over the bank — every published
    version is picked up at the next microbatch launch, no drain, no pause.
    Returns the serve stats dict plus the version histogram
    (``versions: {version: microbatches}``) proving the hot-swap happened
    mid-trace, and re-runs the trace against the FINAL snapshot for the
    usual bitwise parity gate.

    Resilience (DESIGN.md §16): ``faults`` (a ``data.FaultSchedule``) wraps
    the chunk source in ``FaultyChunks`` and arms the full recovery stack —
    retries (``retry`` defaults to ``RetryPolicy()``), the non-finite
    publish guard, and checkpointing (``ckpt_dir`` defaults to a tempdir,
    ``ckpt_every`` to ``publish_every``).  A SUPERVISOR wraps the trainer:
    a crash leaves serving up on the last published bank version and
    restarts the trainer (up to ``max_restarts``), which resumes from the
    latest *verifiable* checkpoint.  The final snapshot is asserted finite.
    The result dict then also carries ``restarts``/``retries``/
    ``quarantined``/``rollbacks`` from the shared ``ResilienceReport``.
    """
    import tempfile
    import threading

    from ..core import (MulticlassSVMConfig, ModelBank, drive_trace,
                        ragged_trace_sizes)
    from ..data import (ArrayChunks, FaultyChunks, ResilienceReport,
                        RetryPolicy, make_blobs_multiclass)

    cfg = MulticlassSVMConfig.create(
        n_classes, budget=budget, lambda_=1e-3, gamma=gamma,
        batch_size=min(64, chunk_rows))
    x, y = make_blobs_multiclass(jax.random.PRNGKey(seed), train_rows, dim,
                                 n_classes=n_classes, sep=2.5)
    source = ArrayChunks(np.asarray(x, np.float32),
                         np.asarray(y, np.int32), chunk_rows=chunk_rows)
    report = report if report is not None else ResilienceReport()
    tmp_ckpt = None
    if faults is not None:
        source = FaultyChunks(source, faults)
        retry = retry if retry is not None else RetryPolicy()
        if ckpt_dir is None:
            tmp_ckpt = tempfile.TemporaryDirectory(prefix="serve_live_ckpt_")
            ckpt_dir = tmp_ckpt.name
        if not ckpt_every:
            ckpt_every = publish_every
    bank = ModelBank()
    fail: list[BaseException] = []

    def trainer() -> None:
        from ..core import fit_multiclass_stream
        attempts = 0
        while True:
            try:
                fit_multiclass_stream(cfg, source, epochs=epochs, seed=seed,
                                      prefetch=2, bank=bank,
                                      publish_every=publish_every,
                                      publish_dtype=bank_dtype,
                                      ckpt_dir=ckpt_dir,
                                      ckpt_every=ckpt_every, retry=retry,
                                      report=report,
                                      guard_finite=faults is not None)
                return
            except BaseException as e:  # noqa: BLE001 — supervised
                attempts += 1
                if attempts > max_restarts:
                    fail.append(e)   # re-raised on the main thread
                    return
                # serving stays up on the last published version; the next
                # attempt resumes from the latest verifiable checkpoint
                report.note_restart()
                if verbose:
                    print(f"[serve --live] trainer crashed ({e!r}); "
                          f"restart {attempts}/{max_restarts} from "
                          f"checkpoint")

    t = threading.Thread(target=trainer, daemon=True, name="live-trainer")
    t.start()
    try:
        bank.wait(1, timeout=120.0)           # first snapshot before serving
        rng = np.random.default_rng(seed)
        req_x = rng.standard_normal((rows, dim)).astype(np.float32)
        result = drive_trace(bank, req_x,
                             ragged_trace_sizes(rows, max_batch, rng),
                             max_batch=max_batch, min_bucket=min_bucket,
                             queue="async")
        t.join(timeout=300.0)
        if fail:
            raise RuntimeError("background trainer failed past "
                               f"{max_restarts} restarts") from fail[0]
        _, final_model = bank.current()
        for name in ("sv_x", "alpha"):
            leaf = jnp.asarray(getattr(final_model, name), jnp.float32)
            if not bool(jnp.all(jnp.isfinite(leaf))):
                raise AssertionError(
                    f"published ServeModel.{name} contains non-finite "
                    "values — the publish guard failed")
    finally:
        if tmp_ckpt is not None:
            tmp_ckpt.cleanup()
    result.update(dim=dim, n_classes=n_classes,
                  final_version=bank.version,
                  restarts=report.restarts,
                  retries=report.retries,
                  quarantined=report.quarantined_chunks(),
                  rollbacks=len(report.rollbacks))
    if verbose:
        print(f"[serve --live] {result['rows']} rows while training "
              f"({result['microbatches']} microbatches); versions served: "
              f"{result['versions']} (final v{bank.version})")
        print(f"[serve --live] {result['rows_per_s']} rows/s; "
              f"p50={result['p50_ms']} ms p99={result['p99_ms']} ms; "
              f"pad waste {result['pad_waste_frac']}")
        if faults is not None:
            print(f"[serve --live] resilience: {report!r}; final snapshot "
                  "finite (guarded publish)")
    return result


def _cache_compatible(cache, pf_cache) -> bool:
    try:
        return (pf_cache is not None and
                jax.tree.structure(cache) == jax.tree.structure(pf_cache))
    except Exception:  # noqa: BLE001
        return False


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    # svm_bsgd arm
    ap.add_argument("--model", default=None, metavar="CKPT_DIR",
                    help="svm_bsgd: checkpoint directory to serve "
                         "(fit_stream / fit_multiclass_stream layout)")
    ap.add_argument("--gamma", type=float, default=0.5,
                    help="svm_bsgd: RBF width the model was trained with")
    ap.add_argument("--bank-dtype", default=None,
                    choices=(None, "float32", "bfloat16"),
                    help="svm_bsgd: quantize the served SV bank")
    ap.add_argument("--rows", type=int, default=4096,
                    help="svm_bsgd: total request rows in the trace")
    ap.add_argument("--max-batch", type=int, default=256,
                    help="svm_bsgd: microbatch rows per fused predict call")
    ap.add_argument("--top-k", type=int, default=None, metavar="K",
                    help="svm_bsgd: also serve the K best class ids + "
                         "calibrated softmax probabilities (sampled; rank 1 "
                         "re-asserted bitwise against the argmax labels)")
    ap.add_argument("--live", action="store_true",
                    help="svm_bsgd: train-while-serve — a background "
                         "fit_multiclass_stream publishes snapshots into a "
                         "ModelBank every K chunks while an AsyncBatchQueue "
                         "serves the trace, hot-swapping mid-flight")
    ap.add_argument("--publish-every", type=int, default=2, metavar="K",
                    help="svm_bsgd --live: chunks between snapshots")
    ap.add_argument("--faults", type=int, default=None, metavar="SEED",
                    help="svm_bsgd --live: chaos drill — inject the seeded "
                         "FaultSchedule.chaos(SEED) (transient IO errors, "
                         "stalls, a NaN chunk, a fatal chunk, a trainer "
                         "crash) and run the full recovery stack: retries, "
                         "quarantine, guarded publish, checkpointed "
                         "supervisor restart")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()
    if args.arch == "svm_bsgd" and args.live:
        faults = None
        if args.faults is not None:
            from ..data import FaultSchedule
            faults = FaultSchedule.chaos(args.faults, nan_chunk=2,
                                         crash_chunk=3, fatal_chunk=5)
        kw = dict(rows=1024, train_rows=2048, chunk_rows=256,
                  epochs=1) if args.smoke else {}
        serve_svm_live(gamma=args.gamma, bank_dtype=args.bank_dtype,
                       publish_every=args.publish_every, seed=args.seed,
                       faults=faults, **kw)
        return
    if args.arch == "svm_bsgd":
        kw = {}
        if args.smoke:
            # default the top-k drive only for the in-process 4-class model;
            # --model may point at a binary (or 2-class) checkpoint where an
            # unasked-for top_k=3 would be an error
            kw = dict(rows=1024, max_batch=64, budget=32, train_rows=1024,
                      n_classes=4, bank_dtype=args.bank_dtype or "bfloat16",
                      top_k=args.top_k or (None if args.model else 3))
        serve_svm(model_dir=args.model, gamma=args.gamma, seed=args.seed,
                  **(kw if args.smoke else
                     dict(rows=args.rows, max_batch=args.max_batch,
                          bank_dtype=args.bank_dtype, top_k=args.top_k)))
        return
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    with make_host_mesh():
        serve(cfg, batch=args.batch, prompt_len=args.prompt_len, gen=args.gen)


if __name__ == "__main__":
    main()
