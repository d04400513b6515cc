"""End-to-end training driver: sharded init, checkpoint-restart, watchdog.

Runs real steps on whatever devices exist (CPU smoke configs, TPU pods with
the production mesh).  Fault-tolerance contract:

  * checkpoints are atomic + keep-last-k (``repro.checkpoint``); on start the
    driver resumes from the newest complete checkpoint automatically, so a
    SIGKILL'd / OOM'd / preempted job loses at most ``ckpt_every`` steps
    (exercised by ``launch/elastic.py`` and tests/test_fault_tolerance.py).
  * a per-step deadline watchdog flags stragglers; after ``max_strikes``
    consecutive overruns the driver exits with code 75 (EX_TEMPFAIL) so the
    supervisor re-admits it elsewhere — on a real cluster this is the
    slow-host escape hatch.
  * ``FAULT_AT_STEP`` env var injects a hard crash at a given step (fault
    drills in tests).

Usage: python -m repro.launch.train --arch smollm_360m --smoke --steps 100
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import checkpoint as ckpt
from ..compile_cache import enable_compile_cache
from ..configs import get, get_smoke
from ..data.tokens import BigramStream, frames_batch
from ..models import init_lm
from ..sharding import specs as sh
from ..train.optimizer import AdamW, cosine_schedule
from .inputs import abstract_params
from .mesh import make_host_mesh
from .steps import make_train_step

EX_TEMPFAIL = 75


def train_loop(cfg, *, steps: int = 100, batch_size: int = 8, seq_len: int = 128,
               ckpt_dir: str | None = None, ckpt_every: int = 25,
               mesh=None, strategy: str = "tp", lr: float = 3e-3,
               step_deadline_s: float | None = None, max_strikes: int = 3,
               log_every: int = 10, seed: int = 0, verbose: bool = True,
               schedule_total: int | None = None):
    """Returns dict of metrics (losses, resumed_from, straggler_strikes).

    ``schedule_total``: the LR schedule's horizon — pass the TARGET total when
    running a partial leg of a longer job, so interrupted + resumed runs see
    the identical schedule (restart transparency)."""
    mesh = mesh or make_host_mesh()
    total = schedule_total or steps
    opt = AdamW(lr=cosine_schedule(lr, warmup=min(20, total // 10 + 1),
                                   total=total))
    params_s, axes = abstract_params(cfg)
    p_shard = sh.param_shardings(axes, params_s, mesh, strategy)

    with mesh:
        params = jax.jit(lambda k: init_lm(k, cfg)[0],
                         out_shardings=p_shard)(jax.random.PRNGKey(seed))
        opt_state = jax.jit(opt.init)(params)

    start_step = 0
    resumed_from = None
    if ckpt_dir:
        latest = ckpt.latest_step(ckpt_dir)
        if latest is not None:
            state = ckpt.load(ckpt_dir, latest,
                              {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            start_step = latest
            resumed_from = latest
            if verbose:
                print(f"[train] resumed from step {latest}")

    step_fn = make_train_step(cfg, opt)
    with mesh:
        jit_step = jax.jit(step_fn, donate_argnums=(0, 1))

    stream = BigramStream(cfg.vocab_size, seed=seed)
    fault_at = int(os.environ.get("FAULT_AT_STEP", -1))
    losses, strikes = [], 0
    base_key = jax.random.PRNGKey(seed + 1)
    deadline = step_deadline_s

    for step in range(start_step, steps):
        # stateless per-step key: a resumed run sees the exact same batches
        # as an uninterrupted one (restart must be semantically transparent)
        sub = jax.random.fold_in(base_key, step)
        if cfg.input_kind == "frames":
            batch = frames_batch(sub, batch_size, seq_len, cfg.frame_dim,
                                 cfg.vocab_size)
        else:
            batch = stream.batch(sub, batch_size, seq_len)
        t0 = time.time()
        with mesh:
            params, opt_state, loss = jit_step(params, opt_state, batch)
        loss = float(loss)
        dt = time.time() - t0
        losses.append(loss)
        if step == fault_at:
            print(f"[train] FAULT INJECTION at step {step}", flush=True)
            os._exit(137)
        if deadline is not None and step > start_step:  # first step compiles
            if dt > deadline:
                strikes += 1
                print(f"[train] STRAGGLER step {step}: {dt:.2f}s > {deadline}s "
                      f"({strikes}/{max_strikes})", flush=True)
                if strikes >= max_strikes:
                    if ckpt_dir:
                        ckpt.save(ckpt_dir, step + 1,
                                  {"params": params, "opt": opt_state})
                    raise SystemExit(EX_TEMPFAIL)
            else:
                strikes = 0
        if verbose and step % log_every == 0:
            print(f"[train] step {step} loss {loss:.4f} ({dt*1e3:.0f} ms)",
                  flush=True)
        if ckpt_dir and (step + 1) % ckpt_every == 0:
            ckpt.save(ckpt_dir, step + 1, {"params": params, "opt": opt_state})

    if ckpt_dir:
        ckpt.save(ckpt_dir, steps, {"params": params, "opt": opt_state})
    return {"losses": losses, "resumed_from": resumed_from,
            "final_loss": losses[-1] if losses else None,
            "bigram_floor": stream.bigram_entropy()
            if cfg.input_kind == "tokens" else None,
            "params": params}


def svm_stream_loop(source, *, layout: str = "replicated", n_classes: int = 8,
                    budget: int = 128, batch_size: int = 8,
                    method: str = "lookup-wd", gamma: float = 0.5,
                    lambda_: float = 1e-4, epochs: int = 1, seed: int = 0,
                    mesh=None, ckpt_dir: str | None = None,
                    ckpt_every: int = 0, max_chunks: int | None = None,
                    prefetch: int = 0, verbose: bool = True, retry=None,
                    guard_finite: bool = False, report=None,
                    skip_chunks=()):
    """Streamed SVM training on the production mesh: the distributed path
    consuming the same chunk stream as the single-device trainers.

    ``source`` is any ``repro.data.stream.ChunkSource``.  Each resident chunk
    runs as ONE pjit'd donated-state program
    (``core.distributed.make_distributed_chunk_step``) with the chunk's batch
    axis sharded over the data axes and the SV state laid out per ``layout``
    — ``replicated`` / ``slots`` for binary, ``class`` for one-vs-rest
    multi-class (classes over ``model``, ``n_classes`` problems).  Epoch
    shuffling, remainder carry, every-K-chunks checkpointing and mid-epoch
    resume are exactly the ``fit_stream`` contract (the drivers are shared).
    ``prefetch > 0`` parses/shuffles/assembles the next chunk on a background
    stager while the current pjit program runs (host-side overlap only here —
    device placement stays with pjit's ``in_shardings``, since the chunk
    batch axis is sharded across the mesh, not single-device).
    ``retry``/``guard_finite``/``report``/``skip_chunks`` are the §16
    resilience knobs, forwarded verbatim to the shared streaming driver.

    Returns ``(state, cfg)``.
    """
    from ..core.bsgd import BSGDConfig, fit_stream, init_state
    from ..core.distributed import make_distributed_chunk_step
    from ..core.multiclass import (MulticlassSVMConfig, check_labels,
                                   fit_multiclass_stream,
                                   init_multiclass_state)
    from .mesh import make_mesh

    is_class = layout == "class"
    if mesh is None:
        # classes shard over ``model``, so the class layout puts the devices
        # there; the binary layouts shard the batch over ``data``
        n = len(jax.devices())
        mesh = make_mesh((1, n) if is_class else (n, 1), ("data", "model"))
    bcfg = BSGDConfig(budget=budget, lambda_=lambda_, gamma=gamma,
                      method=method, batch_size=batch_size)
    cfg = (MulticlassSVMConfig(n_classes=n_classes, binary=bcfg) if is_class
           else bcfg)
    table = cfg.table()

    compiled = {}   # chunk_steps -> pjit'd donated-state chunk program

    def chunk_fn(state, xc, yc):
        if is_class:
            check_labels(yc, n_classes)
        steps = xc.shape[0]
        if steps not in compiled:
            fn, _, in_sh, out_sh = make_distributed_chunk_step(
                cfg, mesh, source.dim, steps, table, layout=layout)
            with mesh:
                compiled[steps] = jax.jit(fn, in_shardings=in_sh,
                                          out_shardings=out_sh,
                                          donate_argnums=(0,))
        with mesh:
            return compiled[steps](state, table, xc, yc)

    if is_class:
        state = init_multiclass_state(cfg, source.dim)
        state = fit_multiclass_stream(cfg, source, epochs=epochs, seed=seed,
                                      state=state, ckpt_dir=ckpt_dir,
                                      ckpt_every=ckpt_every,
                                      max_chunks=max_chunks,
                                      chunk_fn=chunk_fn, prefetch=prefetch,
                                      retry=retry, guard_finite=guard_finite,
                                      report=report, skip_chunks=skip_chunks)
    else:
        state = init_state(cfg, source.dim)
        state = fit_stream(cfg, source, epochs=epochs, seed=seed, state=state,
                           ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
                           max_chunks=max_chunks, chunk_fn=chunk_fn,
                           prefetch=prefetch, retry=retry,
                           guard_finite=guard_finite, report=report,
                           skip_chunks=skip_chunks)
    if verbose:
        counts = np.asarray(state.count).tolist()
        print(f"[train] svm stream done: layout={layout} "
              f"chunks={source.n_chunks} rows={source.n_rows} "
              f"sv_count={counts}", flush=True)
    return state, cfg


def _open_stream(path: str, *, chunk_rows: int, n_features: int | None,
                 binary: bool):
    """CLI helper: a shard directory (*.npz) or a LIBSVM text file."""
    import glob

    from ..data.stream import FileChunks, LibsvmChunks

    if os.path.isdir(path):
        shards = sorted(glob.glob(os.path.join(path, "*.npz")))
        if not shards:
            raise SystemExit(f"{path}: no .npz shards")
        return FileChunks(shards)
    return LibsvmChunks(path, chunk_rows, n_features, binary=binary)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--deadline", type=float, default=None)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", default=None, metavar="PATH",
                    help="svm_bsgd only: chunk source — a directory of .npz "
                         "shards or a LIBSVM text file")
    ap.add_argument("--svm-layout", default="replicated",
                    choices=("replicated", "slots", "class"))
    ap.add_argument("--svm-classes", type=int, default=8)
    ap.add_argument("--svm-budget", type=int, default=128)
    ap.add_argument("--chunk-rows", type=int, default=4096,
                    help="rows per chunk for LIBSVM streams")
    ap.add_argument("--n-features", type=int, default=None)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--prefetch", type=int, default=0, metavar="DEPTH",
                    help="svm_bsgd only: stage the next DEPTH chunks "
                         "(parse/shuffle/assemble) on a background thread "
                         "while the device runs the current chunk")
    ap.add_argument("--retry", type=int, default=0, metavar="ATTEMPTS",
                    help="svm_bsgd only: retry transient chunk-load "
                         "failures up to ATTEMPTS times (bounded backoff); "
                         "chunks that exhaust retries are quarantined and "
                         "skipped, not fatal (DESIGN.md §16)")
    ap.add_argument("--guard-finite", action="store_true",
                    help="svm_bsgd only: per-chunk non-finite sentinel — "
                         "roll back to the last good state and skip the "
                         "offending chunk instead of training on NaN/Inf")
    args = ap.parse_args()
    enable_compile_cache()
    if args.arch == "svm_bsgd":
        if not args.stream:
            raise SystemExit("--arch svm_bsgd needs --stream PATH")
        source = _open_stream(args.stream, chunk_rows=args.chunk_rows,
                              n_features=args.n_features,
                              binary=args.svm_layout != "class")
        retry = None
        report = None
        if args.retry or args.guard_finite:
            from ..data import ResilienceReport, RetryPolicy
            report = ResilienceReport()
            if args.retry:
                retry = RetryPolicy(max_attempts=args.retry)
        svm_stream_loop(source, layout=args.svm_layout,
                        n_classes=args.svm_classes, budget=args.svm_budget,
                        batch_size=args.batch_size, epochs=args.epochs,
                        seed=args.seed, ckpt_dir=args.ckpt_dir,
                        ckpt_every=args.ckpt_every, prefetch=args.prefetch,
                        retry=retry, guard_finite=args.guard_finite,
                        report=report)
        if report is not None:
            print(f"[train] resilience: {report!r}")
        return
    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    metrics = train_loop(cfg, steps=args.steps, batch_size=args.batch_size,
                         seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every,
                         step_deadline_s=args.deadline, lr=args.lr,
                         seed=args.seed)
    print(f"[train] done: final loss {metrics['final_loss']:.4f} "
          f"(bigram floor {metrics['bigram_floor']})")


if __name__ == "__main__":
    main()
