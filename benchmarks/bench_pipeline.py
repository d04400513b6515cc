"""Async-pipeline benchmark: prefetched streaming, continuous batching, and
train-while-serve hot-swap — the PR 7 artifact.

Three cells, one JSON (``BENCH_pipeline.json``):

  * ``stream`` — ``fit_stream`` over a LIBSVM text file (pure-Python parse,
    the host work worth hiding) sync vs ``prefetch=2``; the final states
    must be BITWISE equal (prefetch changes wall-clock only, never math).
  * ``queue`` — one replayed ragged request trace through the synchronous
    ``BatchQueue`` vs the ``AsyncBatchQueue`` (continuous batching +
    per-bucket AOT executables + double-buffered dispatch); both runs carry
    ``drive_trace``'s bitwise parity gate against direct ``predict_labels``.
  * ``live`` — the same async trace while a background
    ``fit_multiclass_stream(bank=..., publish_every=K)`` hot-swaps versioned
    snapshots into a ``ModelBank`` mid-trace, vs the idle-trainer baseline;
    records the served-version histogram and the p99 inflation.

Thread overlap needs cores: the JSON records ``cpus`` and per-bar pass
booleans (prefetch >= 1.3x, async queue >= 1.5x, live p99 <= 2x idle).  On
a single-core machine overlap is physically impossible, and on shared CI
runners the live-p99 bar is co-tenancy roulette — so by default the bars
are REPORTED (loud PASS/FAIL lines) but only enforced as hard failures
when ``BENCH_PIPELINE_STRICT=1`` (a dedicated multi-core perf machine).
The bitwise-parity gates, by contrast, are always fatal.

    PYTHONPATH=src python -m benchmarks.bench_pipeline --smoke --out BENCH_pipeline.json

CI runs the smoke sizing and uploads ``BENCH_pipeline.json`` next to the
serve/stream benches.
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import threading
import time

import numpy as np

PREFETCH_BAR = 1.3      # prefetched fit_stream vs sync, rows/sec
ASYNC_BAR = 1.5         # AsyncBatchQueue vs BatchQueue, rows/sec
LIVE_P99_BAR = 2.0      # hot-swap p99 vs idle-trainer p99


def _cpus() -> int:
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:       # non-linux
        return os.cpu_count() or 1


def _bitwise(a, b) -> bool:
    import jax

    return all(np.array_equal(np.asarray(u), np.asarray(v))
               for u, v in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def cell_stream(args) -> dict:
    """Sync vs prefetched ``fit_stream`` over a LIBSVM text stream."""
    import jax

    from repro.core import BSGDConfig, fit_stream
    from repro.data import LibsvmChunks, dump_libsvm, make_susy_like

    x, y = make_susy_like(jax.random.PRNGKey(args.seed), args.stream_rows,
                          args.dim)
    x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
    cfg = BSGDConfig(budget=args.budget, lambda_=2e-5, gamma=2.0**-7,
                     batch_size=args.batch_size)

    def run(prefetch: int):
        source = LibsvmChunks(path, args.chunk_rows, args.dim, binary=True)
        state = fit_stream(cfg, source, epochs=1, seed=0, prefetch=prefetch)
        t0 = time.perf_counter()           # warm pass: compiles already paid
        state = fit_stream(cfg, source, epochs=1, seed=1, state=state,
                           prefetch=prefetch)
        jax.block_until_ready(state.alpha)
        return state, time.perf_counter() - t0

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "train.libsvm")
        dump_libsvm(path, x, y)
        s_sync, t_sync = run(0)
        s_pre, t_pre = run(args.prefetch)

    assert _bitwise(s_sync, s_pre), \
        "prefetched fit_stream diverged from sync (bitwise)"
    return {
        "rows": int(x.shape[0]), "chunk_rows": args.chunk_rows,
        "prefetch_depth": args.prefetch,
        "sync_rows_per_s": round(x.shape[0] / t_sync, 1),
        "prefetch_rows_per_s": round(x.shape[0] / t_pre, 1),
        "prefetch_vs_sync": round(t_sync / t_pre, 3),
        "bitwise_parity": True,
    }


def _trace(args, rng):
    from repro.core import ragged_trace_sizes

    req_x = rng.standard_normal(
        (args.trace_rows, args.dim)).astype(np.float32)
    sizes = ragged_trace_sizes(args.trace_rows, args.max_batch, rng)
    return req_x, sizes


def cell_queue(args, model, req_x, sizes) -> dict:
    """One ragged trace: synchronous ``BatchQueue`` vs ``AsyncBatchQueue``."""
    from repro.core import drive_trace

    sync = drive_trace(model, req_x, sizes, max_batch=args.max_batch)
    asyn = drive_trace(model, req_x, sizes, max_batch=args.max_batch,
                       queue="async")
    return {
        "trace_rows": int(sum(sizes)),
        "requests": len(sizes), "max_batch": args.max_batch,
        "sync": sync, "async": asyn,
        "async_vs_sync": round(asyn["rows_per_s"] / sync["rows_per_s"], 3),
        "bitwise_parity": True,      # drive_trace asserts it per run
    }


def cell_live(args, idle_p99_ms: float, req_x, sizes) -> dict:
    """Replay the trace continuously while a background trainer hot-swaps
    versioned snapshots into the bank — sustained serving under training.

    The trace loops until the trainer exits, so the served-version histogram
    spans every snapshot published mid-flight (a single pass lasts
    milliseconds — far shorter than a publish interval)."""
    import jax

    from repro.core import (AsyncBatchQueue, ModelBank, MulticlassSVMConfig,
                            fit_multiclass_stream)
    from repro.data import ArrayChunks, make_blobs_multiclass

    cfg = MulticlassSVMConfig.create(
        args.n_classes, budget=args.budget, lambda_=1e-3, gamma=args.gamma,
        batch_size=args.batch_size)
    x, y = make_blobs_multiclass(jax.random.PRNGKey(args.seed),
                                 args.train_rows, args.dim,
                                 n_classes=args.n_classes, sep=2.5)
    source = ArrayChunks(np.asarray(x, np.float32), np.asarray(y, np.int32),
                         chunk_rows=args.chunk_rows)
    bank = ModelBank()
    fail: list[BaseException] = []

    def trainer() -> None:
        try:
            fit_multiclass_stream(cfg, source, epochs=args.live_epochs,
                                  seed=args.seed, prefetch=2, bank=bank,
                                  publish_every=args.publish_every)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            fail.append(e)

    t = threading.Thread(target=trainer, daemon=True, name="bench-trainer")
    t.start()
    bank.wait(1, timeout=300.0)
    rows, passes = 0, 0
    with AsyncBatchQueue(bank, max_batch=args.max_batch) as q:
        q.warmup()
        t0 = time.perf_counter()
        while t.is_alive() or passes == 0:    # at least one full pass
            tickets, off = [], 0
            for s in sizes:
                tickets.append(q.submit(req_x[off:off + s]))
                off += s
            q.drain(timeout=600.0)
            for tk in tickets:
                q.take(tk)
            rows += off
            passes += 1
        wall = time.perf_counter() - t0
        lat = np.asarray(q.latencies_s)
        versions = dict(q.stats["versions"])
    t.join(timeout=600.0)
    if fail:
        raise RuntimeError("background trainer failed") from fail[0]
    p99 = round(float(np.percentile(lat, 99)) * 1e3, 3)
    return {
        "publish_every": args.publish_every,
        "final_version": bank.version,
        "versions_served": versions,
        "trace_passes": passes,
        "rows_per_s": round(rows / wall, 1),
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": p99,
        "idle_p99_ms": idle_p99_ms,
        "live_vs_idle_p99": round(p99 / idle_p99_ms, 3),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--n-classes", type=int, default=8)
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=128)
    ap.add_argument("--stream-rows", type=int, default=65536)
    ap.add_argument("--chunk-rows", type=int, default=4096)
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--train-rows", type=int, default=32768)
    ap.add_argument("--trace-rows", type=int, default=32768)
    ap.add_argument("--max-batch", type=int, default=128)
    ap.add_argument("--live-epochs", type=int, default=4)
    ap.add_argument("--publish-every", type=int, default=2)
    ap.add_argument("--gamma", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizing (16k stream rows, 8k trace rows)")
    ap.add_argument("--out", default="BENCH_pipeline.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        args.stream_rows, args.chunk_rows = 16384, 2048
        args.train_rows, args.trace_rows = 8192, 8192
        args.live_epochs = 8

    import jax

    from repro.core import export_model, fit_multiclass, MulticlassSVMConfig
    from repro.data import make_blobs_multiclass

    cpus = _cpus()
    print(f"== stream: sync vs prefetch={args.prefetch} "
          f"({args.stream_rows} LIBSVM rows) ==", flush=True)
    stream = cell_stream(args)
    print(json.dumps(stream), flush=True)

    # one model + one trace shared by the queue and live cells
    cfg = MulticlassSVMConfig.create(
        args.n_classes, budget=args.budget, lambda_=1e-3, gamma=args.gamma,
        batch_size=args.batch_size)
    x, y = make_blobs_multiclass(jax.random.PRNGKey(args.seed + 1),
                                 args.train_rows, args.dim,
                                 n_classes=args.n_classes, sep=2.5)
    model = export_model(fit_multiclass(cfg, x, y, epochs=1, seed=args.seed),
                         args.gamma)
    rng = np.random.default_rng(args.seed)
    req_x, sizes = _trace(args, rng)

    print(f"== queue: BatchQueue vs AsyncBatchQueue "
          f"({args.trace_rows} trace rows) ==", flush=True)
    queue = cell_queue(args, model, req_x, sizes)
    print(json.dumps({k: queue[k] for k in
                      ("async_vs_sync", "trace_rows")}), flush=True)

    print("== live: hot-swap trace vs idle trainer ==", flush=True)
    live = cell_live(args, queue["async"]["p99_ms"], req_x, sizes)
    print(json.dumps(live), flush=True)

    strict = os.environ.get("BENCH_PIPELINE_STRICT") == "1"
    bars = {
        f"prefetch>={PREFETCH_BAR}x":
            stream["prefetch_vs_sync"] >= PREFETCH_BAR,
        f"async_queue>={ASYNC_BAR}x": queue["async_vs_sync"] >= ASYNC_BAR,
        f"live_p99<={LIVE_P99_BAR}x_idle":
            live["live_vs_idle_p99"] <= LIVE_P99_BAR,
    }
    result = {
        "cpus": cpus,
        "bars_met": bars,
        "bars_enforced": strict,
        "workload": {"dim": args.dim, "n_classes": args.n_classes,
                     "budget": args.budget, "batch_size": args.batch_size,
                     "stream_rows": args.stream_rows,
                     "trace_rows": args.trace_rows,
                     "max_batch": args.max_batch},
        "stream": stream, "queue": queue, "live": live,
    }
    for cell in ("sync", "async"):
        for field in ("bucket_counts", "bucket_occupancy"):
            queue[cell][field] = {str(k): v
                                  for k, v in queue[cell][field].items()}
    live["versions_served"] = {str(k): v
                               for k, v in live["versions_served"].items()}

    for name, ok in bars.items():
        print(f"# bar {name}: {'PASS' if ok else 'FAIL'}", flush=True)
    if cpus == 1:
        print(f"# single-cpu machine ({cpus}): thread overlap is physically "
              f"impossible here — bars recorded for the multi-core CI run",
              flush=True)
    if strict:
        assert all(bars.values()), f"perf bars failed: {bars}"

    print(json.dumps(result, indent=2))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
