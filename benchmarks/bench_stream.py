"""Streaming-epoch benchmark: rows/sec and peak RSS, streamed vs in-memory.

The streaming engine's promise is that throughput stays close to the
in-memory trainer while host memory stays O(chunk), not O(dataset).  Both
measurements run in CHILD processes so each reports its own honest peak RSS
(``ru_maxrss`` would otherwise remember the larger of the two phases):

    PYTHONPATH=src python -m benchmarks.bench_stream --smoke --out BENCH_stream.json

Rows/sec is a warm second pass (the first pass pays the per-chunk-shape
compiles); the JSON artifact records both passes, the chunk geometry and the
RSS split — CI uploads ``BENCH_stream.json`` next to the accuracy bench.
The parent never imports JAX: a process that has touched JAX holds the
accelerator, so the shards are written by a child too, and every child
runs on whatever platform the caller's environment selects.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import tempfile
import time


def _peak_rss_mb() -> float:
    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    return round(ru / 1024.0, 1)      # linux reports KiB


def _cfg(args):
    from repro.core import BSGDConfig

    return BSGDConfig(budget=args.budget, lambda_=2e-5, gamma=2.0**-7,
                      batch_size=args.batch_size)


def child_stream(args) -> dict:
    import glob

    import jax

    from repro.core import fit_stream
    from repro.data import FileChunks

    source = FileChunks(sorted(glob.glob(os.path.join(args.data, "*.npz"))))
    cfg = _cfg(args)
    t0 = time.perf_counter()
    state = fit_stream(cfg, source, epochs=1, seed=0)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    state = fit_stream(cfg, source, epochs=1, seed=1, state=state)
    jax.block_until_ready(state.alpha)
    warm = time.perf_counter() - t0
    return {"mode": "stream", "n_rows": source.n_rows, "dim": source.dim,
            "n_chunks": source.n_chunks,
            "chunk_rows": max(source.chunk_lens),
            "rows_per_s_cold": round(source.n_rows / cold, 1),
            "rows_per_s": round(source.n_rows / warm, 1),
            "peak_rss_mb": _peak_rss_mb()}


def child_inmem(args) -> dict:
    import glob

    import jax
    import numpy as np

    from repro.core import fit
    from repro.data import FileChunks

    source = FileChunks(sorted(glob.glob(os.path.join(args.data, "*.npz"))))
    xs, ys = zip(*[source.load(i) for i in range(source.n_chunks)])
    x, y = np.concatenate(xs), np.concatenate(ys)   # the resident baseline
    cfg = _cfg(args)
    t0 = time.perf_counter()
    state = fit(cfg, x, y, epochs=1, seed=0)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    state = fit(cfg, x, y, epochs=1, seed=1, state=state)
    jax.block_until_ready(state.alpha)
    warm = time.perf_counter() - t0
    return {"mode": "inmem", "n_rows": int(x.shape[0]),
            "rows_per_s_cold": round(x.shape[0] / cold, 1),
            "rows_per_s": round(x.shape[0] / warm, 1),
            "peak_rss_mb": _peak_rss_mb()}


def child_gen(args) -> dict:
    import jax
    import numpy as np

    from repro.data import make_susy_like, write_npz_chunks

    x, y = make_susy_like(jax.random.PRNGKey(1), args.n, args.dim)
    write_npz_chunks(args.data, np.asarray(x), np.asarray(y), args.chunk_rows)
    return {"mode": "gen", "n_rows": args.n}


def _spawn(mode: str, data_dir: str, args) -> dict:
    cmd = [sys.executable, "-m", "benchmarks.bench_stream", "--child", mode,
           "--data", data_dir, "--budget", str(args.budget),
           "--batch-size", str(args.batch_size), "--n", str(args.n),
           "--dim", str(args.dim), "--chunk-rows", str(args.chunk_rows)]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"{mode} child failed:\n{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=65536)
    ap.add_argument("--dim", type=int, default=18)
    ap.add_argument("--chunk-rows", type=int, default=8192)
    ap.add_argument("--budget", type=int, default=128)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizing (16k rows, 2k chunks)")
    ap.add_argument("--out", default="BENCH_stream.json")
    ap.add_argument("--data", default=None,
                    help="existing shard dir (skips generation)")
    ap.add_argument("--child", default=None,
                    choices=("gen", "stream", "inmem"),
                    help=argparse.SUPPRESS)   # internal: one child's job
    args = ap.parse_args()

    if args.child:
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        child = {"gen": child_gen, "stream": child_stream,
                 "inmem": child_inmem}[args.child]
        print(json.dumps(child(args)))
        return

    if args.smoke:
        args.n, args.chunk_rows, args.budget = 16384, 2048, 64

    with tempfile.TemporaryDirectory() as tmp:
        data_dir = args.data
        if data_dir is None:
            data_dir = os.path.join(tmp, "shards")
            _spawn("gen", data_dir, args)
        stream = _spawn("stream", data_dir, args)
        inmem = _spawn("inmem", data_dir, args)

    result = {
        # geometry from the measured source, not the CLI (--data may supply
        # pre-existing shards with different sizing)
        "workload": {"n": stream["n_rows"], "dim": stream["dim"],
                     "chunk_rows": stream["chunk_rows"],
                     "budget": args.budget,
                     "batch_size": args.batch_size,
                     "dataset_over_chunk": round(
                         stream["n_rows"] / stream["chunk_rows"], 1)},
        "stream": stream, "inmem": inmem,
        "stream_vs_inmem_rows_per_s": round(
            stream["rows_per_s"] / inmem["rows_per_s"], 3),
        "stream_vs_inmem_peak_rss": round(
            stream["peak_rss_mb"] / inmem["peak_rss_mb"], 3),
    }
    print(json.dumps(result, indent=2))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
