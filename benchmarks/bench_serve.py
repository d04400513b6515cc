"""Serving benchmark: rows/sec + microbatch latency, fp32 vs bfloat16 bank.

The serving engine's promise is that a budgeted bank scores request streams
fast (the budget exists so prediction stays cheap) and that quantizing the
bank to bfloat16 is free accuracy-wise on separated data while halving bank
bytes.  This pushes an identical ragged request trace through a
``core.predict.BatchQueue`` for both banks and records, per bank: rows/sec,
p50/p99 per-microbatch latency (post-warmup, including dispatch + host
sync), the bucket histogram, and bench-split accuracy.  The run fails if
the bf16 bank is less accurate than fp32 on the bench split, or if either
queue's labels diverge from one direct fused predict call (bitwise).

    PYTHONPATH=src python -m benchmarks.bench_serve --smoke --out BENCH_serve.json

CI runs the smoke sizing and uploads ``BENCH_serve.json`` next to the
stream/accuracy benches.
"""
from __future__ import annotations

import argparse
import json

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-classes", type=int, default=8)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--train-rows", type=int, default=8192)
    ap.add_argument("--bench-rows", type=int, default=8192)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--gamma", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizing (4 classes, 2k train / 2k bench rows)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        args.n_classes, args.train_rows, args.bench_rows = 4, 2048, 2048
        args.budget, args.max_batch = 32, 64

    import jax

    from repro.core import (MulticlassSVMConfig, drive_trace, export_model,
                            fit_multiclass, predict_labels,
                            ragged_trace_sizes)
    from repro.data import make_blobs_multiclass, train_test_split

    cfg = MulticlassSVMConfig.create(args.n_classes, budget=args.budget,
                                     lambda_=1e-3, gamma=args.gamma,
                                     batch_size=8)
    x, y = make_blobs_multiclass(jax.random.PRNGKey(args.seed),
                                 args.train_rows + args.bench_rows, args.dim,
                                 n_classes=args.n_classes, sep=2.5)
    (xtr, ytr), (xbe, ybe) = train_test_split(
        x, y, test_frac=args.bench_rows / (args.train_rows + args.bench_rows))
    state = fit_multiclass(cfg, xtr, ytr, epochs=1, seed=args.seed)

    # one ragged request trace, shared by both banks
    xbe_np = np.asarray(xbe)
    ybe_np = np.asarray(ybe)
    rng = np.random.default_rng(args.seed)
    sizes = ragged_trace_sizes(xbe_np.shape[0], args.max_batch, rng)

    banks, accs = {}, {}
    for tag, bank_dtype in (("fp32", None), ("bf16", "bfloat16")):
        model = export_model(state, args.gamma, bank_dtype=bank_dtype)
        direct = np.asarray(predict_labels(model, xbe_np))
        accs[tag] = round(float((direct == ybe_np.astype(np.int32)).mean()), 4)
        banks[tag] = drive_trace(model, xbe_np, sizes,
                                 max_batch=args.max_batch)
        for field in ("bucket_counts", "bucket_occupancy"):
            banks[tag][field] = {str(k): v
                                 for k, v in banks[tag][field].items()}
        banks[tag]["bench_accuracy"] = accs[tag]

    assert accs["bf16"] >= accs["fp32"], (
        f"bf16 bank lost accuracy on the bench split: {accs}")

    result = {
        "workload": {"n_classes": args.n_classes, "dim": args.dim,
                     "budget": args.budget, "train_rows": int(xtr.shape[0]),
                     "bench_rows": int(xbe_np.shape[0]),
                     "requests": len(sizes), "max_batch": args.max_batch},
        "fp32": banks["fp32"], "bf16": banks["bf16"],
        "bf16_vs_fp32_rows_per_s": round(
            banks["bf16"]["rows_per_s"] / banks["fp32"]["rows_per_s"], 3),
    }
    print(json.dumps(result, indent=2))
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
    print(f"# wrote {args.out}")


if __name__ == "__main__":
    main()
