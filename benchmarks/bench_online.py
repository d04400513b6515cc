"""Online-learning scenario bench: single-pass prequential regret under drift.

The workload the paper's GSS-precomputed merging was built for, finally
measured: every maintenance strategy — merge / multi-merge / removal /
removal-project / quantized — rides ONE pass over a non-stationary stream
(``data.stream.DriftChunks`` over the synthetic generators) at a matched
budget, scored test-then-train by ``core.online.prequential_stream``.
Two model shapes per scenario: binary bsgd and C=16 one-vs-rest.

Readouts per (scenario, strategy) cell:

  * ``mistake_rate``   — cumulative prequential error over the whole pass
    (the online regret readout; lower is better);
  * ``acc_pre`` / ``acc_post`` — mean per-chunk streaming accuracy before
    and after the drift point (how hard the model falls, how fast it
    recovers);
  * ``chunk_acc``      — the full per-chunk trace (drift localization);
  * ``t_s``            — wall-clock for the pass (compile included; the
    strategies share sizes, so relative time is meaningful).

``--smoke`` is the CI sizing and writes ``BENCH_online.json`` (wired into
``benchmarks.run --smoke`` and uploaded as a CI artifact): the label-flip
step schedule for both model shapes, plus a mean-shift ramp for the binary
model.  No strategy is skipped at any sizing — a strategy that cannot run
a cell is a hard error, not a silent gap.

    PYTHONPATH=src python -m benchmarks.bench_online --smoke --out BENCH_online.json
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.core import BSGDConfig, MulticlassSVMConfig, STRATEGIES, prequential_stream
from repro.data import (ArrayChunks, DriftChunks, label_flip_schedule,
                        make_blobs, make_blobs_multiclass, mean_shift_schedule)

from .common import csv_row


def _run_cells(source, n_chunks: int, drift_start: float, make_cfg,
               verbose: bool) -> dict:
    """One scenario: every maintenance strategy over the same drifted
    stream at a matched budget; returns {strategy: metrics}."""
    split = int(drift_start * n_chunks)
    out = {}
    for strat in STRATEGIES:
        cfg = make_cfg(strat)
        t0 = time.perf_counter()
        r = prequential_stream(cfg, source)
        t = time.perf_counter() - t0
        acc = r["chunk_acc"]
        out[strat] = {
            "mistake_rate": r["mistake_rate"],
            "mistakes": r["mistakes"],
            "acc_pre": round(float(np.mean(acc[:split])), 4),
            "acc_post": round(float(np.mean(acc[split:])), 4),
            "chunk_acc": acc,
            "t_s": round(t, 3),
        }
    if verbose:
        for strat, m in out.items():
            print(csv_row(strat, m["mistake_rate"], m["acc_pre"],
                          m["acc_post"], m["t_s"]), flush=True)
    return out


def run_online(n: int = 4096, dim: int = 8, budget: int = 64,
               batch_size: int = 32, chunk_rows: int = 512,
               n_classes: int = 16, mc_dim: int = 16, mc_budget: int = 32,
               drift_start: float = 0.5, seed: int = 0,
               verbose: bool = True) -> dict:
    """The full suite: binary label-flip + binary mean-shift + C-class
    label-rotation scenarios, all five strategies each."""
    lam = 1e-3
    gamma = 0.5

    def binary_cfg(strat):
        return BSGDConfig(budget=budget, lambda_=lam, gamma=gamma,
                          method="lookup-wd", batch_size=batch_size,
                          use_kernel_cache=True, maintenance=strat)

    def mc_cfg(strat):
        return MulticlassSVMConfig.create(
            n_classes, budget=mc_budget, lambda_=lam, gamma=gamma,
            method="lookup-wd", batch_size=batch_size,
            use_kernel_cache=True, maintenance=strat)

    x, y = make_blobs(jax.random.PRNGKey(seed), n, dim, sep=1.6)
    src = ArrayChunks(np.asarray(x, np.float32), np.asarray(y, np.float32),
                      chunk_rows)
    n_chunks = src.n_chunks
    flip = label_flip_schedule(n_chunks, start=drift_start, prob=1.0)
    shift = mean_shift_schedule(n_chunks, dim, magnitude=3.0,
                                start=drift_start, kind="ramp")

    result = {
        "n": n, "dim": dim, "budget": budget, "batch_size": batch_size,
        "chunk_rows": chunk_rows, "n_chunks": n_chunks,
        "drift_start": drift_start, "lambda": lam, "gamma": gamma,
        "schedules": {
            "label-flip": {"kind": "step", "start": drift_start, "prob": 1.0},
            "mean-shift": {"kind": "ramp", "start": drift_start,
                           "magnitude": 3.0},
        },
    }
    if verbose:
        print(csv_row("strategy", "mistake_rate", "acc_pre", "acc_post",
                      "t_s"))
        print(f"# binary / label-flip (n={n}, budget={budget})")
    result["binary_label_flip"] = _run_cells(
        DriftChunks(src, flip=flip, seed=seed), n_chunks, drift_start,
        binary_cfg, verbose)
    if verbose:
        print(f"# binary / mean-shift ramp")
    result["binary_mean_shift"] = _run_cells(
        DriftChunks(src, shift=shift, seed=seed), n_chunks, drift_start,
        binary_cfg, verbose)

    xm, ym = make_blobs_multiclass(jax.random.PRNGKey(seed + 1), n, mc_dim,
                                   n_classes, sep=2.0)
    msrc = ArrayChunks(np.asarray(xm, np.float32), np.asarray(ym), chunk_rows)
    mflip = label_flip_schedule(msrc.n_chunks, start=drift_start, prob=1.0)
    result["ovr_label_rotate"] = {"n_classes": n_classes, "dim": mc_dim,
                                  "budget_per_class": mc_budget}
    if verbose:
        print(f"# ovr C={n_classes} / label-rotate (budget/class={mc_budget})")
    result["ovr_label_rotate"]["rows"] = _run_cells(
        DriftChunks(msrc, flip=mflip, n_classes=n_classes, seed=seed),
        msrc.n_chunks, drift_start, mc_cfg, verbose)

    # the acceptance-level readout: quantized must be competitive post-drift
    for scen in ("binary_label_flip", "ovr_label_rotate"):
        rows = result[scen].get("rows", result[scen])
        best = min(r["mistake_rate"] for k, r in rows.items()
                   if isinstance(r, dict) and "mistake_rate" in r)
        q = rows["quantized"]["mistake_rate"]
        if verbose:
            print(f"# {scen}: best mistake_rate {best:.4f}, "
                  f"quantized {q:.4f}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizing, JSON artifact to --out")
    ap.add_argument("--out", default="BENCH_online.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        result = run_online(n=4096)
        result["smoke"] = True
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"# wrote {args.out}")
        return
    run_online(n=args.n, budget=128, mc_budget=64)


if __name__ == "__main__":
    main()