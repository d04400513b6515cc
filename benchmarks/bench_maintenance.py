"""Maintenance-engine microbench: µs/event for every engine, one JSON row
per (dim, budget, C) cell — the perf artifact behind DESIGN.md §11.

    PYTHONPATH=src python -m benchmarks.bench_maintenance --smoke \
        --out BENCH_maintenance.json

Each cell builds a stacked over-budget state (C classes, ``events`` excess
SVs per class, all same-sign alphas so every event is a genuine merge, exact
kernel caches) and drains it to the budget through four engines:

  * ``class-loop``  — C sequential jitted ``run_maintenance`` calls, one per
                      class slice (the non-vmapped reference the ROADMAP's
                      3x regression was measured against);
  * ``xla-loop``    — ``vmap(run_maintenance)`` with the while-loop body
                      (PR 2's lockstep engine — the regression under test);
  * ``xla-unroll``  — the same vmap with statically inlined masked events;
  * ``pallas``      — the fused merge-event engine on the sorted-excess
                      schedule (``run_maintenance_classes``; Pallas kernel
                      on TPU, its jnp oracle elsewhere — ``impl="auto"``).

µs/event divides wall-clock by C x events — the engines execute identical
event sequences (the parity property in tests/core/test_event_engine.py), so
rows are directly comparable.  ``ratio_vs_class_loop`` is recorded per cell;
the acceptance target for this PR is pallas <= 1.25x class-loop at dim=512,
slots >= 256, and pallas at C=1/budget=256/dim=512 no worse than PR 1's
cached single-merge (~63 µs/event on the 2-core CI container).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.core import (default_table, kernel_cache, run_maintenance,
                        run_maintenance_classes)

from .common import time_fn

ENGINES = ("class-loop", "xla-loop", "xla-unroll", "pallas")


def build_state(c: int, budget: int, events: int, dim: int, seed: int = 0,
                gamma: float = 0.5):
    """Stacked over-budget state: count = budget + events per class, all
    same-sign alphas (every event merges, never the removal fallback)."""
    slots = budget + events
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    sv = jax.random.normal(k1, (c, slots, dim))
    alpha = 0.1 * jnp.abs(jax.random.normal(k2, (c, slots))) + 0.01
    kmat = jax.vmap(lambda s: kernel_cache.exact_cache(s, gamma))(sv)
    count = jnp.full((c,), slots, jnp.int32)
    return sv, alpha, kmat, count


def bench_cell(c: int, budget: int, events: int, dim: int, *,
               gamma: float = 0.5, repeats: int = 3) -> dict:
    """µs/event for every engine on one (dim, budget, C) cell."""
    sv, alpha, kmat, count = build_state(c, budget, events, dim, gamma=gamma)
    table = default_table()
    n0 = jnp.zeros((c,), jnp.int32)

    def per_class(q):
        return run_maintenance(
            sv[q], alpha[q], kmat[q], count[q], n0[q], gamma, table,
            budget=budget, strategy="merge", method="lookup-wd", impl="auto")

    def class_loop():
        return [per_class(q)[1] for q in range(c)]

    def vmapped(unroll):
        fn = jax.vmap(lambda s, a, k, ct, n: run_maintenance(
            s, a, k, ct, n, gamma, table, budget=budget, strategy="merge",
            method="lookup-wd", impl="auto", unroll=unroll))
        return lambda: fn(sv, alpha, kmat, count, n0)[1]

    def fused():
        return run_maintenance_classes(sv, alpha, kmat, count, n0, table,
                                       budget=budget, impl="auto")[1]

    timers = {"class-loop": class_loop, "xla-loop": vmapped(0),
              "xla-unroll": vmapped(events), "pallas": fused}
    n_events = c * events
    out = {}
    for name in ENGINES:
        secs, _ = time_fn(timers[name], warmup=1, repeats=repeats)
        out[name] = secs / n_events * 1e6
    return out


def run(*, dims=(64, 512, 1024), budgets=(256, 1024), classes=(1, 16),
        events: int = 32, repeats: int = 3, verbose: bool = True) -> list[dict]:
    rows = []
    for dim in dims:
        for budget in budgets:
            for c in classes:
                us = bench_cell(c, budget, events, dim, repeats=repeats)
                row = {"dim": dim, "budget": budget, "slots": budget + events,
                       "C": c, "events_per_class": events,
                       "us_per_event": {k: round(v, 2) for k, v in us.items()},
                       "ratio_vs_class_loop": {
                           k: round(us[k] / us["class-loop"], 3)
                           for k in ENGINES if k != "class-loop"}}
                rows.append(row)
                if verbose:
                    per = "  ".join(f"{k}={us[k]:8.1f}" for k in ENGINES)
                    print(f"dim={dim:5d} budget={budget:5d} C={c:3d}  "
                          f"us/event: {per}  "
                          f"(pallas {row['ratio_vs_class_loop']['pallas']:.2f}x"
                          " class-loop)", flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized grid (drops the 1024-dim/-budget cells)")
    ap.add_argument("--events", type=int, default=32,
                    help="excess SVs (= merge events) per class")
    ap.add_argument("--out", default="BENCH_maintenance.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        rows = run(dims=(64, 512), budgets=(256,), classes=(1, 16),
                   events=min(args.events, 16), repeats=3)
    else:
        rows = run(events=args.events)
    payload = {"benchmark": "maintenance_engines", "smoke": bool(args.smoke),
               "engines": list(ENGINES),
               "note": "class-loop at C=1 is exactly PR 1's cached "
                       "single-merge engine (run_maintenance, merge+cache) — "
                       "the same-run baseline for the pallas column",
               "rows": rows}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out} ({len(rows)} cells)")


if __name__ == "__main__":
    main()
