"""Paper Table 3: relative training-time improvement of the lookups vs GSS,
merging frequency, decision agreement, and WD precision factors — plus the
maintenance-engine variants (kernel cache, fused multi-merge) this repo adds
on top of the paper.

Timing compares jit'd whole-epoch training (identical streams, identical
model updates modulo solver choice).  Decision/precision statistics run the
solvers side-by-side on the same pre-maintenance states, exactly like the
paper's paired run.  ``maintenance_bench`` isolates the budget-maintenance
path itself: a fixed number of merge events scanned inside one XLA program,
with the kappa row recomputed per event (seed) vs read from the kernel cache.
"""
from __future__ import annotations

import argparse
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (BSGDConfig, default_table, fit, init_state,
                        kernel_cache, maintenance_step, run_maintenance,
                        train_step)
from repro.data.synthetic import train_test_split

from .common import DATASETS, csv_row, time_fn

# engine variants timed alongside the paper's three solvers; each maps to
# BSGDConfig knobs layered on the lookup-wd solver
ENGINE_VARIANTS = {
    "lookup-wd+cache": dict(method="lookup-wd", use_kernel_cache=True),
    "lookup-wd+mm4": dict(method="lookup-wd", use_kernel_cache=True,
                          maintenance="multi-merge", merge_batch=4),
    "lookup-wd+evt": dict(method="lookup-wd", use_kernel_cache=True,
                          maintenance_engine="pallas"),
    "fused-step": dict(method="lookup-wd", use_kernel_cache=True,
                       step_engine="pallas"),
}


def timed_fit(cfg, xtr, ytr, epochs):
    def run():
        return fit(cfg, xtr, ytr, epochs=epochs, seed=0).alpha
    return time_fn(run, warmup=1, repeats=3)[0]


def decision_stats(name, dim, gen, gamma, lam, *, budget=60, steps=1500):
    """Run BSGD; at every maintenance event compare GSS vs Lookup-WD vs
    GSS-precise on the SAME state (paper's paired methodology)."""
    key = jax.random.PRNGKey(0)
    x, y = gen(key, steps + budget + 10)
    cfg = BSGDConfig(budget=budget, lambda_=lam, gamma=gamma, method="lookup-wd")
    table = default_table()
    state = init_state(cfg, x.shape[1])
    stats = dict(events=0, equal=0, factor_gss=[], factor_lookup=[], steps=0)

    for i in range(steps):
        xb, yb = x[i:i+1], y[i:i+1]
        new_state = train_step(cfg, table, state, xb, yb)
        stats["steps"] += 1
        if int(new_state.n_merges) > int(state.n_merges):
            # recreate the pre-maintenance SV set: replay insert w/o budget
            big = BSGDConfig(budget=cfg.budget + 1, lambda_=lam, gamma=gamma,
                             method="lookup-wd")
            over = train_step(big, table, state, xb, yb)
            args = (over.sv_x, over.alpha, over.count, gamma)
            _, _, _, i_g = maintenance_step(*args, method="gss")
            _, _, _, i_l = maintenance_step(*args, method="lookup-wd", table=table)
            _, _, _, i_p = maintenance_step(*args, method="gss-precise")
            stats["events"] += 1
            stats["equal"] += int(int(i_g.j_star) == int(i_l.j_star))
            wd_p = float(i_p.wd_star)
            # the paper's factor metric is meaningless when the optimal WD is
            # ~0 (near-duplicate SVs: any solver is near-exact; fp noise
            # dominates the ratio) — exclude degenerate events
            if wd_p > 1e-9:
                stats["factor_gss"].append(float(i_g.wd_star) / wd_p)
                stats["factor_lookup"].append(float(i_l.wd_star) / wd_p)
        state = new_state
    return stats


def maintenance_bench(budget: int = 256, dim: int = 512, events: int = 64,
                      gamma: float = 0.5, seed: int = 0, verbose=True):
    """Isolated maintenance timing: ``events`` merge events in one XLA scan.

    Compares the seed path (kappa row recomputed by ``rbf_row`` per event)
    against the kernel-cache engine variants on identical over-budget states.
    Returns {variant: seconds_per_event}.
    """
    slots = budget + events
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    sv = jax.random.normal(k1, (slots, dim))
    # all same sign -> every event is a genuine merge, never the fallback
    alpha = 0.1 * jnp.abs(jax.random.normal(k2, (slots,))) + 0.01
    table = default_table()

    def timed(use_cache, strategy, merge_batch=4):
        kmat = kernel_cache.exact_cache(sv, gamma) if use_cache else None

        def go():
            out = run_maintenance(sv, alpha, kmat, jnp.int32(slots),
                                  jnp.int32(0), gamma, table, budget=budget,
                                  strategy=strategy, method="lookup-wd",
                                  merge_batch=merge_batch, impl="auto")
            return out[1]
        return time_fn(go)[0] / events

    res = {
        "lookup-wd (recompute, seed)": timed(False, "merge"),
        "lookup-wd + kernel cache": timed(True, "merge"),
        "lookup-wd + cache + mm4": timed(True, "multi-merge"),
        "lookup-wd + mm4 (no cache)": timed(False, "multi-merge"),
        "removal (batched)": timed(False, "removal"),
    }
    if verbose:
        base = res["lookup-wd (recompute, seed)"]
        print(f"# maintenance_bench budget={budget} dim={dim} events={events}")
        for k, v in res.items():
            print(f"#   {k:30s} {v * 1e6:9.1f} us/event "
                  f"(x{base / v:.2f} vs seed)", flush=True)
    return res


def run(n: int = 4000, budgets=(50, 150), epochs: int = 2, datasets=None,
        stats_steps: int = 1200, verbose=True):
    rows = []
    names = datasets or list(DATASETS)
    if verbose:
        print(csv_row("dataset", "budget", "t_gss_s", "t_lookup_h_s",
                      "t_lookup_wd_s", "t_lwd_cache_s", "t_lwd_mm4_s",
                      "t_lwd_evt_s", "t_fused_step_s", "improv_h_%",
                      "improv_wd_%"))
    for name in names:
        dim, gen, gamma, lam = DATASETS[name]
        # stable digest, not hash(): str hashing is salted per process
        x, y = gen(jax.random.PRNGKey(zlib.crc32(name.encode()) % 2**31), n)
        (xtr, ytr), _ = train_test_split(x, y)
        for budget in budgets:
            times = {}
            for method in ("gss", "lookup-h", "lookup-wd"):
                cfg = BSGDConfig(budget=budget, lambda_=lam, gamma=gamma,
                                 method=method)
                times[method] = timed_fit(cfg, xtr, ytr, epochs)
            for variant, knobs in ENGINE_VARIANTS.items():
                cfg = BSGDConfig(budget=budget, lambda_=lam, gamma=gamma,
                                 **knobs)
                times[variant] = timed_fit(cfg, xtr, ytr, epochs)
            imp_h = 100 * (times["gss"] - times["lookup-h"]) / times["gss"]
            imp_wd = 100 * (times["gss"] - times["lookup-wd"]) / times["gss"]
            row = (name, budget, round(times["gss"], 3),
                   round(times["lookup-h"], 3), round(times["lookup-wd"], 3),
                   round(times["lookup-wd+cache"], 3),
                   round(times["lookup-wd+mm4"], 3),
                   round(times["lookup-wd+evt"], 3),
                   round(times["fused-step"], 3),
                   round(imp_h, 2), round(imp_wd, 2))
            rows.append(row)
            if verbose:
                print(csv_row(*row), flush=True)
        st = decision_stats(name, dim, gen, gamma, lam, steps=stats_steps)
        freq = st["events"] / max(st["steps"], 1)
        eq = st["equal"] / max(st["events"], 1)
        fg = float(np.mean(st["factor_gss"])) if st["factor_gss"] else float("nan")
        fl = float(np.mean(st["factor_lookup"])) if st["factor_lookup"] else float("nan")
        if verbose:
            print(f"# {name}: merge_freq={freq:.2%} equal_decisions={eq:.2%} "
                  f"factor_gss={fg:.5f} factor_lookupwd={fl:.5f}", flush=True)
        rows.append((name, "stats", freq, eq, fg, fl, ""))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--maintenance-only", action="store_true",
                    help="only the isolated maintenance-path microbench")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.maintenance_only:
        maintenance_bench()
        return
    if args.quick:
        maintenance_bench()
        run(n=1500, budgets=(50,), epochs=1, datasets=["SUSY", "ADULT"],
            stats_steps=400)
    else:
        maintenance_bench()
        run()


if __name__ == "__main__":
    main()
