"""Train-step engine microbench: µs/step composed vs fused, one JSON row per
(dim, budget, C) cell — the perf artifact behind DESIGN.md §12.

    PYTHONPATH=src python -m benchmarks.bench_train_step --smoke \
        --out BENCH_train_step.json

Each cell builds a steady-state model (bank full at exactly ``budget``, all
same-sign alphas, exact kernel caches — every violator insert forces a
maintenance event, the regime the paper trains in after warmup) and times
ONE full train step on a fixed minibatch through both engines:

  * ``composed``  — the three-phase step (``step_engine="composed"``):
                    margin launch, insert launch, then the maintenance
                    engine's event loop;
  * ``fused``     — the fused train-step megakernel path
                    (``step_engine="pallas"``: margin + insert + masked
                    event rounds in one launch chain; the Pallas kernel on
                    TPU, its jnp oracle ``ref.train_step_fused`` elsewhere —
                    ``impl="auto"``).

Both engines make bitwise-identical step decisions at every cell here
(pinned by tests/core/test_step_engine.py::test_fused_step_parity_at_bench_cells),
so µs/step rows compare like for like.  ``ratio_vs_composed`` is recorded
per cell; the acceptance target for this PR is fused <= 0.8x composed at
dim=512 / budget=256 / C=16 on the CPU CI container (methodology matches
BENCH_maintenance.json: median of 3 timed calls after 1 warmup).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.core import BSGDConfig, MulticlassSVMConfig, kernel_cache
from repro.core.bsgd import init_state, train_step
from repro.core.multiclass import init_multiclass_state, train_step_multiclass

from .common import time_fn

ENGINES = ("composed", "fused")
BATCH = 8
GAMMA = 2.0**-7
LAMBDA = 1e-3


def _cfg(budget: int, step_engine: str) -> BSGDConfig:
    return BSGDConfig(budget=budget, lambda_=LAMBDA, gamma=GAMMA,
                      batch_size=BATCH, method="lookup-wd",
                      use_kernel_cache=True, maintenance="merge",
                      step_engine=step_engine)


def _steady_state(state, c: int, budget: int, dim: int, seed: int = 0):
    """Bank full at exactly budget, same-sign alphas, exact caches: every
    violator insert this step pushes the class over budget -> event."""
    lead = () if c == 1 else (c,)
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    slots = state.alpha.shape[-1]
    sv = jax.random.normal(k1, lead + (slots, dim))
    alpha = 0.1 * jnp.abs(jax.random.normal(k2, lead + (slots,))) + 0.01
    alpha = jnp.where(jnp.arange(slots) < budget, alpha, 0.0)
    cache = (kernel_cache.exact_cache if c == 1 else
             jax.vmap(lambda s: kernel_cache.exact_cache(s, GAMMA)))
    kmat = cache(sv, GAMMA) if c == 1 else cache(sv)
    return state._replace(
        sv_x=sv.astype(state.sv_x.dtype), alpha=alpha, kmat=kmat,
        count=jnp.full(lead, budget, jnp.int32),
        step=jnp.full(lead, 3, jnp.int32))


def bench_cell(c: int, budget: int, dim: int, *, repeats: int = 3) -> dict:
    """µs/step for both engines on one (dim, budget, C) cell."""
    key = jax.random.PRNGKey(c * 7 + budget + dim)
    xb = jax.random.normal(key, (BATCH, dim))
    out = {}
    for name, engine in (("composed", "composed"), ("fused", "pallas")):
        if c == 1:
            cfg = _cfg(budget, engine)
            state = _steady_state(init_state(cfg, dim), 1, budget, dim)
            yb = jnp.where(jax.random.uniform(key, (BATCH,)) < 0.5,
                           -1.0, 1.0)
            table = cfg.table()
            fn = lambda: train_step(cfg, table, state, xb, yb, impl="auto")
        else:
            cfg = MulticlassSVMConfig(n_classes=c, binary=_cfg(budget,
                                                               engine))
            state = _steady_state(init_multiclass_state(cfg, dim), c,
                                  budget, dim)
            yb = jax.random.randint(key, (BATCH,), 0, c)
            table = cfg.table()
            fn = lambda: train_step_multiclass(cfg, table, state, xb, yb,
                                               impl="auto")
        secs, _ = time_fn(fn, warmup=1, repeats=repeats)
        out[name] = secs * 1e6
    return out


def run(*, dims=(64, 512), budgets=(256, 1024), classes=(1, 16),
        repeats: int = 3, verbose: bool = True) -> list[dict]:
    rows = []
    for dim in dims:
        for budget in budgets:
            for c in classes:
                us = bench_cell(c, budget, dim, repeats=repeats)
                row = {"dim": dim, "budget": budget,
                       "slots": budget + BATCH, "C": c, "batch": BATCH,
                       "us_per_step": {k: round(v, 1) for k, v in us.items()},
                       "ratio_vs_composed": round(
                           us["fused"] / us["composed"], 3)}
                rows.append(row)
                if verbose:
                    print(f"dim={dim:5d} budget={budget:5d} C={c:3d}  "
                          f"us/step: composed={us['composed']:10.1f}  "
                          f"fused={us['fused']:10.1f}  "
                          f"({row['ratio_vs_composed']:.2f}x composed)",
                          flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI grid: dim {64,512} x budget {256,1024} x "
                         "C {1,16} (includes the acceptance cell "
                         "dim=512/budget=256/C=16)")
    ap.add_argument("--out", default="BENCH_train_step.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        rows = run(repeats=3)
    else:
        rows = run(dims=(64, 512, 1024), repeats=5)
    payload = {"benchmark": "train_step_engines", "smoke": bool(args.smoke),
               "engines": list(ENGINES),
               "note": "one full steady-state train step (bank at budget, "
                       "batch=8 -> every violator insert forces a "
                       "maintenance event); engines are decision-bitwise "
                       "identical at every cell "
                       "(tests/core/test_step_engine.py), so rows compare "
                       "like for like",
               "rows": rows}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {args.out} ({len(rows)} cells)")


if __name__ == "__main__":
    main()
