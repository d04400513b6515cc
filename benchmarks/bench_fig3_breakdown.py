"""Paper Fig. 3: breakdown of budget-maintenance time into
  section A — solving for h / WD (GSS iterations vs table lookup), and
  section B — everything else (kappa row, argmin, executing the merge).

On TPU the equivalent split is [solver kernel] vs [rbf_row + argmin + merge
scatter]; here we measure the jit'd solver paths in isolation on
representative candidate sets, then a full maintenance event, per method.
"""
from __future__ import annotations

import argparse
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import default_table, maintenance_step, merge_math
from repro.core.budget import candidate_scores

from .common import csv_row, time_fn


def _mk_state(key, count, dim):
    k1, k2 = jax.random.split(key)
    sv_x = jax.random.normal(k1, (count, dim))
    alpha = jnp.abs(0.1 * jax.random.normal(k2, (count,))) + 0.01
    return sv_x, alpha


@partial(jax.jit, static_argnames=("method",))
def _solver_only(alpha, kappa, valid, method, table):
    return candidate_scores(alpha, kappa, 0, valid, method, table)[0]


def run(budget: int = 500, dim: int = 20, verbose=True):
    key = jax.random.PRNGKey(0)
    sv_x, alpha = _mk_state(key, budget + 1, dim)
    kappa = jax.random.uniform(key, (budget + 1,), minval=0.05, maxval=0.99)
    valid = jnp.ones((budget + 1,), bool).at[0].set(False)
    table = default_table()
    rows = []
    if verbose:
        print(csv_row("method", "sectionA_us", "full_event_us", "sectionB_us"))
    for method in ("gss-precise", "gss", "lookup-h", "lookup-wd"):
        tbl = table if method.startswith("lookup") else None
        t_a, _ = time_fn(lambda: _solver_only(alpha, kappa, valid, method, tbl),
                         warmup=2, repeats=5)
        t_full, _ = time_fn(
            lambda: maintenance_step(sv_x, alpha, jnp.int32(budget + 1), 0.5,
                                     method=method, table=tbl),
            warmup=2, repeats=5)
        row = (method, round(t_a * 1e6, 1), round(t_full * 1e6, 1),
               round(max(t_full - t_a, 0.0) * 1e6, 1))
        rows.append(row)
        if verbose:
            print(csv_row(*row), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=500)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run(budget=100 if args.quick else args.budget)


if __name__ == "__main__":
    main()
