"""Paper Table 2: test accuracy of GSS-precise / GSS / Lookup-h / Lookup-WD
across datasets and budget sizes — the "no accuracy loss" claim.

``--multiclass`` adds the one-vs-rest mode this repo grows on top of the
paper: per-class merge counts plus wall-clock of the batched lockstep engine
(one fused all-class kernel contraction per step) vs the loop-over-classes
baseline.  ``--solver`` runs the bsgd-vs-bdca head-to-head (time-to-accuracy
on identical streams, binary + OVR).  ``--smoke`` runs a CI-sized subset of
all three and writes the results as JSON (the ``BENCH_*.json``
perf-trajectory artifact).
"""
from __future__ import annotations

import argparse
import json
import zlib

import jax
import numpy as np

from repro.core import (BSGDConfig, METHODS, MulticlassSVMConfig, accuracy,
                        accuracy_multiclass, fit, fit_multiclass,
                        fit_multiclass_loop)
from repro.core.bdca import box_from_lambda
from repro.data.synthetic import make_blobs_multiclass, train_test_split

from .common import DATASETS, csv_row, time_fn

ORDER = ("gss-precise", "gss", "lookup-h", "lookup-wd")


def run(n: int = 3000, budgets=(50, 150), epochs: int = 2, seeds=(0, 1, 2),
        datasets=None, verbose=True):
    rows = []
    names = datasets or list(DATASETS)
    if verbose:
        print(csv_row("dataset", "budget", "method", "acc_mean", "acc_std"))
    for name in names:
        dim, gen, gamma, lam = DATASETS[name]
        # stable digest, not hash(): str hashing is salted per process, and
        # the --smoke artifact must benchmark the SAME dataset every CI run
        x, y = gen(jax.random.PRNGKey(zlib.crc32(name.encode()) % 2**31), n)
        (xtr, ytr), (xte, yte) = train_test_split(x, y)
        for budget in budgets:
            for method in ORDER:
                accs = []
                for seed in seeds:
                    cfg = BSGDConfig(budget=budget, lambda_=lam, gamma=gamma,
                                     method=method, batch_size=1)
                    st = fit(cfg, xtr, ytr, epochs=epochs, seed=seed)
                    accs.append(float(accuracy(st, xte, yte, gamma)))
                row = (name, budget, method, round(float(np.mean(accs)), 4),
                       round(float(np.std(accs)), 4))
                rows.append(row)
                if verbose:
                    print(csv_row(*row), flush=True)
    # the paper's claim: spread between methods within noise
    by_cell = {}
    for name, budget, method, mean, std in rows:
        by_cell.setdefault((name, budget), {})[method] = (mean, std)
    for cell, accs in by_cell.items():
        spread = max(a for a, _ in accs.values()) - min(a for a, _ in accs.values())
        max_std = max(s for _, s in accs.values())
        if verbose:
            print(f"# {cell}: method spread {spread:.4f} "
                  f"(max run std {max_std:.4f})")
    return rows


def run_multiclass(n: int = 6000, n_classes: int = 16, dim: int = 20,
                   budget: int = 50, batch_size: int = 1, verbose=True):
    """One-vs-rest mode: accuracy, per-class merge counts, and wall-clock of
    the batched lockstep engine vs the loop-over-classes baseline (identical
    models — same seed means same permutations)."""
    x, y = make_blobs_multiclass(jax.random.PRNGKey(0), n, dim, n_classes,
                                 sep=1.0)
    (xtr, ytr), (xte, yte) = train_test_split(x, y)
    cfg = MulticlassSVMConfig.create(n_classes, budget=budget, lambda_=1e-4,
                                     gamma=0.1, method="lookup-wd",
                                     batch_size=batch_size)

    def timed(fit_fn):
        t, st = time_fn(lambda: fit_fn(cfg, xtr, ytr, epochs=1, seed=0))
        return t, st

    t_batched, st = timed(fit_multiclass)
    t_loop, st_loop = timed(fit_multiclass_loop)
    g = cfg.binary.gamma
    result = {
        "n_train": int(xtr.shape[0]), "dim": dim, "n_classes": n_classes,
        "budget": budget, "batch_size": batch_size,
        "acc_batched": round(float(accuracy_multiclass(st, xte, yte, g)), 4),
        "acc_loop": round(float(accuracy_multiclass(st_loop, xte, yte, g)), 4),
        "t_batched_s": round(t_batched, 3),
        "t_loop_s": round(t_loop, 3),
        "speedup_batched_vs_loop": round(t_loop / t_batched, 3),
        "merges_per_class": np.asarray(st.n_merges).tolist(),
        "sv_count_per_class": np.asarray(st.count).tolist(),
    }
    if verbose:
        print(csv_row("mode", "classes", "budget", "acc", "t_batched_s",
                      "t_loop_s", "speedup"))
        print(csv_row("ovr-batched", n_classes, budget, result["acc_batched"],
                      result["t_batched_s"], result["t_loop_s"],
                      result["speedup_batched_vs_loop"]), flush=True)
        print(f"# per-class merges: {result['merges_per_class']}")
    return result


def run_solvers(n: int = 3000, budget: int = 50, epochs: int = 2,
                batch_size: int = 8, datasets=None, n_classes: int = 5,
                verbose=True):
    """Head-to-head time-to-accuracy: the primal Pegasos solver (bsgd) vs the
    dual coordinate-ascent solver (bdca) on identical streams — same budget,
    same lookup-wd maintenance, same kernel cache, same batches.  bdca's box
    comes from ``core.bdca.box_from_lambda`` at each dataset's own paper
    lambda and train size — the clamped Pegasos correspondence, so the dual
    runs at the table's hyperparameters instead of a hand-tuned constant.
    Binary rows per dataset plus one OVR multiclass row per solver."""
    names = datasets or list(DATASETS)
    rows = []
    if verbose:
        print(csv_row("dataset", "mode", "solver", "acc", "t_fit_s"))
    for name in names:
        dim, gen, gamma, lam = DATASETS[name]
        x, y = gen(jax.random.PRNGKey(zlib.crc32(name.encode()) % 2**31), n)
        (xtr, ytr), (xte, yte) = train_test_split(x, y)
        for solver in ("bsgd", "bdca"):
            cfg = BSGDConfig(budget=budget, lambda_=lam, gamma=gamma,
                             method="lookup-wd", batch_size=batch_size,
                             use_kernel_cache=True, solver=solver,
                             bdca_C=box_from_lambda(xtr.shape[0], lam))
            t, st = time_fn(
                lambda c=cfg: fit(c, xtr, ytr, epochs=epochs, seed=0),
                warmup=1, repeats=1)
            row = {"dataset": name, "mode": "binary", "solver": solver,
                   "acc": round(float(accuracy(st, xte, yte, gamma)), 4),
                   "t_fit_s": round(t, 3)}
            rows.append(row)
            if verbose:
                print(csv_row(*row.values()), flush=True)
    xm, ym = make_blobs_multiclass(jax.random.PRNGKey(7), n, 20, n_classes,
                                   sep=1.0)
    (xtr, ytr), (xte, yte) = train_test_split(xm, ym)
    for solver in ("bsgd", "bdca"):
        cfg = MulticlassSVMConfig.create(
            n_classes, budget=budget, lambda_=1e-4, gamma=0.1,
            method="lookup-wd", batch_size=batch_size,
            use_kernel_cache=True, solver=solver,
            bdca_C=box_from_lambda(xtr.shape[0], 1e-4))
        t, st = time_fn(
            lambda c=cfg: fit_multiclass(c, xtr, ytr, epochs=epochs, seed=0),
            warmup=1, repeats=1)
        row = {"dataset": f"blobs-{n_classes}c", "mode": "ovr",
               "solver": solver,
               "acc": round(float(accuracy_multiclass(st, xte, yte, 0.1)), 4),
               "t_fit_s": round(t, 3)}
        rows.append(row)
        if verbose:
            print(csv_row(*row.values()), flush=True)
    # the acceptance-level readout: per-cell accuracy gap between solvers
    for i in range(0, len(rows), 2):
        a, b = rows[i], rows[i + 1]
        if verbose:
            print(f"# {a['dataset']}/{a['mode']}: bsgd {a['acc']} vs "
                  f"bdca {b['acc']} (gap {abs(a['acc'] - b['acc']):.4f})")
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=3000)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--multiclass", action="store_true",
                    help="one-vs-rest mode: batched engine vs class loop")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized binary + multiclass run, JSON to --out")
    ap.add_argument("--solver", action="store_true",
                    help="head-to-head: bsgd vs bdca time-to-accuracy")
    ap.add_argument("--out", default="BENCH_table2_accuracy.json",
                    help="JSON output path for --smoke")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        rows = run(n=1200, budgets=(50,), epochs=1, seeds=(0,),
                   datasets=["SUSY", "IJCNN"])
        mc = run_multiclass(n=2500, n_classes=5, budget=30)
        solver_rows = run_solvers(n=1600, budget=40, epochs=2,
                                  datasets=["SKIN", "WEB"])
        with open(args.out, "w") as f:
            json.dump({"binary_rows": rows, "multiclass": mc,
                       "solver_head_to_head": solver_rows}, f, indent=2)
        print(f"# wrote {args.out}")
        return
    if args.solver:
        run_solvers(n=args.n)
        return
    if args.multiclass:
        run_multiclass(n=args.n * 2)
        return
    if args.quick:
        run(n=1200, budgets=(50,), epochs=1, seeds=(0,),
            datasets=["SUSY", "IJCNN"])
    else:
        run(n=args.n)


if __name__ == "__main__":
    main()
