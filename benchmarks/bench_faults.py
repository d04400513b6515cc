"""Resilience bench: what does surviving faults cost (DESIGN.md §16)?

Four cells over the same multiclass stream-train workload:

  * ``clean``      — the pre-PR path: no wrapper, no retry, no guard.
    Baseline wall-clock and test accuracy.
  * ``zero_fault`` — the FULL recovery stack (``FaultyChunks`` with an empty
    schedule, retries, the non-finite guard, a report) on clean data.  The
    acceptance gate: the final state must be BITWISE the clean cell's (the
    zero-fault path compiles to the exact pre-PR chunk programs; the stack
    costs one state copy + one scalar sync per chunk, measured here).
  * ``faulty``     — the ISSUE 10 drill: seeded transient IO errors +
    stalls, one NaN chunk, one fatal (quarantined) shard, a mid-run kill
    AND a torn newest checkpoint.  The run must complete by walking back to
    the last verifiable step, and final accuracy must land within 1% of
    clean (the quarantined shard is the only training data lost).
  * ``live``       — ``serve_svm_live`` with a crash-once chunk: the serve
    supervisor restarts the trainer from checkpoint while serving stays up;
    records restarts/retries/quarantines and the serve stats.  Every
    published snapshot is finite (asserted inside the driver).

``--smoke`` is the CI sizing and writes ``BENCH_faults.json`` (wired into
``benchmarks.run --smoke`` and uploaded as a CI artifact):

    PYTHONPATH=src python -m benchmarks.bench_faults --smoke --out BENCH_faults.json
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

import jax
import numpy as np

from repro.core import (MulticlassSVMConfig, accuracy_multiclass,
                        fit_multiclass_stream)
from repro.data import (ArrayChunks, FaultSchedule, FaultyChunks,
                        ResilienceReport, RetryPolicy, make_blobs_multiclass,
                        train_test_split)

from .common import csv_row


def _leaves_bitwise(a, b) -> bool:
    for la, lb in zip(a, b):
        if la is None or lb is None:
            if la is not lb:
                return False
            continue
        if not np.array_equal(np.asarray(la), np.asarray(lb)):
            return False
    return True


def run_faults(n: int = 2048, dim: int = 8, n_classes: int = 4,
               chunk_rows: int = 128, budget: int = 16, epochs: int = 2,
               seed: int = 0, verbose: bool = True) -> dict:
    cfg = MulticlassSVMConfig.create(n_classes, budget=budget, lambda_=1e-3,
                                     gamma=0.5, batch_size=32)
    x, y = make_blobs_multiclass(jax.random.PRNGKey(seed), n, dim,
                                 n_classes=n_classes, sep=2.0)
    (xtr, ytr), (xte, yte) = train_test_split(x, y)
    xtr, ytr = np.asarray(xtr, np.float32), np.asarray(ytr, np.int32)
    policy = RetryPolicy(max_attempts=3, base_delay_s=0.001, max_delay_s=0.01)

    def clean_src():
        return ArrayChunks(xtr, ytr, chunk_rows)

    def acc(st) -> float:
        return round(float(accuracy_multiclass(st, xte, yte,
                                               cfg.binary.gamma)), 4)

    result: dict = {"n_train": int(xtr.shape[0]), "n_chunks":
                    clean_src().n_chunks, "dim": dim, "n_classes": n_classes,
                    "budget": budget, "epochs": epochs, "seed": seed}

    # -- clean: the pre-PR baseline --------------------------------------
    # untimed warmup: pay the chunk-program jit compile once, so the
    # overhead ratios below compare steady-state walltime, not compile
    fit_multiclass_stream(cfg, clean_src(), epochs=1, seed=seed)
    t0 = time.perf_counter()
    st_clean = fit_multiclass_stream(cfg, clean_src(), epochs=epochs,
                                     seed=seed)
    t_clean = time.perf_counter() - t0
    result["clean"] = {"t_s": round(t_clean, 3), "accuracy": acc(st_clean)}

    # -- zero_fault: full stack, no faults, must be bitwise clean --------
    rep0 = ResilienceReport()
    t0 = time.perf_counter()
    st_zero = fit_multiclass_stream(
        cfg, FaultyChunks(clean_src(), FaultSchedule()), epochs=epochs,
        seed=seed, retry=policy, guard_finite=True, report=rep0)
    t_zero = time.perf_counter() - t0
    bitwise = _leaves_bitwise(st_clean, st_zero)
    if not bitwise:
        raise AssertionError(
            "zero-fault path diverged from the clean run — the resilience "
            "stack changed the realized training programs")
    if rep0.as_dict() != {"retries": 0, "recovered": [], "quarantined": [],
                          "rollbacks": [], "restarts": 0}:
        raise AssertionError(f"zero-fault run reported activity: {rep0!r}")
    result["zero_fault"] = {
        "t_s": round(t_zero, 3),
        "bitwise_identical_to_clean": bitwise,
        "guard_overhead_x": round(t_zero / t_clean, 3),
    }

    # -- faulty: transient IO + stalls + NaN chunk + fatal shard + a kill
    #    with a TORN newest checkpoint ----------------------------------
    n_chunks = clean_src().n_chunks
    sched = FaultSchedule(seed=seed, p_io=0.2, io_attempts=1, p_stall=0.1,
                          stall_s=0.001, nan_chunks=(2,),
                          fatal_chunks=(n_chunks - 2,))

    def faulty_src():
        # fresh wrapper per phase: attempt counters are in-process state
        return FaultyChunks(clean_src(), sched)

    rep = ResilienceReport()
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="bench_faults_ck_") as ck:
        fit_multiclass_stream(cfg, faulty_src(), epochs=epochs, seed=seed,
                              retry=policy, guard_finite=True, report=rep,
                              ckpt_dir=ck, ckpt_every=2,
                              max_chunks=n_chunks + 2)   # killed in epoch 2
        from repro import checkpoint as ckpt
        newest = os.path.join(ck, f"step_{ckpt.latest_step(ck):08d}",
                              "arrays.npz")
        with open(newest, "r+b") as f:
            f.truncate(17)                               # torn mid-write
        st_faulty = fit_multiclass_stream(
            cfg, faulty_src(), epochs=epochs, seed=seed, retry=policy,
            guard_finite=True, report=rep, ckpt_dir=ck, ckpt_every=2)
    t_faulty = time.perf_counter() - t0
    for leaf in (st_faulty.sv_x, st_faulty.alpha):
        if not np.isfinite(np.asarray(leaf, np.float32)).all():
            raise AssertionError("faulty run produced a non-finite state")
    acc_faulty = acc(st_faulty)
    acc_gap = round(result["clean"]["accuracy"] - acc_faulty, 4)
    if acc_gap > 0.01:
        raise AssertionError(
            f"accuracy under faults {acc_faulty} fell more than 1% below "
            f"clean {result['clean']['accuracy']}")
    result["faulty"] = {
        "t_s": round(t_faulty, 3),
        "accuracy": acc_faulty,
        "accuracy_gap_vs_clean": acc_gap,
        "recovery_overhead_x": round(t_faulty / t_clean, 3),
        "report": rep.as_dict(),
        "torn_checkpoint_walked_back": True,
    }

    # -- live: crash-once chunk under the serve supervisor ---------------
    from repro.launch.serve import serve_svm_live
    live_rep = ResilienceReport()
    t0 = time.perf_counter()
    live = serve_svm_live(
        train_rows=max(n, 1024), chunk_rows=chunk_rows, epochs=epochs,
        publish_every=2, budget=budget, n_classes=n_classes, dim=dim,
        rows=512, max_batch=64, seed=seed, verbose=False,
        faults=FaultSchedule(seed=seed, io_chunks=(1,), io_attempts=1,
                             crash_chunks=(5,), nan_chunks=(2,)),
        retry=policy, report=live_rep)
    t_live = time.perf_counter() - t0
    if live["restarts"] < 1:
        raise AssertionError("the crash chunk never exercised the supervisor")
    result["live"] = {
        "t_s": round(t_live, 3),
        "restarts": live["restarts"],
        "retries": live["retries"],
        "quarantined": live["quarantined"],
        "rollbacks": live["rollbacks"],
        "final_version": live["final_version"],
        "rows_served": live["rows"],
        "p50_ms": live["p50_ms"],
        "p99_ms": live["p99_ms"],
    }

    if verbose:
        print(csv_row("cell", "t_s", "accuracy", "overhead_x"))
        print(csv_row("clean", result["clean"]["t_s"],
                      result["clean"]["accuracy"], 1.0))
        print(csv_row("zero_fault", result["zero_fault"]["t_s"], "bitwise",
                      result["zero_fault"]["guard_overhead_x"]))
        print(csv_row("faulty", result["faulty"]["t_s"],
                      result["faulty"]["accuracy"],
                      result["faulty"]["recovery_overhead_x"]))
        print(csv_row("live", result["live"]["t_s"],
                      f"restarts={result['live']['restarts']}", "-"))
        print(f"# faulty report: {rep!r}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizing, JSON artifact to --out")
    ap.add_argument("--out", default="BENCH_faults.json")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.smoke:
        result = run_faults(n=2048)
        result["smoke"] = True
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"# wrote {args.out}")
        return
    run_faults(n=args.n, budget=32)


if __name__ == "__main__":
    main()
