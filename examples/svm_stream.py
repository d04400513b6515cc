"""Streaming-epoch BSGD demo: train over a chunked ON-DISK dataset that never
sits in memory whole, match the in-memory model, and survive a mid-epoch kill.

    PYTHONPATH=src python examples/svm_stream.py [--n 8192] [--chunk-rows 1024]

What it shows (DESIGN.md §9):
  1. the dataset is sharded into on-disk ``.npz`` chunks, at least
     ``--min-ratio`` (default 4) times larger than any single resident chunk;
  2. one streamed pass (``fit_stream`` over ``FileChunks``) reproduces the
     in-memory ``train_epoch`` on the SAME realized row order — allclose
     state, equal accuracy;
  3. a run killed mid-epoch (``max_chunks``) resumes from its every-2-chunks
     checkpoint and finishes BITWISE identical to the uninterrupted run;
  4. streamed rows/sec on this host (rows/s on the chip is the benchmark's
     ``train_rows_per_s``: ``python3 bench/run.py``, PERF.md).
"""
import argparse
import os
import tempfile
import time

import jax
import numpy as np

from repro.core import (BSGDConfig, accuracy, fit, fit_stream, init_state,
                        train_epoch)
from repro.data import (FileChunks, epoch_permutation, make_susy_like,
                        train_test_split, write_npz_chunks)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=8192)
    ap.add_argument("--chunk-rows", type=int, default=1024)
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--min-ratio", type=int, default=4,
                    help="dataset must be >= this many resident chunks")
    args = ap.parse_args()

    x, y = make_susy_like(jax.random.PRNGKey(1), args.n)
    (xtr, ytr), (xte, yte) = train_test_split(x, y)
    xtr, ytr = np.asarray(xtr), np.asarray(ytr)
    cfg = BSGDConfig(budget=args.budget, lambda_=2e-5, gamma=2.0**-7,
                     batch_size=args.batch_size)

    with tempfile.TemporaryDirectory() as tmp:
        paths = write_npz_chunks(os.path.join(tmp, "shards"), xtr, ytr,
                                 args.chunk_rows)
        source = FileChunks(paths)
        ratio = source.n_rows / max(source.chunk_lens)
        print(f"SUSY-like on disk: {source.n_rows} rows in {source.n_chunks} "
              f"chunks of <= {max(source.chunk_lens)} "
              f"({ratio:.1f}x larger than any resident chunk)")
        assert ratio >= args.min_ratio, \
            f"dataset only {ratio:.1f}x a chunk (need >= {args.min_ratio})"

        # -- 1. streamed single pass ------------------------------------
        t0 = time.perf_counter()
        st_stream = fit_stream(cfg, source, epochs=1, seed=0)
        dt = time.perf_counter() - t0
        acc_stream = float(accuracy(st_stream, xte, yte, cfg.gamma))
        print(f"  streamed:  time={dt:6.2f}s rows/sec={source.n_rows/dt:,.0f} "
              f"acc={acc_stream:.4f} SVs={int(st_stream.count)}")

        # -- 2. in-memory reference on the SAME realized order ----------
        ekey = jax.random.fold_in(jax.random.PRNGKey(0), 0)
        perm = epoch_permutation(source, ekey)
        t0 = time.perf_counter()
        st_mem = train_epoch(cfg, cfg.table(), init_state(cfg, source.dim),
                             xtr, ytr, perm)
        jax.block_until_ready(st_mem.alpha)
        dt_mem = time.perf_counter() - t0
        acc_mem = float(accuracy(st_mem, xte, yte, cfg.gamma))
        print(f"  in-memory: time={dt_mem:6.2f}s "
              f"rows/sec={source.n_rows/dt_mem:,.0f} acc={acc_mem:.4f}")
        # the states are allclose (below), not bitwise — chunked scans are
        # different XLA programs — so allow the drift to flip a few test
        # points sitting exactly on the decision boundary
        assert abs(acc_stream - acc_mem) <= 2.0 / len(yte), (acc_stream, acc_mem)
        for name, a, b in zip(st_mem._fields, st_mem, st_stream):
            if a is not None:
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-6, err_msg=name)
        print("  state allclose to in-memory train_epoch on the same order")

        # -- 3. kill mid-epoch, resume from checkpoint ------------------
        ck = os.path.join(tmp, "ckpt")
        kill_after = source.n_chunks // 2 + 1
        fit_stream(cfg, source, epochs=1, seed=0, ckpt_dir=ck, ckpt_every=2,
                   max_chunks=kill_after)        # "SIGKILL" after N chunks
        st_resumed = fit_stream(cfg, source, epochs=1, seed=0, ckpt_dir=ck,
                                ckpt_every=2)    # picks up the cursor
        for name, a, b in zip(st_stream._fields, st_stream, st_resumed):
            if a is not None:
                assert np.array_equal(np.asarray(a), np.asarray(b)), name
        print(f"  killed after {kill_after}/{source.n_chunks} chunks, resumed "
              "mid-epoch: final state BITWISE identical")


if __name__ == "__main__":
    main()
