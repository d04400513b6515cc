"""Drive the stream -> train -> checkpoint -> serve path once on one TPU chip.

    python chip_smoke.py                 # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips    # class-layout training on a
                                         # 4-chip mesh vs one chip, only

Everything runs in this one process, through the library's entry points,
on data made from ``--seed``:

  (a) binary training at the SUSY shape (18 features, gamma 2^-7, lambda
      1e-5, Lookup-WD merge, kernel cache, batch 1, budget 1024): four
      65,536-row ``.npz`` shards through ``fit_stream`` with a checkpoint per
      chunk; its first chunk against the same ``fit_stream`` with
      ``impl="ref"`` (the plain float32 ``jax.numpy`` reference); one chunk
      through the training CLI's ``svm_stream_loop`` as it is configured;
  (b) class-axis training, 16 one-vs-rest classes at dim 512, budget 1024,
      batch 8, through ``fit_multiclass_stream`` with the fused Pallas
      engines and again with the composed/XLA engines;
  (c) serving both checkpoints through ``load_serve_model`` and the async
      queue of ``drive_trace`` (which asserts queue == direct labels);
  (d) each Pallas op against its reference at the phase widths.

Earlier lines report what each phase checked and its wall and compile
seconds; these are smoke timings, not benchmark numbers.  The last line is
one JSON object naming the device.  With no TPU, or if any check fails, the
script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.checkpoint import latest_step  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import kernel_cache  # noqa: E402
from repro.core.bsgd import (BSGDConfig, accuracy, fit_stream,  # noqa: E402
                             train_chunk)
from repro.core.lookup import default_table  # noqa: E402
from repro.core.multiclass import (MulticlassSVMConfig,  # noqa: E402
                                   accuracy_multiclass,
                                   fit_multiclass_stream,
                                   train_chunk_multiclass)
from repro.core.predict import (drive_trace, load_serve_model,  # noqa: E402
                                predict_labels, ragged_trace_sizes)
from repro.data import FileChunks, write_npz_chunks  # noqa: E402
from repro.data.synthetic import (make_blobs_multiclass,  # noqa: E402
                                  make_susy_like)
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.train import svm_stream_loop  # noqa: E402

# (a) SUSY shape: benchmarks/common.py's gamma and lambda
SUSY_DIM, SUSY_GAMMA, SUSY_LAMBDA = 18, 2.0**-7, 1e-5
BUDGET, CHUNK_ROWS, N_CHUNKS = 1024, 65536, 4
# (b) the class axis: C=16 one-vs-rest at dim 512; blobs close enough
# (sep 0.2) that every class keeps inserting violators past its budget
N_CLASSES, CLS_DIM, CLS_BATCH = 16, 512, 8
CLS_GAMMA, CLS_LAMBDA, CLS_SEP, CLS_CHUNK = 2.0**-10, 1e-3, 0.2, 8192
ACC_GAP = 0.005                      # 0.5 percentage points


FAILED: list[str] = []


def check(ok: bool, what: str) -> None:
    """Record one check; a failed one fails the run at its end, so one call
    to the chip reports every check."""
    if not ok:
        FAILED.append(what)
    print(f"  {'ok' if ok else 'FAILED'}: {what}", flush=True)


class CompileClock:
    """Sums JAX's backend-compile durations while a phase runs."""

    def __init__(self):
        self.secs = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs


@contextlib.contextmanager
def phase(name: str, clock: CompileClock):
    print(f"[{name}]", flush=True)
    t0, c0 = time.perf_counter(), clock.secs
    yield
    print(f"  smoke timing: {time.perf_counter() - t0:.1f} s wall, "
          f"{clock.secs - c0:.1f} s compiling", flush=True)


def check_cache(state, gamma: float, where: str) -> None:
    try:
        kernel_cache.check_invariants(state.kmat, state.sv_x, state.count,
                                      gamma)
        problem = ""
    except kernel_cache.CacheInvariantError as e:
        problem = f": {e}"
    check(not problem, f"kernel cache invariants I1-I3, {where}{problem}")


def has_kernel(fn, *args, **kw) -> bool:
    """Whether the lowered program holds a Pallas TPU kernel."""
    return "tpu_custom_call" in fn.lower(*args, **kw).as_text()


def shards(out_dir, x, y, chunk_rows):
    write_npz_chunks(out_dir, np.asarray(x), np.asarray(y), chunk_rows)
    return FileChunks(sorted(glob.glob(os.path.join(out_dir, "*.npz"))))


def phase_binary(tmp: str, seed: int) -> dict:
    n_train = N_CHUNKS * CHUNK_ROWS
    x, y = make_susy_like(jax.random.PRNGKey(seed), n_train + CHUNK_ROWS,
                          SUSY_DIM)
    x_te, y_te = x[n_train:], y[n_train:]
    source = shards(os.path.join(tmp, "susy"), x[:n_train], y[:n_train],
                    CHUNK_ROWS)
    cfg = BSGDConfig(budget=BUDGET, lambda_=SUSY_LAMBDA, gamma=SUSY_GAMMA,
                     method="lookup-wd", maintenance="merge",
                     use_kernel_cache=True, batch_size=1)
    # The reference comparison covers the first chunk: a batch-1 stream is
    # chaotic, and past one chunk the two paths' ulp-level differences in
    # the kernel rows flip a merge choice and the runs part ways.
    first = {impl: fit_stream(cfg, source, seed=seed, impl=impl, max_chunks=1)
             for impl in ("auto", "ref")}
    acc1, acc1_ref = (float(accuracy(first[impl], x_te, y_te, cfg.gamma,
                                     impl=impl)) for impl in ("auto", "ref"))
    print(f"  first chunk: held-out accuracy {acc1:.4f}, reference "
          f"{acc1_ref:.4f}; merges {int(first['auto'].n_merges)}, reference "
          f"{int(first['ref'].n_merges)}")
    check(abs(acc1 - acc1_ref) <= ACC_GAP,
          f"accuracy within {ACC_GAP * 100:.1f} pp of the reference")

    ckpt_dir = os.path.join(tmp, "ckpt_susy")
    state = fit_stream(cfg, source, seed=seed, ckpt_dir=ckpt_dir,
                       ckpt_every=1)
    acc = float(accuracy(state, x_te, y_te, cfg.gamma))
    print(f"  {N_CHUNKS} chunks: held-out accuracy {acc:.4f}")
    check(int(state.count) == BUDGET, f"count == budget ({BUDGET})")
    check(int(state.n_merges) > 0, f"{int(state.n_merges)} merges ran")
    check_cache(state, cfg.gamma, "binary")
    check(latest_step(ckpt_dir) == N_CHUNKS, "a checkpoint per chunk")
    chunk = jax.ShapeDtypeStruct((CHUNK_ROWS, 1, SUSY_DIM), jnp.float32)
    check(has_kernel(train_chunk, cfg, cfg.table(), state, chunk,
                     jax.ShapeDtypeStruct((CHUNK_ROWS, 1), jnp.float32)),
          "tpu_custom_call in the chunk program")

    # the training CLI's path, as configured today (no kernel cache)
    one = FileChunks(source.paths[:1])
    cli_state, _ = svm_stream_loop(one, budget=BUDGET, batch_size=1,
                                   gamma=SUSY_GAMMA, lambda_=SUSY_LAMBDA,
                                   seed=seed, verbose=False)
    cli_acc = float(accuracy(cli_state, x_te, y_te, SUSY_GAMMA))
    print(f"  svm_stream_loop, one chunk: held-out accuracy {cli_acc:.4f}")
    check(int(cli_state.count) == BUDGET
          and bool(jnp.all(jnp.isfinite(cli_state.alpha))),
          "svm_stream_loop chunk ran to a full, finite budget")
    return {"ckpt": ckpt_dir, "x_te": np.asarray(x_te, np.float32),
            "y_te": np.asarray(y_te), "acc": acc}


def phase_classes(tmp: str, seed: int) -> dict:
    n_train = 4 * CLS_CHUNK
    x, y = make_blobs_multiclass(jax.random.PRNGKey(seed + 1),
                                 n_train + CLS_CHUNK, CLS_DIM, N_CLASSES,
                                 sep=CLS_SEP)
    x_te, y_te = x[n_train:], y[n_train:]
    source = shards(os.path.join(tmp, "blobs"), x[:n_train], y[:n_train],
                    CLS_CHUNK)
    runs = {}
    for engines in (("pallas", "pallas"), ("xla", "composed")):
        cfg = MulticlassSVMConfig.create(
            N_CLASSES, budget=BUDGET, lambda_=CLS_LAMBDA, gamma=CLS_GAMMA,
            batch_size=CLS_BATCH, use_kernel_cache=True,
            maintenance_engine=engines[0], step_engine=engines[1])
        ckpt_dir = os.path.join(tmp, f"ckpt_{engines[1]}")
        st = fit_multiclass_stream(cfg, source, seed=seed, ckpt_dir=ckpt_dir,
                                   ckpt_every=source.n_chunks)
        acc = float(accuracy_multiclass(st, x_te, y_te, CLS_GAMMA))
        print(f"  engines {engines}: held-out accuracy {acc:.4f}, counts "
              f"{sorted(set(np.asarray(st.count).tolist()))}, merges "
              f"{int(jnp.sum(st.n_merges))}")
        check_cache(st, CLS_GAMMA, f"engines {engines}")
        check(bool(jnp.all(st.count <= BUDGET))
              and int(jnp.min(st.n_merges)) > 0,
              f"every class at or under budget and merging, {engines}")
        runs[engines[1]] = (st, acc, ckpt_dir, cfg)
    (st, acc, ckpt_dir, cfg), (_, acc_x, _, _) = runs["pallas"], \
        runs["composed"]
    check(abs(acc - acc_x) <= ACC_GAP,
          f"fused-engine accuracy within {ACC_GAP * 100:.1f} pp of the "
          "composed engines")
    chunk = jax.ShapeDtypeStruct((CLS_CHUNK // CLS_BATCH, CLS_BATCH,
                                  CLS_DIM), jnp.float32)
    check(has_kernel(train_chunk_multiclass, cfg, cfg.table(), st, chunk,
                     jax.ShapeDtypeStruct(chunk.shape[:2], jnp.int32)),
          "tpu_custom_call in the class-axis chunk program")
    return {"ckpt": ckpt_dir, "x_te": np.asarray(x_te, np.float32),
            "y_te": np.asarray(y_te), "acc": acc}


def phase_serve(binary: dict, classes: dict, seed: int) -> None:
    rng = np.random.default_rng(seed)
    for name, run, gamma in (("binary", binary, SUSY_GAMMA),
                             ("classes", classes, CLS_GAMMA)):
        model = load_serve_model(run["ckpt"], gamma)
        stats = drive_trace(model, run["x_te"][:4096],
                            ragged_trace_sizes(4096, 256, rng), queue="async")
        check(stats["rows"] == 4096,
              f"{name}: {stats['requests']} ragged requests through the "
              "async queue, labels == direct predict")
        labels = np.asarray(predict_labels(model, run["x_te"]))
        served = float(np.mean(labels.astype(np.float64)
                               == run["y_te"].astype(np.float64)))
        check(abs(served - run["acc"]) <= ACC_GAP,
              f"{name}: served accuracy {served:.4f} matches training's "
              f"{run['acc']:.4f}")


def phase_kernels(seed: int) -> None:
    """Each Pallas op against its reference at the phase widths."""
    tbl = default_table()
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 2), 32))
    nrm = lambda shape: jax.random.normal(next(keys), shape)
    uni = lambda shape, lo=0.0, hi=1.0: jax.random.uniform(
        next(keys), shape, minval=lo, maxval=hi)
    close = lambda a, b, rtol, atol: np.allclose(
        np.asarray(a), np.asarray(b), rtol=rtol, atol=atol)

    for n, m, d, g in ((1, BUDGET + 1, SUSY_DIM, SUSY_GAMMA),
                       (CLS_BATCH, N_CLASSES * (BUDGET + CLS_BATCH), CLS_DIM,
                        CLS_GAMMA)):
        x, y = nrm((n, d)), nrm((m, d))
        err = float(jnp.max(jnp.abs(ops.rbf_matrix(x, y, g, impl="pallas")
                                    - ref.rbf_matrix(x, y, g))))
        check(err <= 1e-5, f"rbf_matrix ({n} x {m}, d={d}): max abs error "
              f"{err:.2e} <= 1e-5")

    for shape in ((BUDGET + 1,), (N_CLASSES, BUDGET + CLS_BATCH)):
        alpha = jnp.abs(nrm(shape)) * 0.2 + 0.01
        kappa = uni(shape)
        valid = jax.random.bernoulli(next(keys), 0.8, shape)
        a_min = (jnp.abs(nrm(shape[:-1])) * 0.05 if len(shape) == 2
                 else jnp.float32(0.05))
        wd_p, in_p = ops.merge_scores(alpha, kappa, valid, a_min,
                                      tbl.wd_table, impl="pallas")
        wd_r, in_r = ops.merge_scores(alpha, kappa, valid, a_min,
                                      tbl.wd_table, impl="ref")
        mask = np.asarray(valid)
        check(np.allclose(np.asarray(wd_p)[mask], np.asarray(wd_r)[mask],
                          rtol=1e-4, atol=1e-7)
              and close(in_p, in_r, 1e-4, 1e-6),
              f"merge_scores {shape} (Lookup-WD) matches the reference")

    s = BUDGET + CLS_BATCH
    alpha = jnp.abs(nrm((s,))) * 0.2 + 0.01
    kappa, valid = uni((4, s)), jax.random.bernoulli(next(keys), 0.8, (4, s))
    a_min = jnp.abs(nrm((4,))) * 0.05
    wd_p, h_p = ops.multi_merge_scores(alpha, kappa, valid, a_min, tbl,
                                       impl="pallas")
    wd_r, h_r = ops.multi_merge_scores(alpha, kappa, valid, a_min, tbl,
                                       impl="ref")
    mask = np.asarray(valid)
    check(np.allclose(np.asarray(wd_p)[mask], np.asarray(wd_r)[mask],
                      rtol=1e-4, atol=1e-7) and close(h_p, h_r, 1e-4, 1e-5),
          f"multi_merge_scores (4 x {s}) matches the reference")

    m, kap = uni((4096,), 0.01, 0.99), uni((4096,), 0.15, 0.999)
    check(close(ops.gss_solve(m, kap, n_iters=10, impl="pallas"),
                ref.gss(m, kap, 10), 1e-5, 1e-5),
          "gss_solve (4096) matches the reference")

    c, d = N_CLASSES, CLS_DIM
    sv = nrm((c, s, d)) * 0.05
    counts = jax.random.randint(next(keys), (c,), s // 2, s + 1)
    counts = counts.astype(jnp.int32)
    al = jnp.where(jnp.arange(s)[None, :] < counts[:, None],
                   0.1 * nrm((c, s)), 0.0)
    km = jax.vmap(lambda v: kernel_cache.exact_cache(v, CLS_GAMMA))(sv)
    over = jnp.arange(c) % 2 == 0
    got = ops.merge_event(sv, al, km, counts, over, tbl, impl="pallas")
    want = ops.merge_event(sv, al, km, counts, over, tbl, impl="ref")
    check(all(close(g_, w_, 1e-5, 1e-5) for g_, w_ in zip(got, want))
          and all(np.array_equal(np.asarray(g_)[1::2], np.asarray(o)[1::2])
                  for g_, o in zip(got, (sv, al, km))),
          f"merge_event (C={c}, S={s}, D={d}) matches the reference")

    count = jnp.full((c,), BUDGET - 2, jnp.int32)
    al = jnp.where(jnp.arange(s)[None, :] < count[:, None],
                   0.05 * nrm((c, s)), 0.0)
    xb = nrm((CLS_BATCH, d)) * 0.05
    yb = jnp.where(uni((c, CLS_BATCH)) < 0.5, -1.0, 1.0)
    k_bb = ref.rbf_matrix(xb, xb, CLS_GAMMA)
    args = (sv, al, km, count, jnp.full((c,), 5, jnp.int32),
            jnp.zeros((c,), jnp.int32), jnp.zeros((c,), jnp.int32), xb, yb,
            k_bb, tbl)
    kw = dict(budget=BUDGET, lambda_=CLS_LAMBDA, gamma=CLS_GAMMA,
              batch_size=CLS_BATCH, maintenance="merge")
    got = ops.train_step(*args, impl="pallas", **kw)
    want = ops.train_step(*args, impl="ref", **kw)
    ints = all(np.array_equal(np.asarray(g_), np.asarray(w_))
               for g_, w_ in zip(got[3:], want[3:]))
    floats = all(close(g_, w_, 1e-5, 5e-5) for g_, w_ in zip(got[:3],
                                                             want[:3]))
    check(ints and floats and int(jnp.sum(want[6])) > 0,
          f"train_step (C={c}, S={s}, D={d}, merge) matches the reference: "
          "counters bitwise, floats within fp32 round-off")


def four_chips(seed: int) -> None:
    """The C=16 class-layout chunk program on a 4-chip mesh (classes spread
    over the chips) against the same stream on one chip."""
    from jax.sharding import Mesh

    devices = jax.devices()
    check(len(devices) == 4, f"{len(devices)} chips")
    x, y = make_blobs_multiclass(jax.random.PRNGKey(seed + 1), 2 * CLS_CHUNK,
                                 CLS_DIM, N_CLASSES, sep=CLS_SEP)
    kw = dict(layout="class", n_classes=N_CLASSES, budget=BUDGET,
              batch_size=CLS_BATCH, gamma=CLS_GAMMA, lambda_=CLS_LAMBDA,
              seed=seed, verbose=False)
    with tempfile.TemporaryDirectory() as tmp:
        source = shards(tmp, x, y, CLS_CHUNK)
        st4, _ = svm_stream_loop(source, **kw)
        one = Mesh(np.asarray(devices[:1]).reshape(1, 1), ("data", "model"))
        st1, _ = svm_stream_loop(source, mesh=one, **kw)
    spread = {d.id for d in st4.sv_x.sharding.device_set}
    check(len(spread) == 4 and not st4.sv_x.sharding.is_fully_replicated,
          "classes sharded over 4 chips")
    print(f"  counts {sorted(set(np.asarray(st4.count).tolist()))}, merges "
          f"{int(jnp.sum(st4.n_merges))}")
    check(int(jnp.min(st4.n_merges)) > 0, "every class merged")
    for name in ("count", "n_inserts", "n_merges"):
        check(np.array_equal(np.asarray(getattr(st4, name)),
                             np.asarray(getattr(st1, name))),
              f"{name} equal on 4 chips and on one")
    for name in ("alpha", "sv_x"):
        a4, a1 = np.asarray(getattr(st4, name)), np.asarray(getattr(st1, name))
        err = float(np.max(np.abs(a4 - a1)))
        check(np.allclose(a4, a1, rtol=1e-5, atol=1e-6),
              f"{name} within fp32 round-off (max abs diff {err:.2e})")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the class-layout chunk program on a "
                         "4-chip mesh and the same stream on one chip")
    args = ap.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform}",
              file=sys.stderr)
        return 2
    print(f"device: {devices[0].device_kind} x {len(devices)}", flush=True)
    enable_compile_cache()
    clock = CompileClock()
    if args.four_chips:
        with phase("four chips: class layout", clock):
            four_chips(args.seed)
    else:
        with tempfile.TemporaryDirectory() as tmp:
            with phase("a: binary stream, SUSY shape", clock):
                binary = phase_binary(tmp, args.seed)
            with phase("b: class axis, C=16", clock):
                classes = phase_classes(tmp, args.seed)
            with phase("c: serve both checkpoints", clock):
                phase_serve(binary, classes, args.seed)
        with phase("d: kernels against their references", clock):
            phase_kernels(args.seed)
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed: {FAILED}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
