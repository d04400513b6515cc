"""Training traffic: a shuffled stream of ``.npz`` shards through the
program's streaming trainer (``fit_stream``, or ``fit_multiclass_stream``
for C > 1), with a checkpoint every chunk and the prefetch worker on.

Set-up makes the rows from the seed on the device, writes the shards,
and drives the first ``setup_chunks`` chunks through the same trainer call
and feed that the window uses, one shard per call, keeping the state after
each.  The same state goes on into the window, which trains a fixed amount
of work: as many chunks as the program trained in ``--seconds`` on the chip
when the cell was set (``rows_per_s``), so every run of a cell does the
same work whatever the seed.  A ``--trace 1`` run traces at most
``trace_chunks`` of them, so that its trace stays small enough to write and
read inside a run's time.

After the window the plain reference (``reference.py``) follows the set-up
chunks from the empty model, and follows the window's last chunk from the
state the window checkpointed before it; each program state is compared
with the reference's (``numbers``), and the window's final state is also
held to the step count its work implies and to the kernel values of its
own support vectors.

Traffic parameters (``traffic/<mix>.json``): ``chunk_rows``,
``setup_chunks``, ``stream`` (``"single_pass"``: fresh rows for every
chunk; ``"epochs"``: ``dataset_rows`` repeated), ``rows_per_s``,
``trace_chunks``, ``probe_rows`` (held-out rows the models are compared
on), ``prefetch``, ``ckpt_every`` (1: the window's last chunk starts from a
checkpoint), ``limits`` (each compared number's limit), ``trace_python``
(the profiler's Python tracer level, which names the host work in gaps).
"""
from __future__ import annotations

import gc
import os

import numpy as np

from bench import common, reference, work
from bench import data as bench_data


# the program whose consecutive runs bound the host gaps in a trace
PROGRAM = "train_chunk"

# the model leaves a state is compared by (program and checkpoint alike)
LEAVES = ("sv_x", "alpha", "count", "step", "n_inserts", "n_merges", "kmat")


def timed_source(base):
    """A ``ChunkSource`` over ``base`` that times each load (host clock)."""
    from repro.data.stream import ChunkSource

    class Timed(ChunkSource):
        def __init__(self):
            self.chunk_lens, self.dim = base.chunk_lens, base.dim
            self.loads_s: list[float] = []

        def load(self, i: int):
            t = common.now()
            with common.span("chunk_load"):
                x, y = base.load(i)
            self.loads_s.append(common.now() - t)
            return x, y

    return Timed()


def window_chunks(traffic: dict, seconds: float, trace: bool) -> int:
    """The window's fixed work in chunks (see the module docstring)."""
    n = max(1, round(seconds * traffic["rows_per_s"] / traffic["chunk_rows"]))
    return min(n, traffic.get("trace_chunks", n)) if trace else n


def _program(cfg: dict, n_classes: int):
    """The trainer entry point and its config object."""
    if n_classes == 1:
        from repro.core.bsgd import BSGDConfig, fit_stream
        return fit_stream, BSGDConfig(**cfg)
    from repro.core.multiclass import (MulticlassSVMConfig,
                                       fit_multiclass_stream)
    return fit_multiclass_stream, MulticlassSVMConfig.create(n_classes, **cfg)


def _host(state) -> dict:
    """A program state as host arrays, by leaf name."""
    import jax
    return {k: (None if v is None else np.asarray(v))
            for k, v in jax.device_get(state._asdict()).items()}


def _order(seed: int, epoch: int, n_chunks: int, pos: int, n_rows: int):
    """(chunk id, row order) of the chunk the trainer runs at position
    ``pos`` of epoch ``epoch`` of one call: the program's documented shuffle
    contract (epoch key ``fold_in(key(seed), epoch)``; chunk order
    ``permutation(fold_in(epoch_key, 0), n_chunks)``; rows
    ``permutation(fold_in(epoch_key, 1 + chunk_id), n_rows)``)."""
    import jax
    key = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
    cid = int(np.asarray(jax.random.permutation(jax.random.fold_in(key, 0),
                                                n_chunks))[pos])
    perm = np.asarray(jax.random.permutation(
        jax.random.fold_in(key, 1 + cid), n_rows))
    return cid, perm


def _ckpt_state(ckpt_dir: str, step: int) -> dict:
    """The model leaves of checkpoint ``step`` the trainer wrote."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}", "arrays.npz")
    out = {}
    with np.load(path) as z:
        for k in z.files:
            leaf = k.replace("\\", "/").split("/")
            if leaf[0] == "state" and leaf[-1] in LEAVES:
                out[leaf[-1]] = np.asarray(z[k])
    return out


def run(ctx) -> dict:
    import jax

    from repro.data.stream import FileChunks, write_npz_chunks

    conf, traffic = ctx.config, ctx.traffic
    data, svm = conf["data"], conf["svm"]
    n_classes = data.get("n_classes", 1)
    batch, budget = svm["batch_size"], svm["budget"]
    chunk = traffic["chunk_rows"]
    if chunk % batch:
        raise ValueError("chunk_rows must be a multiple of the batch size")
    n_setup = traffic["setup_chunks"]
    n_window = window_chunks(traffic, ctx.seconds, ctx.trace)
    if traffic["stream"] == "single_pass":
        # as many shards as an untraced run writes, so that set-up runs the
        # same programs with and without the trace
        n_train = (n_setup + window_chunks(traffic, ctx.seconds, False)
                   ) * chunk
    else:
        n_train = traffic["dataset_rows"]
    n_probe = traffic["probe_rows"]

    # -- set-up: rows, shards, the first chunks through the trainer ---------
    key = jax.random.PRNGKey(ctx.pseed)
    x, y = jax.device_get(bench_data.make(data, key, n_train + n_probe))
    x, y = np.asarray(x, np.float32), np.asarray(y)
    x_probe, y_probe = x[n_train:], y[n_train:]
    shard_dir = os.path.join(ctx.tmp, "shards")
    paths = write_npz_chunks(shard_dir, x[:n_train], y[:n_train], chunk)
    del x, y
    fit, cfg = _program(svm, n_classes)
    run_kw = dict(seed=ctx.pseed, ckpt_every=traffic["ckpt_every"],
                  prefetch=traffic["prefetch"])

    setup_states, state = [], None
    for k in range(n_setup):
        with common.span(f"setup_chunk_{k}"):
            state = fit(cfg, FileChunks([paths[k]]), state=state,
                        ckpt_dir=os.path.join(ctx.tmp, f"setup_{k}"),
                        **run_kw)
            setup_states.append(_host(state))
    window_paths = (paths[n_setup:] if traffic["stream"] == "single_pass"
                    else paths)
    epochs = 1 if traffic["stream"] == "single_pass" else 10 ** 6
    # warm what the window's call adds to the set-up calls (the state copy
    # and the shuffle over the window's shard count) without training
    fit(cfg, FileChunks(window_paths), state=state, max_chunks=0, **run_kw)
    gc.collect()
    compiles0 = ctx.clock.count
    setup_s = common.now() - ctx.t_start
    ctx.say(f"set-up {setup_s:.3f} s; {ctx.clock.summary()}")

    # -- the measured window ---------------------------------------------
    source = timed_source(FileChunks(window_paths))
    win_dir = os.path.join(ctx.tmp, "window")
    with ctx.tracing(), common.span("bench_window"):
        t0 = common.now()
        state = fit(cfg, source, state=state, epochs=epochs,
                    max_chunks=n_window, ckpt_dir=win_dir, **run_kw)
        jax.block_until_ready(state)
        t_end = common.now()
    compiled_in_window = ctx.clock.count - compiles0
    device = common.device_info(ctx.chips)
    final = _host(state)
    del state

    start = setup_states[-1]
    steps = int(np.max(final["step"]) - np.max(start["step"]))
    merges = int(np.sum(final["n_merges"]) - np.sum(start["n_merges"]))
    inserts = int(np.sum(final["n_inserts"]) - np.sum(start["n_inserts"]))
    rows = steps * batch
    ctx.say(f"window {t_end - t0:.3f} s: {rows} rows in "
            f"{n_window} chunks, {steps} steps, {merges} merges, "
            f"{inserts} inserts; {compiled_in_window} compiles in the window")
    if compiled_in_window:
        ctx.say("WARNING: programs compiled inside the window")

    shape = dict(C=n_classes, S=budget + batch, d=data["dim"], B=batch)
    w = work.window_work(steps=steps, merges=merges, inserts=inserts, **shape)

    record = {
        "kind": "train", "setup_s": setup_s, "window_s": t_end - t0,
        "rows": rows, "steps": steps, "merges": merges, "inserts": inserts,
        "chunks": n_window, "chunk_load_s": source.loads_s,
        "work": w, "chips": ctx.chips, "device": device,
        "attempted": n_window, "failed": 0,
    }

    # -- correctness: the reference follows the set-up chunks and the
    # window's last chunk ------------------------------------------------
    before = (_ckpt_state(win_dir, n_window - 1) if n_window > 1
              else start)
    n_w = len(window_paths)
    g = n_window - 1
    last = (window_paths, (g // n_w, n_w, g % n_w))
    expect_step = int(np.max(start["step"])) + n_window * (chunk // batch)
    record["checks"] = compare(ctx, conf, traffic, paths[:n_setup],
                               setup_states, before, last, final, expect_step,
                               x_probe, y_probe)
    return record


def _rows(path: str, perm: np.ndarray, batch: int, n_classes: int):
    """One shard in the trainer's order, as (steps, B, d) rows and
    (steps, C, B) one-vs-rest targets."""
    with np.load(path) as z:
        x, y = z["x"][perm], z["y"][perm]
    steps = x.shape[0] // batch
    xc = x[: steps * batch].reshape(steps, batch, -1)
    yc = np.asarray(reference.ovr_targets(
        y[: steps * batch].reshape(steps, batch), n_classes))
    return xc, yc


def _shard_rows(path: str) -> int:
    with np.load(path) as z:
        return int(z["y"].shape[0])


def _ref_state(st: dict) -> reference.State:
    """A program state (host arrays) as the reference's state."""
    import jax.numpy as jnp
    sv, al = st["sv_x"], st["alpha"]
    per_class = [np.asarray(st[k]).reshape(-1) for k in
                 ("count", "n_inserts", "n_merges")]
    if sv.ndim == 2:
        sv, al = sv[None], al[None]
    return reference.State(
        sv=jnp.asarray(sv, jnp.float32), alpha=jnp.asarray(al, jnp.float32),
        count=jnp.asarray(per_class[0], jnp.int32),
        t=jnp.asarray(np.max(st["step"]), jnp.int32),
        n_inserts=jnp.asarray(per_class[1], jnp.int32),
        n_merges=jnp.asarray(per_class[2], jnp.int32))


def _chunk(conf: dict, st: reference.State, xc, yc, prec: str):
    import jax
    svm = conf["svm"]
    h_tab, wd_tab = reference.merge_tables(svm.get("grid_size", 400))
    out = reference.run_chunk(st, xc, yc, h_tab, wd_tab,
                              budget=svm["budget"], lambda_=svm["lambda_"],
                              gamma=svm["gamma"], prec=prec)
    return jax.device_get(out)


def follow(conf: dict, paths, seed: int, prec: str) -> list:
    """The reference's states after each of ``paths``, trained one shard
    per call from the empty model as the set-up does."""
    data, svm = conf["data"], conf["svm"]
    n_classes = data.get("n_classes", 1)
    st = reference.init(n_classes, svm["budget"] + svm["batch_size"],
                        data["dim"])
    out = []
    for p in paths:
        _, perm = _order(seed, 0, 1, 0, _shard_rows(p))
        xc, yc = _rows(p, perm, svm["batch_size"], n_classes)
        st = _chunk(conf, st, xc, yc, prec)
        out.append(st)
    return out


def replay(conf: dict, before: dict, last, seed: int, prec: str):
    """The reference over the window's last chunk, from ``before``."""
    paths, (epoch, n_chunks, pos) = last
    n = _shard_rows(paths[0])
    cid, perm = _order(seed, epoch, n_chunks, pos, n)
    xc, yc = _rows(paths[cid], perm, conf["svm"]["batch_size"],
                   conf["data"].get("n_classes", 1))
    return _chunk(conf, _ref_state(before), xc, yc, prec)


def _as_classes(st, gamma: float, prec: str):
    """Program state (host arrays by leaf) or reference state -> (sv
    (C,S,d), alpha (C,S), count (C,), merges (C,), step, the kernel values
    it holds among its own SVs (C,S,S)).  The program holds them in its
    kernel cache; a reference state holds none and computes them at
    ``prec``."""
    if isinstance(st, reference.State):
        held = reference.gram(st.sv, st.count, gamma=gamma, prec=prec)
        return (np.asarray(st.sv), np.asarray(st.alpha),
                np.asarray(st.count), np.asarray(st.n_merges), int(st.t),
                np.asarray(held))
    sv, al = st["sv_x"], st["alpha"]
    cnt = np.asarray(st["count"]).reshape(-1)
    mg = np.asarray(st["n_merges"]).reshape(-1)
    km = st.get("kmat")
    if sv.ndim == 2:
        sv, al = sv[None], al[None]
        km = None if km is None else km[None]
    return sv, al, cnt, mg, int(np.max(st["step"])), km


def kcache_gap(sv, count, held, gamma: float) -> float:
    """Widest gap between the kernel values a model holds for its own
    active support vectors and the plain float32 kernel of them."""
    exact = np.asarray(reference.gram(sv, count, gamma=gamma))
    act = np.arange(sv.shape[1])[None, :] < np.asarray(count)[:, None]
    mask = act[:, :, None] & act[:, None, :]
    return float(np.max(np.where(mask, np.abs(held - exact), 0.0)))


def numbers(prog, ref, x_probe, y_probe, *, gamma: float, lambda_: float,
            n_classes: int, prec: str = "f32") -> dict:
    """The compared numbers for one state: the coefficients slot by slot,
    decision values, model norms and the objective on the probe rows, the
    merge count, the step, and the kernel values the model holds against
    the plain kernel of its own SVs."""
    psv, pal, pcnt, pmg, pstep, pheld = _as_classes(prog, gamma, prec)
    rsv, ral, rcnt, rmg, rstep, _ = _as_classes(ref, gamma, "f32")
    fp = np.asarray(reference.decision(psv, pal, pcnt, x_probe, gamma=gamma))
    fr = np.asarray(reference.decision(rsv, ral, rcnt, x_probe, gamma=gamma))
    wp = np.asarray(reference.rkhs_norm(psv, pal, pcnt, gamma=gamma))
    wr = np.asarray(reference.rkhs_norm(rsv, ral, rcnt, gamma=gamma))
    t = np.asarray(reference.ovr_targets(y_probe[None], n_classes))[0]

    def objective(f, w):
        return float(np.sum(0.5 * lambda_ * w ** 2
                            + np.mean(np.maximum(0.0, 1.0 - t * f), axis=1)))

    def active(a, cnt):
        return np.where(np.arange(a.shape[1])[None] < cnt[:, None], a, 0.0)

    # coefficients slot by slot: the slot layout is the program's
    # documented one, so two runs in lockstep agree to rounding
    pa, ra = active(pal, pcnt), active(ral, rcnt)
    a_norm = np.max(np.abs(ra), axis=1)
    a_scale = np.maximum(a_norm, np.median(a_norm))
    fr_norm = np.sqrt(np.mean(fr ** 2, axis=1))
    f_scale = np.maximum(fr_norm, np.median(fr_norm))
    w_scale = np.maximum(wr, np.median(wr))
    lp, lr = objective(fp, wp), objective(fr, wr)
    return {
        "alpha_gap": float(np.max(np.max(np.abs(pa - ra), axis=1)
                                  / a_scale)),
        "f_gap": float(np.max(np.sqrt(np.mean((fp - fr) ** 2, axis=1))
                              / f_scale)),
        "norm_gap": float(np.max(np.abs(wp - wr) / w_scale)),
        "loss_gap": abs(lp - lr) / abs(lr),
        "merge_gap": abs(int(pmg.sum()) - int(rmg.sum()))
        / max(int(rmg.sum()), 1),
        "step_off": abs(pstep - rstep),
        "kcache_gap": (None if pheld is None
                       else kcache_gap(psv, pcnt, pheld, gamma)),
    }


def compare(ctx, conf, traffic, setup_paths, setup_states, before, last,
            final, expect_step: int, x_probe, y_probe) -> dict:
    """Run the reference over the set-up chunks and the window's last chunk
    and hold each number against its limit (``limits`` in the traffic
    file), at its worst over the compared states."""
    svm, n_classes = conf["svm"], conf["data"].get("n_classes", 1)
    kw = dict(gamma=svm["gamma"], lambda_=svm["lambda_"],
              n_classes=n_classes)
    ref_states = follow(conf, setup_paths, ctx.pseed, "f32")
    seen = []
    for k, (p, r) in enumerate(zip(setup_states, ref_states)):
        seen.append(numbers(p, r, x_probe, y_probe, **kw))
        ctx.say(f"set-up chunk {k + 1} vs reference: " + _fmt(seen[-1]))
    win = numbers(final, replay(conf, before, last, ctx.pseed, "f32"),
                  x_probe, y_probe, **kw)
    # the work the window ran, against the step count it implies
    win["step_off"] = max(win["step_off"],
                          abs(int(np.max(final["step"])) - expect_step))
    seen.append(win)
    ctx.say("window's last chunk vs reference from its checkpoint: "
            + _fmt(win))
    return judged(seen, traffic["limits"])


def _fmt(nums: dict) -> str:
    return ", ".join(f"{n} {v:.6g}" for n, v in nums.items()
                     if v is not None)


def judged(seen: list[dict], limits: dict) -> dict:
    """``{name: {"value", "limit"}}``: each limited number at its worst
    over the compared states."""
    return {name: {"value": max(c[name] for c in seen), "limit": limit}
            for name, limit in limits.items()}


def control(ctx) -> dict:
    """The reference at bfloat16 in the program's place over the set-up
    chunks, against the float32 reference (no window, no program)."""
    import jax

    from repro.data.stream import write_npz_chunks
    conf, traffic = ctx.config, ctx.traffic
    data, svm = conf["data"], conf["svm"]
    n_classes = data.get("n_classes", 1)
    chunk, n_cmp = traffic["chunk_rows"], traffic["setup_chunks"]
    n_probe = traffic["probe_rows"]
    n_train = (n_cmp * chunk if traffic["stream"] == "single_pass"
               else traffic["dataset_rows"])
    x, y = jax.device_get(bench_data.make(
        data, jax.random.PRNGKey(ctx.pseed), n_train + n_probe))
    x, y = np.asarray(x, np.float32), np.asarray(y)
    paths = write_npz_chunks(os.path.join(ctx.tmp, "shards"), x[:n_train],
                             y[:n_train], chunk)[:n_cmp]
    low = follow(conf, paths, ctx.pseed, "bf16")
    ref = follow(conf, paths, ctx.pseed, "f32")
    kw = dict(gamma=svm["gamma"], lambda_=svm["lambda_"],
              n_classes=n_classes)
    seen = [numbers(p, r, x[n_train:], y[n_train:], prec="bf16", **kw)
            for p, r in zip(low, ref)]
    for k, nums in enumerate(seen):
        ctx.say(f"control chunk {k + 1} vs reference: " + _fmt(nums))
    return judged(seen, traffic["limits"])
