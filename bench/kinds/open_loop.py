"""Serve traffic: an open loop of requests through the program's
continuous-batching queue (``AsyncBatchQueue``) on a full-budget bank.

Requests arrive on a fixed schedule whether or not earlier ones are done
(independent users).  Each is timed from when it was due to when its labels
came back, so a stall also counts against every request queued behind it.
A collector thread blocks in ``take`` for each request in turn, which also
opens the queue's dispatch gate as a live caller would.

Each request row is a blend ``l * a + (1 - l) * b`` of two rows of the data
with ``l`` uniform in [0, 1], so that many lie near the boundary between
two classes: there a served label depends on the last digits of the class
scores, which is where a lower precision shows.

The schedule is one fixed multiset of request sizes and inter-arrival
gaps, drawn from ``base_seed``; ``--seed`` only permutes their order, so
every seed offers the same rows over the same span.

Traffic parameters (``traffic/<mix>.json``): ``rate_rps`` (mean arrivals
per second), ``size_median`` and ``size_sigma`` (lognormal request rows),
``size_max``, ``max_batch`` (the queue's largest microbatch),
``pool_rows`` (distinct request rows, reused cyclically),
``check_requests`` (how many answered requests the reference re-scores),
``base_seed``, ``limits`` (each compared number's limit).
"""
from __future__ import annotations

import gc
import queue as queue_mod
import threading
import time
import numpy as np

from bench import common, reference
from bench import data as bench_data


# the program whose consecutive runs bound the host gaps in a trace
PROGRAM = None


def schedule(traffic: dict, seconds: float, seed: int):
    """(sizes, due offsets in s): the base multiset, permuted by ``seed``."""
    base = np.random.default_rng(traffic["base_seed"])
    n = max(1, int(round(traffic["rate_rps"] * seconds)))
    sizes = np.clip(np.round(traffic["size_median"] * np.exp(
        traffic["size_sigma"] * base.standard_normal(n))), 1,
        traffic["size_max"]).astype(np.int64)
    gaps = base.exponential(1.0 / traffic["rate_rps"], n)
    rng = np.random.default_rng(seed)
    sizes, gaps = rng.permutation(sizes), rng.permutation(gaps)
    return sizes, np.cumsum(gaps) - gaps[0]


def make_bank(data: dict, svm: dict, traffic: dict, key):
    """A full-budget one-vs-rest bank and a pool of request rows, made on
    the device from ``key``: slots hold rows of the data, each class's
    coefficients are positive on its own rows and negative on the rest,
    with equal mass on each side;
    each request row is a random blend of two further rows."""
    import jax
    import jax.numpy as jnp
    c, budget = data["n_classes"], svm["budget"]
    slots, dim = budget + svm["batch_size"], data["dim"]

    @jax.jit
    def build(key):
        n_pool = traffic["pool_rows"]
        x, y = bench_data.make(data, key, c * slots + 2 * n_pool)
        sv, lab = x[: c * slots].reshape(c, slots, dim), y[: c * slots]
        mag = jnp.abs(jax.random.normal(jax.random.fold_in(key, 1),
                                        (c, slots)))
        live = jnp.arange(slots)[None] < budget
        own = lab.reshape(c, slots) == jnp.arange(c)[:, None]
        pos = jnp.where(own & live, mag, 0.0)
        neg = jnp.where(~own & live, mag, 0.0)
        # each class's positive and negative mass equal, so that no class
        # wins every row by a constant offset
        alpha = budget * (pos / jnp.maximum(jnp.sum(pos, 1, keepdims=True),
                                            1e-30)
                          - neg / jnp.maximum(jnp.sum(neg, 1, keepdims=True),
                                              1e-30))
        lam = jax.random.uniform(jax.random.fold_in(key, 2), (n_pool, 1))
        pool = (lam * x[c * slots: c * slots + n_pool]
                + (1.0 - lam) * x[c * slots + n_pool:])
        return sv, alpha, pool

    sv, alpha, pool = build(key)
    return sv, alpha, jnp.full((c,), budget, jnp.int32), pool


def open_loop(q, pool: np.ndarray, sizes, due, t0: float, timeout: float):
    """Drive one open loop; returns per-request (latency s, labels or None,
    row offset) and the generator's lateness per request."""
    n = len(sizes)
    offs = np.concatenate([[0], np.cumsum(sizes)[:-1]]) % (
        pool.shape[0] - int(np.max(sizes)))
    submitted: queue_mod.Queue = queue_mod.Queue()
    done = [None] * n
    latency = np.full(n, np.inf)
    late = np.zeros(n)

    def collect():
        for _ in range(n):
            i, ticket = submitted.get()
            if ticket is None:
                continue
            try:
                done[i] = q.take(ticket, timeout=timeout)
                latency[i] = common.now() - (t0 + due[i])
            except Exception as e:  # noqa: BLE001 — a failed request counts
                done[i] = e         # as missing every limit (latency inf)

    collector = threading.Thread(target=collect, name="bench-collector",
                                 daemon=True)
    collector.start()
    for i in range(n):
        wait = t0 + due[i] - common.now()
        if wait > 0:
            time.sleep(wait)
        late[i] = common.now() - (t0 + due[i])
        try:
            ticket = q.submit(pool[offs[i]: offs[i] + sizes[i]])
        except Exception as e:  # noqa: BLE001 — refused: counts as failed
            done[i] = e
            ticket = None
        submitted.put((i, ticket))
    collector.join(timeout)
    if collector.is_alive():
        raise RuntimeError(f"requests still unanswered {timeout} s after "
                           "the last arrival")
    return latency, done, offs, late


def run(ctx) -> dict:
    import jax

    from repro.core.bsgd import SVMState
    from repro.core.predict import AsyncBatchQueue, export_model

    conf, traffic = ctx.config, ctx.traffic
    data, svm = conf["data"], conf["svm"]
    key = jax.random.PRNGKey(ctx.pseed)
    sv, alpha, count, pool = make_bank(data, svm, traffic, key)
    zero = jax.numpy.zeros((data["n_classes"],), jax.numpy.int32)
    model = export_model(SVMState(sv_x=sv, alpha=alpha, count=count,
                                  step=zero, n_inserts=zero, n_merges=zero),
                         svm["gamma"])
    pool = np.asarray(pool, np.float32)
    sizes, due = schedule(traffic, ctx.seconds, ctx.seed)
    q = AsyncBatchQueue(model, max_batch=traffic["max_batch"])
    try:
        q.warmup()
        gc.collect()
        compiles0 = ctx.clock.count
        setup_s = common.now() - ctx.t_start
        ctx.say(f"set-up {setup_s:.3f} s; {ctx.clock.summary()}")
        with ctx.tracing(), common.span("bench_window"):
            t0 = common.now()
            latency, done, offs, late = open_loop(
                q, pool, sizes, due, t0, timeout=ctx.seconds + 60)
            t_end = common.now()
        stats = dict(q.stats)
        micro = list(q.latencies_s)
    finally:
        q.close()
    compiled = ctx.clock.count - compiles0
    device = common.device_info(ctx.chips)
    del model, q

    failed = int(np.sum(~np.isfinite(latency)))
    ctx.say(f"window {t_end - t0:.3f} s: {len(sizes)} requests, "
            f"{int(np.sum(sizes))} rows, {failed} failed; generator late "
            f"p50 {np.quantile(late, 0.5) * 1e3:.4f} ms, p99 "
            f"{np.quantile(late, 0.99) * 1e3:.4f} ms; "
            f"{stats['microbatches']} microbatches; {compiled} compiles in "
            "the window")
    if compiled:
        ctx.say("WARNING: programs compiled inside the window")
    record = {
        "kind": "serve", "setup_s": setup_s, "window_s": t_end - t0,
        "latency_s": latency.tolist(), "late_s": late.tolist(),
        "rows": int(np.sum(sizes)), "stats": stats,
        "microbatch_s": micro, "chips": ctx.chips, "device": device,
        "attempted": len(sizes), "failed": failed,
    }
    record["checks"] = compare(ctx, sv, alpha, count, pool, sizes, offs,
                               done, svm["gamma"], traffic)
    return record


def _sample(n: int, sizes, k: int, seed: int) -> np.ndarray:
    """``k`` requests drawn from the seed, with the largest among them."""
    rng = np.random.default_rng(seed)
    pick = rng.choice(n, size=min(k, n), replace=False)
    return np.unique(np.concatenate([pick, [int(np.argmax(sizes))]]))


def label_gap(scores: np.ndarray, labels: np.ndarray) -> float:
    """Widest gap by which a served label's reference score lies below the
    reference's best: (C, n) scores, (n,) labels."""
    best = scores.max(axis=0)
    got = scores[labels, np.arange(labels.shape[0])]
    return float(np.max(best - got)) if labels.size else 0.0


def _ref_scores(sv, alpha, count, x, gamma, prec):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(sv, alpha, count, x):
        def one(s, a, c):
            a = jnp.where(jnp.arange(a.shape[0]) < c, a, 0.0)
            k = jnp.exp(-gamma * reference.sqdist(x, s, prec))
            return reference.matvec(k, a, prec)
        return jax.vmap(one)(sv, alpha, count)

    return np.asarray(f(sv, alpha, count, x))


def compare(ctx, sv, alpha, count, pool, sizes, offs, done, gamma,
            traffic) -> dict:
    """Re-score a seeded sample of the answered requests with the plain
    reference and hold the served labels to it."""
    idx = _sample(len(sizes), sizes, traffic["check_requests"], ctx.seed)
    rows, labels, wrong_shape = [], [], 0
    for i in idx:
        got = done[i]
        if not isinstance(got, np.ndarray):
            continue                      # failed: counted in ``failed``
        if got.shape != (sizes[i],):
            wrong_shape += 1
            continue
        rows.append(pool[offs[i]: offs[i] + sizes[i]])
        labels.append(got.astype(np.int64))
    x = np.concatenate(rows) if rows else np.zeros((0, pool.shape[1]))
    lab = np.concatenate(labels) if labels else np.zeros((0,), np.int64)
    scores = _ref_scores(sv, alpha, count, x, gamma, "f32")
    gap = label_gap(scores, lab)
    ctx.say(f"re-scored {len(rows)} requests ({x.shape[0]} rows): label gap "
            f"{gap:.6g}, {int(np.sum(scores.argmax(0) != lab))} labels not "
            f"the reference's first, {wrong_shape} answers of the wrong "
            "shape")
    return {"label_gap": {"value": gap, "limit": traffic["limits"][
        "label_gap"]},
        "wrong_shape": {"value": float(wrong_shape), "limit": 0.0}}


def control(ctx) -> dict:
    """The reference at bfloat16 in the queue's place, on the same sample."""
    import jax
    conf, traffic = ctx.config, ctx.traffic
    sv, alpha, count, pool = make_bank(conf["data"], conf["svm"], traffic,
                                       jax.random.PRNGKey(ctx.pseed))
    pool = np.asarray(pool, np.float32)
    sizes, _ = schedule(traffic, ctx.seconds, ctx.seed)
    offs = np.concatenate([[0], np.cumsum(sizes)[:-1]]) % (
        pool.shape[0] - int(np.max(sizes)))
    idx = _sample(len(sizes), sizes, traffic["check_requests"], ctx.seed)
    x = np.concatenate([pool[offs[i]: offs[i] + sizes[i]] for i in idx])
    gamma = conf["svm"]["gamma"]
    low = _ref_scores(sv, alpha, count, x, gamma, "bf16").argmax(axis=0)
    scores = _ref_scores(sv, alpha, count, x, gamma, "f32")
    gap = label_gap(scores, low)
    ctx.say(f"control: bf16 labels on {x.shape[0]} rows, label gap {gap:.6g}, "
            f"{int(np.sum(scores.argmax(0) != low))} labels not the "
            "reference's first")
    return {"label_gap": {"value": gap,
                          "limit": traffic["limits"]["label_gap"]}}
