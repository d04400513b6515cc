"""Budgeted Stochastic Gradient Descent kernel SVM (Pegasos + merge budget).

Faithful JAX port of the paper's training loop (Wang et al. 2012 BSGD with the
paper's four budget-maintenance solvers), adapted to fixed shapes:

  * SV storage has ``slots = budget + batch_size`` rows; ``count`` is the
    active watermark.  Insert = scatter at the watermark; merge = masked
    argmin + compaction (see ``core.budget``).
  * Pegasos step t:  eta_t = 1/(lambda t);  alpha *= (1 - eta_t lambda);
    every margin violator in the minibatch is inserted with
    alpha = eta_t y / batch_size;  maintenance runs until count <= budget
    via the pluggable engine in ``core.budget`` (merge / multi-merge /
    removal strategies, optionally backed by the persistent SV-SV kernel
    cache in ``core.kernel_cache`` — DESIGN.md §4-5).
  * ``batch_size = 1`` reproduces the paper's setting exactly; larger
    minibatches are the TPU-friendly configuration (see DESIGN.md §3).

Everything jits; ``train_epoch`` wraps the step in ``lax.scan`` so a whole
pass over the data is one XLA program.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import budget as budget_mod
from . import kernel_cache
from .lookup import MergeLookupTable, default_table
from ..kernels import ops as kops


class SVMState(NamedTuple):
    sv_x: jax.Array    # (slots, dim)
    alpha: jax.Array   # (slots,)
    count: jax.Array   # () int32 — active SVs
    step: jax.Array    # () int32 — Pegasos t (starts at 1)
    n_inserts: jax.Array  # () int32 — margin violations so far
    n_merges: jax.Array   # () int32 — budget-maintenance events so far
    kmat: jax.Array | None = None  # (slots, slots) SV-SV kernel cache (fp32),
                                   # or None when cfg.use_kernel_cache is off;
                                   # invariants in core.kernel_cache / DESIGN.md


@dataclasses.dataclass(frozen=True)
class BSGDConfig:
    """Budgeted-SGD hyperparameters (one binary problem).

    Attributes:
      budget: maximum active support vectors; maintenance runs whenever the
        post-insert count exceeds it (storage is ``slots = budget +
        batch_size`` rows, DESIGN.md §2).
      lambda_: Pegasos regularization; the paper's C-parameterization is
        ``lambda = 1 / (n * C)`` (``BSGDConfig.from_C``).
      gamma: RBF kernel width, k(a, b) = exp(-gamma ||a - b||^2).
      method: how merge candidates are scored — ``gss`` (runtime golden
        section search, eps 0.01), ``gss-precise`` (eps 1e-10, reference),
        ``lookup-h`` / ``lookup-wd`` (the paper's precomputed bilinear
        tables; ``lookup-wd`` needs the fewest flops).
      batch_size: minibatch rows per Pegasos step; 1 reproduces the paper,
        larger is the TPU-friendly configuration.
      grid_size: resolution of the precomputed lookup tables.
      dtype: alpha / margin arithmetic dtype.
      sv_dtype: SV row storage dtype (``"bfloat16"`` halves HBM + gather
        traffic at scale; kappa error ~1e-3); None = ``dtype``.
      use_kernel_cache: maintain the persistent (slots, slots) SV-SV kernel
        matrix so maintenance reads kappa rows instead of recomputing them
        (DESIGN.md §4).
      maintenance: what one maintenance event does — ``merge`` (paper
        Alg. 1), ``multi-merge`` (P fused pairs/event), ``removal``
        (drop smallest-|alpha|; no kernel evals), ``removal-project``
        (BOGD: drop + project mass onto survivors via cached rows) or
        ``quantized`` (fixed-centroid codebook absorbs arriving violators
        via cached rows, arXiv 1701.00167 — the online-learning strategy;
        requires the cache, xla engines only).
      merge_batch: P, pairs per fused multi-merge event.
      unroll_maintenance: inline ``batch_size`` masked events instead of the
        while_loop — bitwise loop-parity under vmap (DESIGN.md §5);
        compile size grows with ``batch_size``.
      maintenance_engine: how maintenance events execute — ``"xla"`` (the
        per-class engine in ``core.budget``; vmapped over the class axis by
        the multi-class step) or ``"pallas"`` (the fused maintenance-event
        engine: one ``kernels.ops.merge_event`` round per event, classes
        folded onto the kernel grid, sorted-excess schedule — DESIGN.md
        §11).  ``"pallas"`` requires ``use_kernel_cache=True``,
        ``maintenance="merge"`` and ``method="lookup-wd"``.
      step_engine: how a WHOLE train step executes — ``"composed"`` (margin
        rbf -> shrink/insert -> maintenance engine, three phase launches) or
        ``"pallas"`` (the fused train-step megakernel
        ``kernels/train_step.py``: margin + insert + event rounds chained in
        one launch per class block, the kernel cache maintained in VMEM
        across phases — DESIGN.md §12).  ``"pallas"`` requires
        ``use_kernel_cache=True``, ``method="lookup-wd"`` and
        ``maintenance`` in ``("merge", "multi-merge")``; on non-TPU backends
        it dispatches to the fused reference path ``ref.train_step_fused``
        (one XLA program instead of three phase launches).
      solver: which optimizer drives the working set — ``"bsgd"`` (primal
        Pegasos SGD, the source paper) or ``"bdca"`` (dual coordinate
        ascent over the budgeted bank, ``core.bdca`` / arXiv 1806.10182).
        Both share violator insertion, the kernel cache, the maintenance
        strategy layer, streaming and serving (the §14 solver contract in
        DESIGN.md).  ``"bdca"`` ascends on the cached Gram matrix, so it
        requires ``use_kernel_cache=True``; the fused train-step megakernel
        implements the Pegasos update, so ``step_engine="pallas"`` is
        incompatible (``maintenance_engine="pallas"`` composes fine).
      bdca_rounds: Gauss-Seidel coordinate-ascent sweeps over the working
        set per minibatch step (``solver="bdca"`` only).  Each sweep is one
        O(slots^2) pass over the cached Gram matrix; 2 is the
        speed/optimality sweet spot at bench sizes.
      bdca_C: the dual box constraint ``0 <= alpha_i <= C``
        (``solver="bdca"`` only).  The same C-parameterization as
        ``from_C`` — pass ``bdca_C=C`` alongside ``lambda_ = 1/(nC)`` for a
        like-for-like solver comparison.
    """

    budget: int = 100
    lambda_: float = 1e-4
    gamma: float = 1.0
    method: str = "lookup-wd"          # gss | gss-precise | lookup-h | lookup-wd
    batch_size: int = 1
    grid_size: int = 400
    dtype: str = "float32"             # alpha / margin arithmetic dtype
    sv_dtype: str | None = None        # SV row storage (bf16 halves HBM + gather
                                       # traffic at scale; kappa error ~1e-3)
    use_kernel_cache: bool = False     # persistent SV-SV kernel matrix: kappa
                                       # rows are read, not recomputed
    maintenance: str = "merge"         # merge | multi-merge | removal |
                                       # removal-project | quantized
    merge_batch: int = 4               # P pairs per fused multi-merge event
    unroll_maintenance: bool = False   # inline batch_size masked events instead
                                       # of a while_loop: bitwise loop-parity
                                       # under vmap (core.budget docstring);
                                       # compile size grows with batch_size
    maintenance_engine: str = "xla"    # xla | pallas — pallas runs the fused
                                       # all-class merge-event kernel on the
                                       # sorted-excess schedule (DESIGN.md §11)
    step_engine: str = "composed"      # composed | pallas — pallas fuses the
                                       # whole step (margin + insert + event
                                       # rounds) into one launch chain per
                                       # class block (DESIGN.md §12)
    solver: str = "bsgd"               # bsgd | bdca — primal Pegasos SGD or
                                       # dual coordinate ascent (core.bdca);
                                       # the §14 solver contract
    bdca_rounds: int = 2               # ascent sweeps per step (bdca only)
    bdca_C: float = 1.0                # dual box 0 <= alpha <= C (bdca only)

    def __post_init__(self):
        if self.maintenance not in budget_mod.STRATEGIES:
            raise ValueError(f"maintenance={self.maintenance!r} not in "
                             f"{budget_mod.STRATEGIES}")
        if self.maintenance == "multi-merge" and not (
                1 <= self.merge_batch <= self.budget):
            raise ValueError("multi-merge needs 1 <= merge_batch <= budget")
        if self.maintenance_engine not in ("xla", "pallas"):
            raise ValueError(f"maintenance_engine={self.maintenance_engine!r}"
                             " not in ('xla', 'pallas')")
        if self.maintenance_engine == "pallas" and not (
                self.use_kernel_cache and self.maintenance == "merge"
                and self.method == "lookup-wd"):
            raise ValueError(
                "maintenance_engine='pallas' runs the fused Lookup-WD merge "
                "event off the kernel cache: it requires "
                "use_kernel_cache=True, maintenance='merge' and "
                "method='lookup-wd'")
        if self.maintenance in ("removal-project", "quantized") \
                and not self.use_kernel_cache:
            raise ValueError(
                f"maintenance={self.maintenance!r} reads projection/"
                "absorption coefficients from cached kernel rows: it "
                "requires use_kernel_cache=True")
        if self.step_engine not in ("composed", "pallas"):
            raise ValueError(f"step_engine={self.step_engine!r} not in "
                             "('composed', 'pallas')")
        if self.step_engine == "pallas" and not (
                self.use_kernel_cache and self.method == "lookup-wd"
                and self.maintenance in ("merge", "multi-merge")):
            raise ValueError(
                "step_engine='pallas' runs the fused train-step megakernel "
                "off the kernel cache: it requires use_kernel_cache=True, "
                "method='lookup-wd' and maintenance in "
                "('merge', 'multi-merge')")
        if self.solver not in ("bsgd", "bdca"):
            raise ValueError(f"solver={self.solver!r} not in "
                             "('bsgd', 'bdca')")
        if self.solver == "bdca":
            if not self.use_kernel_cache:
                raise ValueError(
                    "solver='bdca' ascends on the cached working-set Gram "
                    "matrix (SVMState.kmat): it requires "
                    "use_kernel_cache=True")
            if self.step_engine == "pallas":
                raise ValueError(
                    "step_engine='pallas' fuses the Pegasos primal update; "
                    "solver='bdca' needs step_engine='composed' "
                    "(maintenance_engine='pallas' composes fine)")
            if self.bdca_rounds < 1:
                raise ValueError("solver='bdca' needs bdca_rounds >= 1")
            if not self.bdca_C > 0:
                raise ValueError("solver='bdca' needs bdca_C > 0")

    @property
    def slots(self) -> int:
        return self.budget + self.batch_size

    def table(self) -> MergeLookupTable | None:
        if self.method.startswith("lookup"):
            return default_table(self.grid_size)
        return None

    @staticmethod
    def from_C(n: int, C: float, **kw) -> "BSGDConfig":
        return BSGDConfig(lambda_=1.0 / (n * C), **kw)


def init_state(cfg: BSGDConfig, dim: int) -> SVMState:
    dt = jnp.dtype(cfg.dtype)
    # distinct zero buffers per counter: the streaming path donates the whole
    # state, and XLA rejects the same buffer donated twice
    z = lambda: jnp.zeros((), jnp.int32)
    return SVMState(
        sv_x=jnp.zeros((cfg.slots, dim), jnp.dtype(cfg.sv_dtype or cfg.dtype)),
        alpha=jnp.zeros((cfg.slots,), dt),
        count=z(), step=jnp.ones((), jnp.int32), n_inserts=z(), n_merges=z(),
        kmat=kernel_cache.init_cache(cfg.slots) if cfg.use_kernel_cache
        else None)


def decision_function(state: SVMState, x, gamma, *, impl: str = "auto"):
    """f(x) = sum_j alpha_j k(sv_j, x);  x: (n, d) -> (n,)."""
    k = kops.rbf_matrix(x, state.sv_x, gamma, impl=impl)          # (n, slots)
    active = jnp.arange(state.alpha.shape[0]) < state.count
    # fp32 on every backend (a TPU's default f32 matmul is one bf16 pass)
    return jnp.matmul(k, jnp.where(active, state.alpha, 0.0),
                      precision=jax.lax.Precision.HIGHEST)


def predict(state: SVMState, x, gamma, **kw):
    return jnp.sign(decision_function(state, x, gamma, **kw))


def insert_from_rows(cfg: BSGDConfig, state: SVMState, xb, yb, k_b,
                     k_bb=None) -> SVMState:
    """The Pegasos shrink + violator insert half of a step (no maintenance).

    Returns the post-insert state: ``count`` may exceed the budget by up to
    ``batch_size`` — the maintenance engine drains it back.  Split out of
    ``train_step_from_rows`` so the fused maintenance-event engine can vmap
    ONLY this part over the class axis and run maintenance once, outside the
    vmap, on the whole stacked state (``core.multiclass``).
    """
    slots = cfg.slots
    t = state.step
    eta = 1.0 / (cfg.lambda_ * t)

    # margins under the current model; the kernel rows k(xb, sv) are kept —
    # they double as the cache update on insert (zero extra kernel evals)
    # mask by the state's own width: callers may replay a step under a
    # one-larger budget on the same arrays (see bench_table3 decision_stats)
    active = jnp.arange(state.alpha.shape[0]) < state.count
    f = jnp.matmul(k_b.astype(state.alpha.dtype),
                   jnp.where(active, state.alpha, 0.0),
                   precision=jax.lax.Precision.HIGHEST)
    margin = yb * f

    # Pegasos shrink: w <- (1 - eta lambda) w  == alpha *= (1 - 1/t)
    alpha = state.alpha * (1.0 - eta * cfg.lambda_)

    # insert violators at the watermark (scatter with drop for non-violators)
    viol = margin < 1.0
    pos = state.count + jnp.cumsum(viol.astype(jnp.int32)) - 1
    idx = jnp.where(viol, pos, slots)                 # slots == OOB -> dropped
    sv_x = state.sv_x.at[idx].set(xb.astype(state.sv_x.dtype), mode="drop")
    new_alpha = (eta * yb / cfg.batch_size).astype(alpha.dtype)
    alpha = alpha.at[idx].set(new_alpha, mode="drop")
    n_new = jnp.sum(viol).astype(jnp.int32)

    kmat = state.kmat
    if cfg.use_kernel_cache:
        kmat = kernel_cache.insert_rows(kmat, idx, k_b, k_bb)

    return SVMState(sv_x=sv_x, alpha=alpha, count=state.count + n_new,
                    step=t + 1, n_inserts=state.n_inserts + n_new,
                    n_merges=state.n_merges, kmat=kmat)


def drain_budget(cfg: BSGDConfig, table, state: SVMState, *,
                 impl: str = "auto") -> SVMState:
    """The maintenance half of a train step, shared by every solver.

    Drains an over-budget post-insert ``count`` back to ``cfg.budget``
    through the configured strategy/engine (the §14 solver contract:
    a solver produces the insert/update half, this drain is common).
    """
    unroll = cfg.batch_size if cfg.unroll_maintenance else 0

    if cfg.maintenance_engine == "pallas":
        # the fused event engine is class-batched; the binary step lifts to
        # C = 1 (same decisions and schedule, one no-op-free grid row)
        sv_x, alpha, kmat, count, n_merges = jax.tree.map(
            lambda a: a[0],
            budget_mod.run_maintenance_classes(
                state.sv_x[None], state.alpha[None], state.kmat[None],
                state.count[None], state.n_merges[None], table,
                budget=cfg.budget, impl=impl, unroll=unroll))
    else:
        # budget maintenance until count <= budget (strategy: core.budget)
        sv_x, alpha, kmat, count, n_merges = budget_mod.run_maintenance(
            state.sv_x, state.alpha, state.kmat, state.count, state.n_merges,
            cfg.gamma, table, budget=cfg.budget, strategy=cfg.maintenance,
            method=cfg.method, merge_batch=cfg.merge_batch, impl=impl,
            unroll=unroll)

    return state._replace(sv_x=sv_x, alpha=alpha, count=count,
                          n_merges=n_merges, kmat=kmat)


@partial(jax.jit, static_argnames=("cfg", "impl"))
def train_step_from_rows(cfg: BSGDConfig, table, state: SVMState, xb, yb,
                         k_b, k_bb=None, *, impl: str = "auto") -> SVMState:
    """Pegasos minibatch step + maintenance from precomputed kernel rows.

    ``k_b = k(xb, sv_x)`` of shape (batch, slots) and — only when the kernel
    cache is on — ``k_bb = k(xb, xb)`` of shape (batch, batch).  This is the
    seam the one-vs-rest engine (``core.multiclass``) vmaps over the class
    axis: all classes' rows come from ONE fused ``rbf_matrix`` call against
    the flattened (C * slots, dim) SV bank, then each class runs this
    row-consuming step.  Everything below is vmap-clean (masked argmin/top-k,
    scatter-with-drop — no per-example control flow).
    """
    state = insert_from_rows(cfg, state, xb, yb, k_b, k_bb)
    return drain_budget(cfg, table, state, impl=impl)


@partial(jax.jit, static_argnames=("cfg", "impl"))
def train_step(cfg: BSGDConfig, table, state: SVMState, xb, yb, *,
               impl: str = "auto") -> SVMState:
    """One minibatch step + budget maintenance (``cfg.solver`` dispatch).

    xb: (batch, dim), yb: (batch,) in {-1, +1}.
    """
    if cfg.solver == "bdca":
        # dual coordinate ascent (core.bdca) — same fused margin rows, same
        # maintenance drain; only the insert/update half differs
        from . import bdca
        k_b = kops.rbf_matrix(xb, state.sv_x, cfg.gamma, impl=impl)
        k_bb = kops.rbf_matrix(xb, xb, cfg.gamma, impl=impl)
        return bdca.train_step_from_rows(cfg, table, state, xb, yb, k_b,
                                         k_bb, impl=impl)
    if cfg.step_engine == "pallas":
        # the fused megakernel is class-batched; the binary step lifts to
        # C = 1 (margin + insert + event rounds in one launch chain)
        k_bb = kops.rbf_matrix(xb, xb, cfg.gamma, impl=impl)
        sv, al, km, cnt, st_, nin, nmg = (a[0] for a in kops.train_step(
            state.sv_x[None], state.alpha[None], state.kmat[None],
            state.count[None], state.step[None], state.n_inserts[None],
            state.n_merges[None], xb, yb[None], k_bb, table,
            budget=cfg.budget, lambda_=cfg.lambda_, gamma=cfg.gamma,
            batch_size=cfg.batch_size, maintenance=cfg.maintenance,
            merge_batch=cfg.merge_batch,
            unroll=cfg.batch_size if cfg.unroll_maintenance else 0,
            impl=impl))
        return SVMState(sv_x=sv, alpha=al, count=cnt, step=st_,
                        n_inserts=nin, n_merges=nmg, kmat=km)
    k_b = kops.rbf_matrix(xb, state.sv_x, cfg.gamma, impl=impl)   # (batch, slots)
    k_bb = (kops.rbf_matrix(xb, xb, cfg.gamma, impl=impl)         # (batch, batch)
            if cfg.use_kernel_cache else None)
    return train_step_from_rows(cfg, table, state, xb, yb, k_b, k_bb,
                                impl=impl)


@partial(jax.jit, static_argnames=("cfg", "impl"))
def train_epoch(cfg: BSGDConfig, table, state: SVMState, x, y, perm, *,
                impl: str = "auto") -> SVMState:
    """One pass over resident data as a single jitted ``lax.scan``.

    Args:
      table: the precomputed ``MergeLookupTable`` (``cfg.table()``), or None
        for the gss methods.
      x: (n, d) rows; y: (n,) labels in {-1, +1}; perm: (n,) row order for
        this epoch (rows past the last full ``batch_size`` multiple are
        dropped).
    Returns the updated ``SVMState``.  The streamed counterpart over a chunk
    source is ``train_epoch_stream``.
    """
    n = perm.shape[0]
    steps = n // cfg.batch_size
    order = perm[: steps * cfg.batch_size].reshape(steps, cfg.batch_size)

    def scan_body(st, batch_idx):
        xb = jnp.take(x, batch_idx, axis=0)
        yb = jnp.take(y, batch_idx, axis=0)
        return train_step(cfg, table, st, xb, yb, impl=impl), ()

    state, _ = jax.lax.scan(scan_body, state, order)
    return state


def fit(cfg: BSGDConfig, x, y, *, epochs: int = 1, seed: int = 0,
        impl: str = "auto", state: SVMState | None = None) -> SVMState:
    """Train a budgeted SVM on in-memory data: shuffled epochs over (x, y).

    Args:
      cfg: hyperparameters (``BSGDConfig``); ``cfg.table()`` supplies the
        precomputed merge lookup when the method needs one.
      x: (n, dim) training rows; y: (n,) labels in {-1, +1}.
      epochs: passes over the data; each uses a fresh permutation derived
        from ``seed``.
      impl: kernel implementation dispatch (``auto | pallas |
        pallas_interpret | ref`` — see ``kernels.ops``).
      state: resume from an existing ``SVMState`` instead of a fresh model
        (its ``slots``/dtypes must match ``cfg``).

    Returns the final ``SVMState``.  For data larger than device memory use
    ``fit_stream`` (same model, chunked host pipeline).
    """
    table = cfg.table()
    if state is None:
        state = init_state(cfg, x.shape[1])
    key = jax.random.PRNGKey(seed)
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        perm = jax.random.permutation(sub, x.shape[0])
        state = train_epoch(cfg, table, state, x, y, perm, impl=impl)
    return state


# ---------------------------------------------------------------------------
# Streaming epochs: chunked host pipeline -> one donated-state program/chunk
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("cfg", "impl"), donate_argnums=(2,))
def train_chunk(cfg: BSGDConfig, table, state: SVMState, xc, yc, *,
                impl: str = "auto") -> SVMState:
    """One resident chunk as a single donated-state XLA program.

    ``xc: (steps, batch, dim)``, ``yc: (steps, batch)`` — the chunk already
    shuffled and reshaped into minibatches on the host.  The scan body is the
    same traced ``train_step`` as the in-memory ``train_epoch``, so the hot
    path is identical; donating ``state`` lets XLA update the budgeted model
    in place while chunks stream through.
    """
    def body(st, xy):
        xb, yb = xy
        return train_step(cfg, table, st, xb, yb, impl=impl), ()

    state, _ = jax.lax.scan(body, state, (xc, yc))
    return state


def _assemble_chunks(source, key, *, batch_size: int, start_chunk: int,
                     end: int, carry, stage=None, retry=None, report=None,
                     skip_chunks=()):
    """Host-side assembly of one epoch: yield ``(pos, xc, yc, carry)``.

    The single definition of the chunk -> minibatch-block transform shared by
    the synchronous and prefetched streaming paths (bitwise-identity between
    them is BY CONSTRUCTION: the async path runs this very generator on a
    worker thread).  Per chunk: prepend the previous chunk's remainder rows,
    reshape the batch-aligned part to ``(steps, batch, dim)`` (``xc/yc`` are
    None for a chunk that yields no full batch), and copy the new remainder
    out of the chunk buffer (O(chunk) residency promise).  ``stage`` maps the
    assembled blocks (the ``jax.device_put`` hook of the prefetched path).
    ``retry``/``report``/``skip_chunks`` pass straight to ``iter_epoch`` —
    a quarantined (or skipped) chunk contributes no rows, so the carry flows
    across it and the surviving batch sequence is bitwise the one of a run
    where the chunk never existed (DESIGN.md §16).
    """
    from ..data import stream as stream_mod

    cx, cy = carry if carry is not None else (None, None)
    for pos, x, y in stream_mod.iter_epoch(source, key,
                                           start_chunk=start_chunk,
                                           end_chunk=end, retry=retry,
                                           report=report,
                                           skip_chunks=skip_chunks):
        x, y = np.asarray(x), np.asarray(y)
        if cx is not None and cx.size:
            x = np.concatenate([cx.astype(x.dtype, copy=False), x])
            y = np.concatenate([cy.astype(y.dtype, copy=False), y])
        steps = x.shape[0] // batch_size
        used = steps * batch_size
        # copy the (< batch_size rows) remainder: a view would keep the whole
        # chunk buffer alive through the next chunk's load (O(chunk) promise)
        cx, cy = x[used:].copy(), y[used:].copy()
        xc = yc = None
        if steps:
            xc = x[:used].reshape(steps, batch_size, x.shape[1])
            yc = y[:used].reshape(steps, batch_size)
            if stage is not None:
                xc, yc = stage(xc, yc)
        yield pos, xc, yc, (cx, cy)


def _stage_chunks(gen, depth: int):
    """Run an assembly generator ``depth`` items ahead on a worker thread.

    The prefetched streaming pipeline: the worker parses/shuffles/assembles
    (and, via the generator's ``stage`` hook, ``jax.device_put``s) chunk
    ``i+1``..``i+depth`` while the consumer's donated-state scan of chunk
    ``i`` runs.  A bounded queue applies backpressure; a worker exception is
    re-raised on the CONSUMER's thread at the point the failing chunk would
    have been yielded, and abandoning the generator (early close, consumer
    exception) stops the worker promptly — no hung thread either way.
    """
    import queue as queue_mod
    import threading

    q = queue_mod.Queue(maxsize=depth)
    stop = threading.Event()
    _DONE, _FAIL = object(), object()

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue_mod.Full:
                continue
        return False

    def work():
        try:
            for item in gen:
                if not _put((None, item)):
                    return
            _put((_DONE, None))
        except BaseException as e:  # noqa: BLE001 — re-raised on consumer
            _put((_FAIL, e))

    t = threading.Thread(target=work, daemon=True, name="chunk-stager")
    t.start()
    try:
        while True:
            tag, item = q.get()
            if tag is _DONE:
                return
            if tag is _FAIL:
                raise item
            yield item
    finally:
        stop.set()
        t.join(timeout=5.0)


@jax.jit
def _tree_all_finite(tree):
    """One fused all-finite reduction over the inexact leaves of a pytree —
    the O(1)-sync non-finite sentinel of the streaming guard (int counters
    are always finite and are skipped)."""
    leaves = [leaf for leaf in jax.tree.leaves(tree)
              if jnp.issubdtype(leaf.dtype, jnp.inexact)]
    if not leaves:
        return jnp.bool_(True)
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(leaf)) for leaf in leaves]))


@dataclasses.dataclass
class _StreamGuard:
    """Per-chunk training guards for the streaming drivers (DESIGN.md §16).

    ``finite=True`` snapshots the state before each chunk program and, after
    it, runs ONE fused ``isfinite`` all-reduce over the float leaves (a
    single scalar sync).  On trip the chunk is rolled back and skipped —
    a poisoned state is never kept, never checkpointed, never published.
    ``check`` (optional, debug mode) runs a host-side validator — the cache
    invariant checker — on every accepted state.
    """

    finite: bool = True
    report: object = None       # faults.ResilienceReport (rollback tally)
    check: object = None        # callable(state) -> None, raises on violation


def _make_guard(guard_finite: bool, debug_invariants: bool, binary_cfg,
                report):
    """Resolve the ``guard_finite``/``debug_invariants`` fit-driver knobs to
    a ``_StreamGuard`` (or None — the exact pre-resilience chunk loop)."""
    if not (guard_finite or debug_invariants):
        return None
    check = None
    if debug_invariants and binary_cfg.use_kernel_cache:
        def check(state):
            kernel_cache.check_invariants(state.kmat, state.sv_x, state.count,
                                          binary_cfg.gamma)
    return _StreamGuard(finite=guard_finite, report=report, check=check)


def _stream_epoch(chunk_fn, state, source, *, batch_size: int, key,
                  start_chunk: int = 0, carry=None, on_chunk=None,
                  max_chunks: int | None = None, prefetch: int = 0,
                  stage=None, retry=None, report=None, skip_chunks=(),
                  guard=None):
    """Generic one-epoch streaming driver shared by binary and multi-class.

    ``chunk_fn(state, xc, yc) -> state`` runs one jitted chunk program.
    Rows left over when a chunk is not a multiple of ``batch_size`` *carry*
    into the next chunk (so the realized batch sequence equals the in-memory
    one on the concatenated order); the final sub-batch rows of the epoch are
    dropped, matching ``train_epoch``'s truncation.  Chunks are staged in the
    source's own dtypes (no forced cast — streamed and in-memory training see
    the same arrays); checkpointed carry rows are stored as float32 and cast
    back on resume.  ``on_chunk(state, pos, carry)`` fires after each chunk
    program — the checkpoint hook.

    ``prefetch > 0`` moves the whole host pipeline (chunk load, shuffle,
    carry splice, minibatch reshape, and — for the default single-device
    programs — the ``jax.device_put`` transfer) onto a background worker
    running up to ``prefetch`` chunks ahead of the device, double-buffered
    against the donated-state scan of the current chunk.  The worker runs the
    same ``_assemble_chunks`` generator as the sync path, so the realized
    batch sequence (and therefore training) is bitwise identical.  ``stage``
    overrides the staging transform (``None`` with a custom distributed
    ``chunk_fn`` keeps host arrays — pjit places them per its in_shardings).

    Resilience (all default-off — the zero-fault path is the exact pre-PR
    loop): ``retry``/``report``/``skip_chunks`` flow into the ingest layer
    (``iter_epoch`` — transient-failure retries, quarantine-as-skip);
    ``guard`` (a ``_StreamGuard``) snapshots the state per chunk and rolls
    back any chunk whose resulting state has a non-finite float leaf, so a
    NaN/Inf row (or a diverged update) can never persist into checkpoints or
    published ``ServeModel`` snapshots — the rollback fires BEFORE
    ``on_chunk``.

    Returns ``(state, next_chunk, carry, chunks_run)``; ``next_chunk <
    source.n_chunks`` means the epoch was cut short by ``max_chunks``.
    """
    # resolve the budget to an exclusive end position up front so chunks past
    # it are never read from the source
    end = (source.n_chunks if max_chunks is None
           else min(source.n_chunks, start_chunk + max_chunks))
    gen = _assemble_chunks(source, key, batch_size=batch_size,
                           start_chunk=start_chunk, end=end, carry=carry,
                           stage=stage if prefetch else None, retry=retry,
                           report=report, skip_chunks=skip_chunks)
    items = _stage_chunks(gen, prefetch) if prefetch else gen
    out_carry = carry
    try:
        for pos, xc, yc, out_carry in items:
            if xc is not None:
                if guard is not None and guard.finite:
                    # the chunk program donates its input state, so the
                    # last-good snapshot must be copied out BEFORE the launch
                    snap = jax.tree.map(jnp.copy, state)
                    new_state = chunk_fn(state, xc, yc)
                    if bool(_tree_all_finite(new_state)):
                        state = new_state
                    else:
                        state = snap       # roll back + skip the poisoned
                        if guard.report is not None:      # chunk wholesale
                            guard.report.note_rollback(pos)
                else:
                    state = chunk_fn(state, xc, yc)
                if guard is not None and guard.check is not None:
                    guard.check(state)
            if on_chunk is not None:
                on_chunk(state, pos, out_carry)
    finally:
        if prefetch:
            items.close()                 # stop the stager on any exit
    if out_carry is None:
        out_carry = (np.zeros((0, source.dim), np.float32),
                     np.zeros((0,), np.float32))
    return state, end, out_carry, end - start_chunk


def _ckpt_template(state: SVMState, batch_size: int, dim: int):
    """Target tree for the streaming checkpoint: model state + epoch RNG key
    + the (padded, fixed-shape) inter-chunk carry rows."""
    return {
        "state": state,
        "epoch_key": jax.random.PRNGKey(0),
        "carry_x": jnp.zeros((batch_size - 1, dim), jnp.float32),
        "carry_y": jnp.zeros((batch_size - 1,), jnp.float32),
        "carry_n": jnp.zeros((), jnp.int32),
    }


def _pad_carry(carry, batch_size: int, dim: int):
    cx, cy = carry
    n = cx.shape[0]
    px = np.zeros((batch_size - 1, dim), np.float32)
    py = np.zeros((batch_size - 1,), np.float32)
    px[:n], py[:n] = cx, cy
    return px, py, np.int32(n)


def _device_stage(xc, yc):
    """Default staging for the prefetched single-device path: start the
    host->device transfer of an assembled block from the worker thread, so
    the copy (and not just the parse) overlaps the previous chunk's scan."""
    return jax.device_put(xc), jax.device_put(yc)


def _fit_stream(batch_size: int, source, chunk_fn, state, *,
                epochs: int, seed: int, ckpt_dir, ckpt_every: int,
                max_chunks, keep_last: int, prefetch: int = 0, stage=None,
                publish=None, publish_every: int = 0, retry=None,
                report=None, skip_chunks=(), guard=None):
    """Shared multi-epoch streaming driver (see ``fit_stream`` for the
    contract).  ``publish(state)`` fires every ``publish_every`` chunks (and
    once at the very end) — the ``ModelBank`` snapshot hook.  Resume walks
    back past torn/corrupt checkpoint steps to the newest verifiable one
    (``checkpoint.latest_verifiable_step``); ``retry``/``report``/
    ``skip_chunks``/``guard`` are the §16 resilience hooks threaded into
    every epoch."""
    from .. import checkpoint as ckpt

    dim = source.dim
    n_chunks = source.n_chunks
    start_epoch, start_chunk = 0, 0
    carry, resume_key = None, None
    if ckpt_dir:
        latest = ckpt.latest_step(ckpt_dir)
        if latest is not None:
            # a torn/bit-flipped newest step (crash mid-save outside the
            # atomic-rename path, disk corruption) must not kill the restart:
            # fall back to the newest step whose checksums verify
            verified = ckpt.latest_verifiable_step(ckpt_dir)
            if verified is None:
                raise ValueError(
                    f"{ckpt_dir}: checkpoint steps {ckpt.all_steps(ckpt_dir)}"
                    " exist but none verify (manifest/arrays corrupt) — "
                    "refusing to silently restart from scratch")
            latest = verified
        if latest is not None:
            meta = ckpt.load_metadata(ckpt_dir, latest)
            if meta.get("kind") != "stream-epoch":
                raise ValueError(f"{ckpt_dir}: step {latest} is not a "
                                 "streaming checkpoint")
            # the cursor is only meaningful against the same shuffle and the
            # same chunking — a silent mismatch would train some rows twice
            # and others never, so refuse instead
            if meta["seed"] != seed:
                raise ValueError(
                    f"{ckpt_dir}: checkpoint was written with seed="
                    f"{meta['seed']}, resume called with seed={seed}")
            if meta["n_chunks"] != n_chunks:
                raise ValueError(
                    f"{ckpt_dir}: checkpoint cursor is against "
                    f"{meta['n_chunks']} chunks, source now has {n_chunks} — "
                    "re-chunked data cannot resume mid-epoch")
            tree = ckpt.load(ckpt_dir, latest,
                             _ckpt_template(state, batch_size, dim))
            state = tree["state"]
            start_epoch, start_chunk = meta["epoch"], meta["next_chunk"]
            resume_key = tree["epoch_key"]    # the interrupted epoch's key
            cn = int(tree["carry_n"])
            carry = (np.asarray(tree["carry_x"])[:cn],
                     np.asarray(tree["carry_y"])[:cn])
            if start_chunk >= n_chunks:       # checkpoint at an epoch boundary
                start_epoch, start_chunk, carry = start_epoch + 1, 0, None
                resume_key = None

    budget_left = max_chunks
    base_key = jax.random.PRNGKey(seed)
    for epoch in range(start_epoch, epochs):
        # the resumed epoch continues under its checkpointed RNG key (equal,
        # by the seed guard above, to the rederived one); later epochs fold
        epoch_key = (resume_key if epoch == start_epoch and
                     resume_key is not None
                     else jax.random.fold_in(base_key, epoch))

        def save(st, pos, cr, *, _epoch=epoch, _key=epoch_key):
            done = pos + 1
            if (publish is not None and publish_every
                    and done % publish_every == 0):
                publish(st)
            if not (ckpt_dir and ckpt_every and done % ckpt_every == 0):
                return
            px, py, cn = _pad_carry(cr, batch_size, dim)
            ckpt.save(ckpt_dir, _epoch * n_chunks + done,
                      {"state": st, "epoch_key": _key, "carry_x": px,
                       "carry_y": py, "carry_n": cn},
                      keep_last=keep_last,
                      metadata={"kind": "stream-epoch", "epoch": _epoch,
                                "next_chunk": done, "n_chunks": n_chunks,
                                "seed": seed})

        state, next_chunk, carry, ran = _stream_epoch(
            chunk_fn, state, source, batch_size=batch_size, key=epoch_key,
            start_chunk=start_chunk, carry=carry, on_chunk=save,
            max_chunks=budget_left, prefetch=prefetch, stage=stage,
            retry=retry, report=report, skip_chunks=skip_chunks, guard=guard)
        if budget_left is not None:
            budget_left -= ran
        if next_chunk < n_chunks:             # cut short by max_chunks
            if publish is not None:
                publish(state)
            return state
        jax.block_until_ready(state.alpha)    # sync only at epoch end
        start_chunk, carry = 0, None          # sub-batch remainder dropped
    if publish is not None:
        publish(state)                        # the final model always lands
    return state


def _make_publish(bank, gamma, bank_dtype):
    """Build the ``ModelBank`` snapshot hook for a streaming trainer.

    The chunk programs DONATE the state, so the next chunk invalidates the
    buffers a naive export would alias — the hook copies the state out first
    and publishes a genuinely immutable ``ServeModel`` snapshot.
    """
    if bank is None:
        return None
    from .predict import export_model   # lazy: predict imports this module

    def publish(state):
        snap = jax.tree.map(jnp.copy, state)
        bank.publish(export_model(snap, gamma, bank_dtype=bank_dtype))

    return publish


def train_epoch_stream(cfg: BSGDConfig, table, state: SVMState, source, *,
                       key=None, impl: str = "auto", start_chunk: int = 0,
                       carry=None, on_chunk=None, max_chunks: int | None = None,
                       chunk_fn=None, prefetch: int = 0, retry=None,
                       report=None, skip_chunks=()):
    """One streamed pass over a ``repro.data.stream`` chunk source.

    The chunked counterpart of ``train_epoch``: chunks are loaded on the
    host in the deterministic shuffled order derived from ``key`` (chunk
    order permuted, then rows within each chunk — ``None`` streams in natural
    order), and each becomes ONE donated-state jitted program
    (``train_chunk``); only the budgeted ``SVMState`` stays on device between
    chunks.  Remainder rows of a ragged chunk carry into the next chunk, so
    the realized minibatch sequence equals ``train_epoch`` on
    ``epoch_permutation(source, key)`` — the equivalence the stream tests pin.

    ``start_chunk``/``carry`` resume mid-epoch (see ``fit_stream`` for the
    checkpointed version); ``on_chunk(state, pos, carry)`` fires after each
    chunk; ``max_chunks`` bounds how many chunk programs run (fault drills).
    ``chunk_fn(state, xc, yc)`` overrides the jitted per-chunk program — the
    distributed path passes a pjit'd one (``launch.train.svm_stream_loop``).
    ``prefetch > 0`` assembles (and, for the default chunk program, device-
    transfers) up to that many chunks ahead on a background thread — bitwise
    the same training, the host pipeline just overlaps the device scan
    (DESIGN.md §13).

    Returns ``(state, next_chunk, carry)``; ``next_chunk == source.n_chunks``
    means the epoch completed.  The chunk programs DONATE ``state``: the
    caller's input buffers are consumed — keep using the returned state (or
    use ``fit_stream``, which copies a provided state up front).
    """
    stage = _device_stage if chunk_fn is None else None
    if chunk_fn is None:
        def chunk_fn(st, xc, yc):
            return train_chunk(cfg, table, st, xc, yc, impl=impl)
    state, next_chunk, carry, _ = _stream_epoch(
        chunk_fn, state, source, batch_size=cfg.batch_size, key=key,
        start_chunk=start_chunk, carry=carry, on_chunk=on_chunk,
        max_chunks=max_chunks, prefetch=prefetch, stage=stage, retry=retry,
        report=report, skip_chunks=skip_chunks)
    if next_chunk == source.n_chunks:
        jax.block_until_ready(state.alpha)
    return state, next_chunk, carry


def fit_stream(cfg: BSGDConfig, source, *, epochs: int = 1, seed: int = 0,
               impl: str = "auto", state: SVMState | None = None,
               ckpt_dir: str | None = None, ckpt_every: int = 0,
               max_chunks: int | None = None, keep_last: int = 3,
               chunk_fn=None, prefetch: int = 0, bank=None,
               publish_every: int = 0, publish_dtype=None, retry=None,
               guard_finite: bool = False, debug_invariants: bool = False,
               report=None, skip_chunks=()) -> SVMState:
    """Out-of-core ``fit``: shuffled streamed epochs over a chunk source.

    Args:
      source: a ``repro.data.stream.ChunkSource`` (in-memory ``ArrayChunks``,
        sharded ``FileChunks``, incremental ``LibsvmChunks``); only one chunk
        is host-resident at a time and only the budgeted state lives on
        device across chunks.
      epochs / seed: as in ``fit``; the per-epoch shuffle is the
        deterministic chunk-order + intra-chunk composition (DESIGN.md §9).
      ckpt_dir / ckpt_every: write a resumable checkpoint every
        ``ckpt_every`` chunks through ``repro.checkpoint`` (0 = off).  The
        checkpoint stores the model, the epoch RNG key, the inter-chunk carry
        rows and the ``(epoch, next_chunk)`` cursor; calling ``fit_stream``
        again with the same ``ckpt_dir`` resumes mid-epoch and reproduces the
        uninterrupted run bit-for-bit (the resume test pins this).
      max_chunks: stop after this many chunk programs without writing a final
        checkpoint — simulates a hard kill in tests/fault drills.
      chunk_fn: override the per-chunk program (distributed path).
      prefetch: assemble (and device-transfer, for the default chunk program)
        up to this many chunks ahead on a background thread — bitwise the
        same run as ``prefetch=0`` including checkpoints and resume, the host
        pipeline just overlaps the device scan (DESIGN.md §13).
      bank / publish_every / publish_dtype: publish an immutable, versioned
        ``ServeModel`` snapshot into ``bank`` (a ``core.predict.ModelBank``)
        every ``publish_every`` chunks and once at the end — the
        train-while-serve hot-swap feed.  ``publish_dtype`` quantizes the
        published bank (e.g. ``"bfloat16"``).
      retry / report / skip_chunks: the §16 ingest-resilience hooks — a
        ``data.faults.RetryPolicy`` retries transient chunk-load failures
        with bounded backoff and quarantines (skips + records in ``report``,
        a ``data.faults.ResilienceReport``) chunks that exhaust it;
        ``skip_chunks`` excludes chunk ids up front as if they never existed.
      guard_finite: snapshot the state before each chunk program and run one
        fused ``isfinite`` all-reduce over its float leaves after — a chunk
        producing any non-finite value is rolled back and skipped (recorded
        in ``report``), so NaN/Inf rows can never poison checkpoints or
        published snapshots.  Costs one state copy + one scalar sync per
        chunk; off (default) the chunk loop is exactly the pre-resilience
        program.
      debug_invariants: additionally verify the kernel-cache invariants
        I1-I3 on every accepted state (host-side, O(count^2 * dim) — debug
        only; no-op without ``use_kernel_cache``).

    Returns the final ``SVMState``.  The chunk programs run with donated
    state; a caller-provided ``state`` is copied once up front so the
    caller's arrays stay valid (same non-destructive contract as ``fit``).
    """
    table = cfg.table()
    if state is None:
        state = init_state(cfg, source.dim)
    else:
        state = jax.tree.map(jnp.array, state)   # donation would delete it
    stage = _device_stage if chunk_fn is None else None
    if chunk_fn is None:
        def chunk_fn(st, xc, yc):
            return train_chunk(cfg, table, st, xc, yc, impl=impl)
    return _fit_stream(cfg.batch_size, source, chunk_fn, state,
                       epochs=epochs, seed=seed, ckpt_dir=ckpt_dir,
                       ckpt_every=ckpt_every, max_chunks=max_chunks,
                       keep_last=keep_last, prefetch=prefetch, stage=stage,
                       publish=_make_publish(bank, cfg.gamma, publish_dtype),
                       publish_every=publish_every, retry=retry,
                       report=report, skip_chunks=skip_chunks,
                       guard=_make_guard(guard_finite, debug_invariants,
                                         cfg, report))


def accuracy(state: SVMState, x, y, gamma, **kw) -> jax.Array:
    pred = predict(state, x, gamma, **kw)
    return jnp.mean((pred == y).astype(jnp.float32))
