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
pass over the data is one XLA program.  The entry points here are the C = 1
case of the one training engine, ``core.multiclass``, which vmaps the
per-class row step defined here (``train_step_from_rows``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

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
    dtype: str = "float32"
    sv_dtype: str | None = None
    use_kernel_cache: bool = False
    maintenance: str = "merge"         # merge | multi-merge | removal |
                                       # removal-project | quantized
    merge_batch: int = 4
    unroll_maintenance: bool = False
    maintenance_engine: str = "xla"    # xla | pallas
    step_engine: str = "composed"      # composed | pallas
    solver: str = "bsgd"               # bsgd | bdca
    bdca_rounds: int = 2
    bdca_C: float = 1.0

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
    # mask by the state's own width: a caller may replay a step under a
    # one-larger budget on the same arrays
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

    xb: (batch, dim), yb: (batch,) in {-1, +1}.  The class-axis step
    ``multiclass.train_step_ovr`` at C = 1: the state lifted to a leading
    axis of one, ``yb`` the one class's targets.
    """
    from . import multiclass   # the engine; it imports this module
    return _one_class(multiclass.train_step_ovr, cfg, table, state, xb,
                      yb[None], impl=impl)


def _one_class(fn, cfg, table, state: SVMState, *args, **kw) -> SVMState:
    """``fn(cfg, table, state, ...)`` of the class-axis engine on a binary
    ``state``: lifted to one class (C = 1), the axis dropped after."""
    out = fn(cfg, table, jax.tree.map(lambda a: a[None], state), *args, **kw)
    return jax.tree.map(lambda a: a[0], out)


def carries_padded(cfg: BSGDConfig, impl: str) -> bool:
    """Whether a scan of ``cfg``'s steps runs the fused Pallas kernel and so
    carries the state in its lane-padded layout (``multiclass.scan_ovr``).
    The ``ref`` path and the composed engine carry the state as it is."""
    return cfg.step_engine == "pallas" and kops.runs_pallas(impl)


def stream_kernel(cfg: BSGDConfig, impl: str, state: SVMState) -> str:
    """The train-step kernel that trains ``state`` under ``cfg``, by name in
    ``kops.FUSED_KERNELS``: ``"ref"`` unless a scan carries the padded
    state (``carries_padded``), else ``kops.fused_kernel`` of the state's
    lane-padded shapes (``kops.pad_fused_state``)."""
    if not carries_padded(cfg, impl):
        return "ref"
    lane = lambda n: -(-n // 128) * 128
    s, d = state.sv_x.shape[-2:]
    return kops.fused_kernel(lane(s), lane(d), cfg.grid_size,
                             lane(cfg.batch_size), state.sv_x.dtype.itemsize)


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
    source is ``train_epoch_stream``; the class-axis epoch at C = 1.
    """
    from . import multiclass
    return _one_class(multiclass.scan_epoch_ovr, cfg, table, state, x, y,
                      perm, lambda yb: yb[None], impl=impl)


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


@partial(jax.jit, static_argnames=("cfg", "impl"), donate_argnums=(2,))
def train_chunk(cfg: BSGDConfig, table, state: SVMState, xc, yc, *,
                impl: str = "auto") -> SVMState:
    """One resident chunk as a single donated-state XLA program.

    ``xc: (steps, batch, dim)``, ``yc: (steps, batch)`` — the chunk already
    shuffled and reshaped into minibatches on the host.  The class-axis chunk
    program (``multiclass.train_chunk_ovr``) at C = 1: its scan body is the
    same traced step as the in-memory ``train_epoch``, so the hot path is
    identical; donating ``state`` lets XLA update the budgeted model in
    place while chunks stream through.  The fused Pallas step carries its
    lane-padded state through the scan (``multiclass.scan_ovr``).
    """
    from . import multiclass
    return _one_class(multiclass.train_chunk_ovr, cfg, table, state, xc,
                      yc[:, None], impl=impl)


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
    from . import multiclass
    return multiclass.stream_epoch_ovr(
        partial(train_chunk, cfg, table, impl=impl), state, source,
        batch_size=cfg.batch_size, key=key, chunk_fn=chunk_fn,
        start_chunk=start_chunk, carry=carry, on_chunk=on_chunk,
        max_chunks=max_chunks, prefetch=prefetch, retry=retry, report=report,
        skip_chunks=skip_chunks)[:3]


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
    from . import multiclass
    return multiclass.fit_stream_ovr(
        cfg, partial(train_chunk, cfg, cfg.table(), impl=impl),
        lambda: init_state(cfg, source.dim), source, state=state, impl=impl,
        epochs=epochs, seed=seed, ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
        max_chunks=max_chunks, keep_last=keep_last, chunk_fn=chunk_fn,
        prefetch=prefetch, bank=bank, publish_every=publish_every,
        publish_dtype=publish_dtype, retry=retry, guard_finite=guard_finite,
        debug_invariants=debug_invariants, report=report,
        skip_chunks=skip_chunks)


def accuracy(state: SVMState, x, y, gamma, **kw) -> jax.Array:
    pred = predict(state, x, gamma, **kw)
    return jnp.mean((pred == y).astype(jnp.float32))
