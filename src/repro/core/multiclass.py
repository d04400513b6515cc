"""One-vs-rest multi-class BSGD: the class axis as a leading state dimension.

The paper's lookup-based merge makes budget maintenance cheap enough to run
*per class per step* — exactly what one-vs-rest multi-class kernel SVMs need
(Picard 2018 shows budgeted kernel SVMs paying off in large multi-class
regimes).  This module stacks C independent binary BSGD problems into one
``SVMState`` whose every array carries a leading ``(C,)`` axis and trains
them in lockstep:

  * margins for ALL classes come from a single fused kernel contraction —
    ONE ``rbf_matrix`` call against the flattened ``(C * slots, dim)`` SV
    bank, reshaped to ``(C, batch, slots)`` — not C sequential kernel calls
    (``class_kernel_rows``);
  * the Pegasos update + budget maintenance is ``jax.vmap`` of
    ``bsgd.train_step_from_rows`` over the class axis — the step is
    vmap-clean, and with ``unroll_maintenance=True`` it is *bitwise*
    loop-parity (property test in ``tests/core/test_multiclass.py``);
  * ONE ``MergeLookupTable`` is shared by every class (closed over the vmap,
    never stacked — 640 KB total regardless of C).

Prediction is argmax over the C per-class decision functions, again from one
fused kernel call.  The loop-over-classes baseline (`fit_multiclass_loop`)
is kept as the benchmark reference point (`bench_table2_accuracy
--multiclass` reports batched vs loop wall-clock).

Sharding: ``core.distributed`` maps this layout onto the production mesh
with ``layout="class"`` — classes over the ``model`` axis, the minibatch
over the data axes (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from . import budget as budget_mod
from .bsgd import (BSGDConfig, SVMState, _device_stage, _fit_stream,
                   _make_guard, _make_publish, _stream_epoch, init_state,
                   insert_from_rows, train_step_from_rows)
from ..kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class MulticlassSVMConfig:
    """C one-vs-rest copies of a binary ``BSGDConfig``.

    Attributes:
      n_classes: number of one-vs-rest problems (stacked along the leading
        state axis; labels are integer ids in [0, n_classes)).
      binary: the per-class ``BSGDConfig`` — every binary knob (budget,
        solver, kernel cache, maintenance strategy, dtypes) applies to each
        class unchanged; ONE lookup table is shared by all classes.
    """

    n_classes: int
    binary: BSGDConfig

    def __post_init__(self):
        if self.n_classes < 2:
            raise ValueError(f"n_classes={self.n_classes} < 2")

    @property
    def slots(self) -> int:
        return self.binary.slots

    def table(self):
        return self.binary.table()

    @staticmethod
    def create(n_classes: int, **kw) -> "MulticlassSVMConfig":
        """Build from binary hyperparameters: ``create(5, budget=100, ...)``."""
        return MulticlassSVMConfig(n_classes=n_classes, binary=BSGDConfig(**kw))


def ovr_targets(y, n_classes: int, dtype=jnp.float32):
    """Integer class labels (n,) -> one-vs-rest targets (C, n) in {-1, +1}.

    Labels must be 0-based: an out-of-range id would silently train as "not
    any class" (all-(-1) targets) and could never be predicted.  The fit
    drivers validate concrete labels up front (``check_labels``).
    """
    y = y.astype(jnp.int32)
    onehot = jnp.arange(n_classes, dtype=jnp.int32)[:, None] == y[None, :]
    return jnp.where(onehot, 1.0, -1.0).astype(dtype)


def check_labels(y, n_classes: int) -> None:
    """Raise on concrete labels outside [0, n_classes); no-op on tracers."""
    try:
        y_min, y_max = int(jnp.min(y)), int(jnp.max(y))
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        return
    if y_min < 0 or y_max >= n_classes:
        raise ValueError(
            f"class labels must be integers in [0, {n_classes}); got range "
            f"[{y_min}, {y_max}] — remap 1-based labels (e.g. y - 1) first")


def init_multiclass_state(cfg: MulticlassSVMConfig, dim: int) -> SVMState:
    """Stacked ``SVMState``: every leaf gains a leading ``(C,)`` axis."""
    st = init_state(cfg.binary, dim)
    return jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (cfg.n_classes,) + a.shape), st)


def class_kernel_rows(sv_x, x, gamma, *, impl: str = "auto"):
    """``k(x, sv_c)`` for every class from ONE kernel call.

    sv_x: (C, slots, dim) stacked SV bank; x: (n, dim).
    Returns (C, n, slots) — the batched all-class kernel contraction: the
    class axis is flattened into the SV axis so the whole thing is a single
    ``(n, C * slots)`` rbf block (one Pallas launch / one XLA matmul), then
    reshaped back.
    """
    c, slots, dim = sv_x.shape
    k = kops.rbf_matrix(x, sv_x.reshape(c * slots, dim), gamma, impl=impl)
    return jnp.moveaxis(k.reshape(x.shape[0], c, slots), 1, 0)


def decision_function_multiclass(state: SVMState, x, gamma, *,
                                 impl: str = "auto"):
    """Per-class scores f_c(x); x: (n, d) -> (C, n).

    Same fused fold as the serving cell (``kernels.ops.class_scores``): one
    kernel launch against the flattened (C * slots, dim) bank.
    """
    active = jnp.arange(state.alpha.shape[-1])[None, :] < state.count[:, None]
    alpha = jnp.where(active, state.alpha, 0.0)                   # (C, slots)
    return kops.class_scores(x, state.sv_x, alpha, gamma, impl=impl)


def predict_multiclass(state: SVMState, x, gamma, **kw):
    """argmax over the C one-vs-rest decision functions; returns (n,) int32."""
    scores = decision_function_multiclass(state, x, gamma, **kw)
    return jnp.argmax(scores, axis=0).astype(jnp.int32)


def accuracy_multiclass(state: SVMState, x, y, gamma, **kw) -> jax.Array:
    pred = predict_multiclass(state, x, gamma, **kw)
    return jnp.mean((pred == y.astype(jnp.int32)).astype(jnp.float32))


@partial(jax.jit, static_argnames=("cfg", "impl"))
def train_step_multiclass(cfg: MulticlassSVMConfig, table, state: SVMState,
                          xb, yb, *, impl: str = "auto") -> SVMState:
    """One lockstep solver step for all C one-vs-rest problems.

    xb: (batch, dim); yb: (batch,) integer class ids in [0, C).
    ``cfg.binary.solver`` picks the per-class update (Pegasos primal SGD or
    BDCA dual ascent — ``core.bdca``); both plug into the identical class
    vmap / fused-maintenance structure below.
    One fused rbf call produces every class's margin rows; the per-class
    update (insert + budget maintenance) is vmapped over the class axis with
    the lookup table and minibatch closed over (shared, not stacked).

    With ``maintenance_engine="pallas"`` only the shrink + insert half is
    vmapped; maintenance then runs ONCE on the whole stacked state through
    the fused merge-event engine (``budget.run_maintenance_classes``) —
    classes fold onto the kernel grid and the sorted-excess schedule bounds
    the rounds by the worst class's excess instead of C x worst
    (DESIGN.md §11).

    With ``step_engine="pallas"`` the WHOLE step — margin rows, shrink +
    insert, event rounds — is one ``kernels.ops.train_step`` launch chain:
    classes fold onto the kernel grid and the cache stays VMEM-resident
    across all three phases (DESIGN.md §12).
    """
    y_ovr = ovr_targets(yb, cfg.n_classes, dtype=jnp.dtype(cfg.binary.dtype))
    return train_step_ovr(cfg.binary, table, state, xb, y_ovr, impl=impl)


def train_step_ovr(b: BSGDConfig, table, state: SVMState, xb, y_ovr, *,
                   impl: str = "auto") -> SVMState:
    """``train_step_multiclass`` from one-vs-rest targets ``y_ovr`` of shape
    (C, batch) in {-1, +1}; ``b`` is the per-class config.  The distributed
    class layout calls it on each device's slice of the classes."""
    if b.step_engine == "pallas":
        k_bb = kops.rbf_matrix(xb, xb, b.gamma, impl=impl)
        sv, al, km, cnt, st_, nin, nmg = kops.train_step(
            state.sv_x, state.alpha, state.kmat, state.count, state.step,
            state.n_inserts, state.n_merges, xb, y_ovr, k_bb, table,
            budget=b.budget, lambda_=b.lambda_, gamma=b.gamma,
            batch_size=b.batch_size, maintenance=b.maintenance,
            merge_batch=b.merge_batch,
            unroll=b.batch_size if b.unroll_maintenance else 0, impl=impl)
        return SVMState(sv_x=sv, alpha=al, count=cnt, step=st_,
                        n_inserts=nin, n_merges=nmg, kmat=km)
    k_b = class_kernel_rows(state.sv_x, xb, b.gamma, impl=impl)   # (C, batch, slots)
    k_bb = (kops.rbf_matrix(xb, xb, b.gamma, impl=impl)
            if b.use_kernel_cache else None)

    # the §14 solver contract: a solver is an (insert+update, full-step) pair
    # with bsgd's row-consuming signatures; everything downstream — the class
    # vmap, the fused maintenance engine, streaming, serving — is shared
    if b.solver == "bdca":
        from . import bdca
        insert_fn, row_step_fn = bdca.insert_from_rows, bdca.train_step_from_rows
    else:
        insert_fn, row_step_fn = insert_from_rows, train_step_from_rows

    if b.maintenance_engine == "pallas":
        def one_insert(st, yc, kc):
            return insert_fn(b, st, xb, yc, kc, k_bb)

        mid = jax.vmap(one_insert)(state, y_ovr, k_b)
        sv_x, alpha, kmat, count, n_merges = \
            budget_mod.run_maintenance_classes(
                mid.sv_x, mid.alpha, mid.kmat, mid.count, mid.n_merges,
                table, budget=b.budget, impl=impl,
                unroll=b.batch_size if b.unroll_maintenance else 0)
        return mid._replace(sv_x=sv_x, alpha=alpha, count=count,
                            n_merges=n_merges, kmat=kmat)

    def one_class(st, yc, kc):
        return row_step_fn(b, table, st, xb, yc, kc, k_bb, impl=impl)

    return jax.vmap(one_class)(state, y_ovr, k_b)


@partial(jax.jit, static_argnames=("cfg", "impl"))
def train_epoch_multiclass(cfg: MulticlassSVMConfig, table, state: SVMState,
                           x, y, perm, *, impl: str = "auto") -> SVMState:
    """One pass over resident (x, integer y) as a single jitted lax.scan —
    the class-axis counterpart of ``train_epoch`` (same perm/truncation
    contract; streamed form: ``train_epoch_multiclass_stream``)."""
    bs = cfg.binary.batch_size
    steps = perm.shape[0] // bs
    order = perm[: steps * bs].reshape(steps, bs)

    def scan_body(st, batch_idx):
        xb = jnp.take(x, batch_idx, axis=0)
        yb = jnp.take(y, batch_idx, axis=0)
        return train_step_multiclass(cfg, table, st, xb, yb, impl=impl), ()

    state, _ = jax.lax.scan(scan_body, state, order)
    return state


def fit_multiclass(cfg: MulticlassSVMConfig, x, y, *, epochs: int = 1,
                   seed: int = 0, impl: str = "auto",
                   state: SVMState | None = None) -> SVMState:
    """Train C one-vs-rest problems in lockstep on in-memory data.

    Mirrors ``bsgd.fit``: shuffled epochs (permutation per epoch from
    ``seed``) over ``x: (n, dim)`` with integer labels ``y: (n,)`` in
    [0, n_classes) — validated up front when concrete.  ``state`` resumes an
    existing stacked model.  Out-of-core counterpart:
    ``fit_multiclass_stream``.
    """
    check_labels(y, cfg.n_classes)
    table = cfg.table()
    if state is None:
        state = init_multiclass_state(cfg, x.shape[1])
    key = jax.random.PRNGKey(seed)
    for _ in range(epochs):
        key, sub = jax.random.split(key)
        perm = jax.random.permutation(sub, x.shape[0])
        state = train_epoch_multiclass(cfg, table, state, x, y, perm,
                                       impl=impl)
    return state


@partial(jax.jit, static_argnames=("cfg", "impl"), donate_argnums=(2,))
def train_chunk_multiclass(cfg: MulticlassSVMConfig, table, state: SVMState,
                           xc, yc, *, impl: str = "auto") -> SVMState:
    """One resident chunk of the one-vs-rest engine as a single donated-state
    program; ``xc: (steps, batch, dim)``, ``yc: (steps, batch)`` class ids
    (cf. ``bsgd.train_chunk``)."""
    def body(st, xy):
        xb, yb = xy
        return train_step_multiclass(cfg, table, st, xb,
                                     yb.astype(jnp.int32), impl=impl), ()

    state, _ = jax.lax.scan(body, state, (xc, yc))
    return state


def train_epoch_multiclass_stream(cfg: MulticlassSVMConfig, table,
                                  state: SVMState, source, *, key=None,
                                  impl: str = "auto", start_chunk: int = 0,
                                  carry=None, on_chunk=None,
                                  max_chunks: int | None = None,
                                  chunk_fn=None, prefetch: int = 0,
                                  retry=None, report=None, skip_chunks=()):
    """One streamed pass of the one-vs-rest engine over a chunk source.

    The multi-class counterpart of ``bsgd.train_epoch_stream`` — identical
    chunk-carry contract (deterministic shuffle, donated per-chunk program —
    the caller's input state buffers are consumed —, remainder carry,
    ``prefetch`` background staging, ``(state, next_chunk, carry)`` return);
    labels are integer class ids in [0, C).
    """
    stage = _device_stage if chunk_fn is None else None
    if chunk_fn is None:
        def chunk_fn(st, xc, yc):
            return train_chunk_multiclass(cfg, table, st, xc, yc, impl=impl)
    state, next_chunk, carry, _ = _stream_epoch(
        chunk_fn, state, source, batch_size=cfg.binary.batch_size, key=key,
        start_chunk=start_chunk, carry=carry, on_chunk=on_chunk,
        max_chunks=max_chunks, prefetch=prefetch, stage=stage, retry=retry,
        report=report, skip_chunks=skip_chunks)
    if next_chunk == source.n_chunks:
        jax.block_until_ready(state.alpha)
    return state, next_chunk, carry


def fit_multiclass_stream(cfg: MulticlassSVMConfig, source, *,
                          epochs: int = 1, seed: int = 0, impl: str = "auto",
                          state: SVMState | None = None,
                          ckpt_dir: str | None = None, ckpt_every: int = 0,
                          max_chunks: int | None = None, keep_last: int = 3,
                          chunk_fn=None, prefetch: int = 0, bank=None,
                          publish_every: int = 0,
                          publish_dtype=None, retry=None,
                          guard_finite: bool = False,
                          debug_invariants: bool = False, report=None,
                          skip_chunks=()) -> SVMState:
    """Out-of-core ``fit_multiclass``: streamed shuffled epochs over a chunk
    source of integer-labelled rows (contract as in ``bsgd.fit_stream`` —
    same checkpointing, cursor, bitwise-resume, copied-caller-state,
    ``prefetch`` background staging, ``bank``/``publish_every`` snapshot
    semantics, and ``retry``/``guard_finite``/``debug_invariants``/
    ``report``/``skip_chunks`` resilience knobs).  Labels are validated per
    concrete chunk."""
    table = cfg.table()
    if state is None:
        state = init_multiclass_state(cfg, source.dim)
    else:
        state = jax.tree.map(jnp.array, state)   # donation would delete it
    stage = _device_stage if chunk_fn is None else None
    if chunk_fn is None:
        def chunk_fn(st, xc, yc):
            check_labels(yc, cfg.n_classes)
            return train_chunk_multiclass(cfg, table, st, xc, yc, impl=impl)
    return _fit_stream(cfg.binary.batch_size, source, chunk_fn, state,
                       epochs=epochs, seed=seed, ckpt_dir=ckpt_dir,
                       ckpt_every=ckpt_every, max_chunks=max_chunks,
                       keep_last=keep_last, prefetch=prefetch, stage=stage,
                       publish=_make_publish(bank, cfg.binary.gamma,
                                             publish_dtype),
                       publish_every=publish_every, retry=retry,
                       report=report, skip_chunks=skip_chunks,
                       guard=_make_guard(guard_finite, debug_invariants,
                                         cfg.binary, report))


def fit_multiclass_loop(cfg: MulticlassSVMConfig, x, y, *, epochs: int = 1,
                        seed: int = 0, impl: str = "auto") -> SVMState:
    """Loop-over-classes baseline: C sequential binary fits on OVR labels.

    Identical epoch permutations (same seed) mean this trains the same model
    as ``fit_multiclass`` — it just pays C sequential kernel calls per step
    plus C scans per epoch.  Kept as the reference point the batched engine
    is benchmarked against (``bench_table2_accuracy --multiclass``).
    """
    from .bsgd import fit

    check_labels(y, cfg.n_classes)
    y_ovr = ovr_targets(y, cfg.n_classes, dtype=jnp.dtype(cfg.binary.dtype))
    states = [fit(cfg.binary, x, y_ovr[c], epochs=epochs, seed=seed, impl=impl)
              for c in range(cfg.n_classes)]
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *states)
