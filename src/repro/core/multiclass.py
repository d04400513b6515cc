"""The training engine: one-vs-rest BSGD with the class axis as a leading
state dimension.

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

It is the only implementation of the train step, scan, chunk and epoch
programs and stream driver.  A binary problem is its C = 1 case: ``bsgd``'s
entry points lift the state to a leading axis of one, pass the ±1 labels as
the one class's targets, and drop the axis.

Prediction is argmax over the C per-class decision functions, again from one
fused kernel call.  The loop-over-classes baseline (`fit_multiclass_loop`,
C sequential binary fits) is kept as the parity reference of the batched
engine.

Sharding: ``core.distributed`` maps this layout onto the production mesh
with ``layout="class"`` — classes over the ``model`` axis, the minibatch
over the data axes (DESIGN.md §8).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import budget as budget_mod
from . import kernel_cache
from .. import obs
from .bsgd import (BSGDConfig, SVMState, carries_padded, init_state,
                   insert_from_rows, stream_kernel, train_step_from_rows)
from .predict import export_model
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
    return train_step_ovr(cfg.binary, table, state, xb, _targets(cfg, yb),
                          impl=impl)


def _targets(cfg: MulticlassSVMConfig, yb):
    """Class ids (batch,) -> the step's one-vs-rest targets (C, batch)."""
    return ovr_targets(yb, cfg.n_classes, dtype=jnp.dtype(cfg.binary.dtype))


def _class_map(fn, *args):
    """``jax.vmap(fn)`` over the leading class axis of ``args``; at C = 1,
    ``fn`` on the one class, the axis dropped and put back.  The two are the
    same map, but XLA may round the batched ops differently, and a binary
    model (``bsgd``'s entry points) trains bitwise as the unbatched step."""
    if jax.tree.leaves(args)[0].shape[0] > 1:
        return jax.vmap(fn)(*args)
    out = fn(*jax.tree.map(lambda a: a[0], args))
    return jax.tree.map(lambda a: a[None], out)


def train_step_ovr(b: BSGDConfig, table, state: SVMState, xb, y_ovr, *,
                   impl: str = "auto") -> SVMState:
    """``train_step_multiclass`` from one-vs-rest targets ``y_ovr`` of shape
    (C, batch) in {-1, +1}; ``b`` is the per-class config.  The distributed
    class layout calls it on each device's slice of the classes, and
    ``bsgd.train_step`` at C = 1."""
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

        mid = _class_map(one_insert, state, y_ovr, k_b)
        sv_x, alpha, kmat, count, n_merges = \
            budget_mod.run_maintenance_classes(
                mid.sv_x, mid.alpha, mid.kmat, mid.count, mid.n_merges,
                table, budget=b.budget, impl=impl,
                unroll=b.batch_size if b.unroll_maintenance else 0)
        return mid._replace(sv_x=sv_x, alpha=alpha, count=count,
                            n_merges=n_merges, kmat=kmat)

    def one_class(st, yc, kc):
        return row_step_fn(b, table, st, xb, yc, kc, k_bb, impl=impl)

    return _class_map(one_class, state, y_ovr, k_b)


def scan_ovr(b: BSGDConfig, table, state: SVMState, xs, batch, *,
             impl: str) -> SVMState:
    """``lax.scan`` of ``train_step_ovr`` over ``xs``: the body of every
    chunk and epoch program.  ``state`` is stacked (every leaf has a leading
    (C,) axis); ``batch(x)`` maps one scanned element of ``xs`` to the
    minibatch ``xb`` (batch, d) and its one-vs-rest targets (C, batch).

    Where the scan runs the fused Pallas kernel (``carries_padded``) it
    carries the lane-padded state: padded once before the loop (named scope
    ``train_chunk.pad``) and sliced once after it (``train_chunk.unpad``);
    in between the kernel updates the carried blocks in place, so no step
    copies the state, and the real state is bitwise that of a scan of
    ``kops.train_step``.
    """
    if not carries_padded(b, impl):
        def body(st, x):
            xb, y_ovr = batch(x)
            return train_step_ovr(b, table, st, xb, y_ovr, impl=impl), ()

        state, _ = jax.lax.scan(body, state, xs)
        return state
    _, s, d = state.sv_x.shape

    def fused(carry, x):
        xb, y_ovr = batch(x)
        k_bb = kops.rbf_matrix(xb, xb, b.gamma, impl=impl)
        return kops.train_step_padded(
            *carry, xb, y_ovr, k_bb, table, budget=b.budget,
            lambda_=b.lambda_, gamma=b.gamma,
            batch_size=b.batch_size, maintenance=b.maintenance,
            merge_batch=b.merge_batch, impl=impl), ()

    with jax.named_scope("train_chunk.pad"):
        padded = kops.pad_fused_state(state.sv_x, state.alpha, state.kmat)
    carry = (*padded, state.count, state.step, state.n_inserts,
             state.n_merges)
    (sv, al, km, cnt, step, nin, nmg), _ = jax.lax.scan(fused, carry, xs)
    with jax.named_scope("train_chunk.unpad"):
        sv, al, km = kops.unpad_fused_state(sv, al, km, s, d)
    return SVMState(sv_x=sv, alpha=al, count=cnt, step=step, n_inserts=nin,
                    n_merges=nmg, kmat=km)


def scan_epoch_ovr(b: BSGDConfig, table, state: SVMState, x, y, perm,
                   targets, *, impl: str) -> SVMState:
    """``scan_ovr`` over one pass of resident rows in ``perm``'s order (rows
    past the last full ``batch_size`` multiple are dropped); ``targets(yb)``
    maps a minibatch's labels to its (C, batch) one-vs-rest targets."""
    steps = perm.shape[0] // b.batch_size
    order = perm[: steps * b.batch_size].reshape(steps, b.batch_size)
    return scan_ovr(b, table, state, order,
                    lambda idx: (jnp.take(x, idx, axis=0),
                                 targets(jnp.take(y, idx, axis=0))),
                    impl=impl)


@partial(jax.jit, static_argnames=("cfg", "impl"))
def train_epoch_multiclass(cfg: MulticlassSVMConfig, table, state: SVMState,
                           x, y, perm, *, impl: str = "auto") -> SVMState:
    """One pass over resident (x, integer y) as a single jitted lax.scan —
    the class-axis counterpart of ``train_epoch`` (same perm/truncation
    contract; streamed form: ``train_epoch_multiclass_stream``)."""
    return scan_epoch_ovr(cfg.binary, table, state, x, y, perm,
                          partial(_targets, cfg), impl=impl)


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


@partial(jax.jit, static_argnames=("b", "impl"), donate_argnums=(2,))
def train_chunk_ovr(b: BSGDConfig, table, state: SVMState, xc, tc, *,
                    impl: str = "auto") -> SVMState:
    """One resident chunk as a single donated-state program, from
    one-vs-rest targets: ``xc: (steps, batch, dim)``, ``tc: (steps, C,
    batch)`` in {-1, +1}, ``b`` the per-class config.  ``bsgd.train_chunk``
    is its C = 1 case; ``online.prequential_stream`` runs it for both."""
    with jax.named_scope("train_chunk"):
        return scan_ovr(b, table, state, (xc, tc), lambda xt: xt, impl=impl)


@partial(jax.jit, static_argnames=("cfg", "impl"), donate_argnums=(2,))
def train_chunk_multiclass(cfg: MulticlassSVMConfig, table, state: SVMState,
                           xc, yc, *, impl: str = "auto") -> SVMState:
    """One resident chunk of the one-vs-rest engine as a single donated-state
    program; ``xc: (steps, batch, dim)``, ``yc: (steps, batch)`` class ids
    (cf. ``bsgd.train_chunk``).  The fused Pallas step carries its
    lane-padded state through the scan (``scan_ovr``)."""
    with jax.named_scope("train_chunk_multiclass"):
        return scan_ovr(
            cfg.binary, table, state, (xc, yc),
            lambda xy: (xy[0], _targets(cfg, xy[1].astype(jnp.int32))),
            impl=impl)


# ---------------------------------------------------------------------------
# Streaming epochs: chunked host pipeline -> one donated-state program/chunk
# ---------------------------------------------------------------------------

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
                with obs.span("stream.stage"):
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
        try:
            q.put_nowait(item)
            return True
        except queue_mod.Full:
            pass
        with obs.span("stream.backpressure"):
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue_mod.Full:
                    continue
        return False

    ctx = obs.context()

    def work():
        with obs.attach(ctx):
            try:
                for item in gen:
                    if not _put((None, item)):
                        return
                _put((_DONE, None))
            except BaseException as e:  # noqa: BLE001 — re-raised on
                _put((_FAIL, e))            # the consumer's thread

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


def stream_epoch_ovr(program, state: SVMState, source, *, batch_size: int,
                     key, chunk_fn=None, start_chunk: int = 0, carry=None,
                     on_chunk=None, max_chunks: int | None = None,
                     prefetch: int = 0, retry=None, report=None,
                     skip_chunks=(), guard_finite: bool = False, check=None,
                     epoch: int = 0):
    """The one-epoch streaming driver behind ``bsgd.train_epoch_stream``,
    ``train_epoch_multiclass_stream`` and ``fit_stream_ovr`` (contract, with
    ``prefetch`` and the §16 resilience hooks, in ``bsgd.train_epoch_stream``
    and ``bsgd.fit_stream``).

    ``program(state, xc, yc) -> state`` runs one jitted chunk program;
    ``chunk_fn`` replaces it (the distributed path's pjit'd program, whose
    blocks stay on the host for pjit to place).  Chunks are staged in the
    source's own dtypes; carry rows are checkpointed as float32.
    ``on_chunk(state, pos, carry)`` fires after each chunk program — the
    checkpoint hook.  The prefetch worker runs the same ``_assemble_chunks``
    generator as the sync path, so the batch sequence is bitwise the same.
    ``guard_finite`` rolls a chunk whose state has a non-finite float leaf
    back (tallied in ``report``) BEFORE ``on_chunk``; ``check(state)``
    validates every accepted state.  Each chunk is one ``fit.chunk`` span
    (attributes ``epoch``, ``pos``, ``rows``) over its ``stream.wait``, its
    ``chunk.launch``, the guard's ``guard.finite`` and ``on_chunk``.

    Returns ``(state, next_chunk, carry, chunks_run)``; ``next_chunk <
    source.n_chunks`` means ``max_chunks`` cut the epoch short; otherwise the
    state is ready (the epoch's one sync).
    """
    # the default single-device programs start each block's host->device
    # copy on the stager, so the copy (not just the parse) overlaps the scan
    stage = None if chunk_fn else lambda *b: tuple(map(jax.device_put, b))
    chunk_fn = chunk_fn or program
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
        while True:
            with obs.span("fit.chunk") as chunk:
                with obs.span("stream.wait") as wait:
                    item = next(items, None)
                    if item is None:          # the epoch's end, no chunk
                        wait.discard()
                        chunk.discard()
                if item is None:
                    break
                pos, xc, yc, out_carry = item
                chunk.set(epoch=epoch, pos=pos,
                          rows=0 if xc is None else xc.shape[0] * xc.shape[1])
                if xc is not None:
                    if guard_finite:
                        # the chunk program donates its input state, so the
                        # last-good snapshot must be copied out BEFORE the
                        # launch
                        snap = jax.tree.map(jnp.copy, state)
                        with obs.span("chunk.launch"):
                            new_state = chunk_fn(state, xc, yc)
                        with obs.span("guard.finite"):
                            finite = bool(_tree_all_finite(new_state))
                        if finite:
                            state = new_state
                        else:
                            state = snap   # roll back + skip the poisoned
                            if report is not None:  # chunk wholesale
                                report.note_rollback(pos)
                    else:
                        with obs.span("chunk.launch"):
                            state = chunk_fn(state, xc, yc)
                    if check is not None:
                        check(state)
                if on_chunk is not None:
                    on_chunk(state, pos, out_carry)
    finally:
        if prefetch:
            items.close()                 # stop the stager on any exit
    if out_carry is None:
        out_carry = (np.zeros((0, source.dim), np.float32),
                     np.zeros((0,), np.float32))
    if end == source.n_chunks:
        jax.block_until_ready(state.alpha)
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


def fit_stream_ovr(b: BSGDConfig, program, init, source, *,
                   state: SVMState | None, impl: str, epochs: int, seed: int,
                   ckpt_dir, ckpt_every: int, max_chunks, keep_last: int,
                   chunk_fn=None, prefetch: int = 0, bank=None,
                   publish_every: int = 0, publish_dtype=None, retry=None,
                   guard_finite: bool = False,
                   debug_invariants: bool = False, report=None,
                   skip_chunks=()) -> SVMState:
    """The multi-epoch streaming driver behind ``fit_multiclass_stream`` and
    ``bsgd.fit_stream`` (contract there); ``b`` is the per-class config,
    ``program(state, xc, yc)`` the default chunk program and ``init()`` the
    fresh state when ``state`` is None.  Resume walks back past torn/corrupt
    checkpoint steps to the newest verifiable one
    (``checkpoint.latest_verifiable_step``); ``retry``/``report``/
    ``skip_chunks``, ``guard_finite`` and ``debug_invariants`` are the §16
    resilience hooks threaded into every epoch.  The kernel that trains
    (``stream_kernel``) rides on the ``fit.stream`` span as its index in
    ``kops.FUSED_KERNELS``."""
    if state is None:
        state = init()
    else:
        state = jax.tree.map(jnp.array, state)   # donation would delete it

    def publish(st):
        # the chunk programs DONATE the state, so the next chunk invalidates
        # the buffers a naive export would alias: copy the state out first
        if bank is not None:
            with obs.span("publish"):
                bank.publish(export_model(jax.tree.map(jnp.copy, st),
                                          b.gamma, bank_dtype=publish_dtype))

    check = None
    if debug_invariants and b.use_kernel_cache:
        def check(st):
            kernel_cache.check_invariants(st.kmat, st.sv_x, st.count, b.gamma)
    batch_size = b.batch_size
    kernel = stream_kernel(b, impl, state)
    with obs.span("fit.stream", kernel=kops.FUSED_KERNELS.index(kernel)):
        from .. import checkpoint as ckpt

        dim = source.dim
        n_chunks = source.n_chunks
        start_epoch, start_chunk = 0, 0
        carry, resume_key = None, None
        if ckpt_dir:
            latest = ckpt.latest_step(ckpt_dir)
            if latest is not None:
                # a torn/bit-flipped newest step (crash mid-save outside the
                # atomic-rename path, disk corruption) must not kill the
                # restart: fall back to the newest step whose checksums verify
                verified = ckpt.latest_verifiable_step(ckpt_dir)
                if verified is None:
                    raise ValueError(
                        f"{ckpt_dir}: checkpoint steps "
                        f"{ckpt.all_steps(ckpt_dir)} exist but none verify "
                        "(manifest/arrays corrupt) — "
                        "refusing to silently restart from scratch")
                latest = verified
            if latest is not None:
                meta = ckpt.load_metadata(ckpt_dir, latest)
                if meta.get("kind") != "stream-epoch":
                    raise ValueError(f"{ckpt_dir}: step {latest} is not a "
                                     "streaming checkpoint")
                # the cursor is only meaningful against the same shuffle and
                # the same chunking — a silent mismatch would train some rows
                # twice and others never, so refuse instead
                if meta["seed"] != seed:
                    raise ValueError(
                        f"{ckpt_dir}: checkpoint was written with seed="
                        f"{meta['seed']}, resume called with seed={seed}")
                if meta["n_chunks"] != n_chunks:
                    raise ValueError(
                        f"{ckpt_dir}: checkpoint cursor is against "
                        f"{meta['n_chunks']} chunks, source now has "
                        f"{n_chunks} — re-chunked data cannot resume "
                        "mid-epoch")
                tree = ckpt.load(ckpt_dir, latest,
                                 _ckpt_template(state, batch_size, dim))
                state = tree["state"]
                start_epoch, start_chunk = meta["epoch"], meta["next_chunk"]
                resume_key = tree["epoch_key"]    # the interrupted epoch's key
                cn = int(tree["carry_n"])
                carry = (np.asarray(tree["carry_x"])[:cn],
                         np.asarray(tree["carry_y"])[:cn])
                if start_chunk >= n_chunks:   # checkpoint at an epoch end
                    start_epoch, start_chunk, carry = start_epoch + 1, 0, None
                    resume_key = None

        budget_left = max_chunks
        base_key = jax.random.PRNGKey(seed)
        for epoch in range(start_epoch, epochs):
            # the resumed epoch continues under its checkpointed RNG key
            # (equal, by the seed guard above, to the rederived one); later
            # epochs fold
            epoch_key = (resume_key if epoch == start_epoch and
                         resume_key is not None
                         else jax.random.fold_in(base_key, epoch))

            def save(st, pos, cr, *, _epoch=epoch, _key=epoch_key):
                done = pos + 1
                if publish_every and done % publish_every == 0:
                    publish(st)
                if not (ckpt_dir and ckpt_every
                        and done % ckpt_every == 0):
                    return
                px, py, cn = _pad_carry(cr, batch_size, dim)
                ckpt.save(ckpt_dir, _epoch * n_chunks + done,
                          {"state": st, "epoch_key": _key, "carry_x": px,
                           "carry_y": py, "carry_n": cn},
                          keep_last=keep_last,
                          metadata={"kind": "stream-epoch",
                                    "epoch": _epoch, "next_chunk": done,
                                    "n_chunks": n_chunks, "seed": seed})

            state, next_chunk, carry, ran = stream_epoch_ovr(
                program, state, source, batch_size=batch_size,
                key=epoch_key, chunk_fn=chunk_fn, start_chunk=start_chunk,
                carry=carry, on_chunk=save, max_chunks=budget_left,
                prefetch=prefetch, retry=retry, report=report,
                skip_chunks=skip_chunks, guard_finite=guard_finite,
                check=check, epoch=epoch)
            if budget_left is not None:
                budget_left -= ran
            if next_chunk < n_chunks:         # cut short by max_chunks
                publish(state)
                return state
            start_chunk, carry = 0, None  # sub-batch remainder dropped
        publish(state)                        # the final model always lands
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
    return stream_epoch_ovr(
        partial(train_chunk_multiclass, cfg, table, impl=impl), state,
        source, batch_size=cfg.binary.batch_size, key=key, chunk_fn=chunk_fn,
        start_chunk=start_chunk, carry=carry, on_chunk=on_chunk,
        max_chunks=max_chunks, prefetch=prefetch, retry=retry, report=report,
        skip_chunks=skip_chunks)[:3]


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

    def program(st, xc, yc):
        check_labels(yc, cfg.n_classes)
        return train_chunk_multiclass(cfg, table, st, xc, yc, impl=impl)

    return fit_stream_ovr(
        cfg.binary, program, lambda: init_multiclass_state(cfg, source.dim),
        source, state=state, impl=impl, epochs=epochs, seed=seed,
        ckpt_dir=ckpt_dir, ckpt_every=ckpt_every, max_chunks=max_chunks,
        keep_last=keep_last, chunk_fn=chunk_fn, prefetch=prefetch, bank=bank,
        publish_every=publish_every, publish_dtype=publish_dtype, retry=retry,
        guard_finite=guard_finite, debug_invariants=debug_invariants,
        report=report, skip_chunks=skip_chunks)


def fit_multiclass_loop(cfg: MulticlassSVMConfig, x, y, *, epochs: int = 1,
                        seed: int = 0, impl: str = "auto") -> SVMState:
    """Loop-over-classes baseline: C sequential binary fits on OVR labels.

    Identical epoch permutations (same seed) mean this trains the same model
    as ``fit_multiclass`` — it just pays C sequential kernel calls per step
    plus C scans per epoch.  Kept as the parity reference of the batched
    engine.
    """
    from .bsgd import fit

    check_labels(y, cfg.n_classes)
    y_ovr = ovr_targets(y, cfg.n_classes, dtype=jnp.dtype(cfg.binary.dtype))
    states = [fit(cfg.binary, x, y_ovr[c], epochs=epochs, seed=seed, impl=impl)
              for c in range(cfg.n_classes)]
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *states)
