"""Distributed minibatch BSGD: the paper's solver as a pjit'd program.

Parallel structure (DESIGN.md §3.5):
  * the minibatch is sharded over the data axes (pod, data) — each shard
    computes margins for its examples against the full SV set;
  * the SV set is sharded over the *model* axis along the budget dimension:
    the (batch, slots) kernel matrix contraction over features happens per
    shard, and the margin sum over SVs psums across model;
  * maintenance decisions (argmin over |alpha|, candidate scoring against
    the lookup table, the merge scatter) operate on the replicated-alpha
    view — cheap *because* the lookup made them cheap; with runtime GSS the
    sequential solver chain would serialize every replica (the paper's cost,
    amplified by scale).

Multi-class (``layout="class"``, DESIGN.md §8): the stacked one-vs-rest
state's leading ``(C,)`` axis shards over ``model`` — every device owns
whole classes, so per-class maintenance needs NO collective at all; the
minibatch shards over the data axes and all-gathers once into the fused
(batch, C * slots) kernel contraction.

``make_distributed_step`` returns (step_fn, in_shardings, out_shardings,
abstract args) — consumed by both the real trainer and the dry-run, so the
SVM cell is exercised on the production mesh exactly like the LM cells.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .bsgd import BSGDConfig, SVMState, train_step
from .lookup import MergeLookupTable
from .multiclass import (MulticlassSVMConfig, ovr_targets, train_step_ovr,
                         train_step_multiclass)
from .predict import ServeModel, predict_labels


def sv_shardings(cfg: BSGDConfig, mesh, dim: int, *, layout: str = "replicated"):
    """Shardings for SVMState + batch on the production mesh.

    layout="slots":       SV arrays sharded over `model` along the budget dim,
                          batch over (pod, data).  First/naive plan — GSPMD
                          reshards the SV state around the insert scatter and
                          maintenance argmin (all-gather heavy, see §Perf).
    layout="replicated":  SV state replicated (100 MB — trivially fits), batch
                          sharded over EVERY mesh axis (256/512-way).  The
                          kernel matrix needs no communication at all; the
                          only collective left is gathering the minibatch's
                          violator rows for the (replicated) insert.
    """
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    if layout == "replicated":
        batch_axes = dp + ("model",)
        slot_axis = None
    else:
        batch_axes = dp
        slot_axis = "model" if cfg.slots % mesh.shape["model"] == 0 else None
    return SVMState(
        sv_x=NamedSharding(mesh, P(slot_axis, None)),
        alpha=NamedSharding(mesh, P(slot_axis)),
        count=NamedSharding(mesh, P()),
        step=NamedSharding(mesh, P()),
        n_inserts=NamedSharding(mesh, P()),
        n_merges=NamedSharding(mesh, P()),
        # The kernel cache rides the SV layout: rows sharded with the slots
        # axis (each shard owns its SVs' kappa rows), columns replicated.
        kmat=(NamedSharding(mesh, P(slot_axis, None))
              if cfg.use_kernel_cache else None),
    ), NamedSharding(mesh, P(batch_axes, None)), NamedSharding(mesh, P(batch_axes))


def multiclass_shardings(cfg: MulticlassSVMConfig, mesh):
    """``layout="class"`` shardings: classes over ``model``, batch over the
    data axes.  Requires ``n_classes`` divisible by the model-axis size
    (falls back to replicated classes otherwise)."""
    dp = ("pod", "data") if "pod" in mesh.axis_names else ("data",)
    cls = "model" if cfg.n_classes % mesh.shape["model"] == 0 else None
    state_sh = SVMState(
        sv_x=NamedSharding(mesh, P(cls, None, None)),
        alpha=NamedSharding(mesh, P(cls, None)),
        count=NamedSharding(mesh, P(cls)),
        step=NamedSharding(mesh, P(cls)),
        n_inserts=NamedSharding(mesh, P(cls)),
        n_merges=NamedSharding(mesh, P(cls)),
        kmat=(NamedSharding(mesh, P(cls, None, None))
              if cfg.binary.use_kernel_cache else None),
    )
    return (state_sh, NamedSharding(mesh, P(dp, None)),
            NamedSharding(mesh, P(dp)))


def _per_device(step, mesh, state_spec):
    """``step(state, table, xb, yb)`` as one program per device.

    Each device holds its ``state_spec`` shard of the state and the whole
    table and minibatch.  GSPMD cannot partition a Mosaic kernel, so running
    the step per device under ``shard_map`` is what lets the Pallas kernels
    (``impl``) run on a multi-chip mesh.
    """
    return jax.shard_map(step, mesh=mesh,
                         in_specs=(state_spec, P(), P(), P()),
                         out_specs=state_spec, check_vma=False)


def _make_multiclass_step(cfg: MulticlassSVMConfig, mesh, dim: int,
                          table: MergeLookupTable | None, impl: str):
    b = cfg.binary
    state_sh, x_sh, y_sh = multiclass_shardings(cfg, mesh)
    repl = NamedSharding(mesh, P())
    table_sh = (MergeLookupTable(h_table=repl, wd_table=repl)
                if table is not None else None)
    c = cfg.n_classes
    c_local = c // mesh.shape["model"] if state_sh.count.spec[0] else c

    def local_step(state: SVMState, table, xb, yb):
        # this device's classes: [first, first + c_local) of the C targets
        y_ovr = ovr_targets(yb, c, dtype=jnp.dtype(b.dtype))
        first = jax.lax.axis_index("model") * c_local if c_local < c else 0
        y_ovr = jax.lax.dynamic_slice_in_dim(y_ovr, first, c_local)
        return train_step_ovr(b, table, state, xb, y_ovr, impl=impl)

    step = _per_device(local_step, mesh,
                       jax.tree.map(lambda sh: sh.spec, state_sh))
    args = (
        SVMState(
            sv_x=jax.ShapeDtypeStruct((c, b.slots, dim),
                                      jnp.dtype(b.sv_dtype or b.dtype)),
            alpha=jax.ShapeDtypeStruct((c, b.slots), jnp.dtype(b.dtype)),
            count=jax.ShapeDtypeStruct((c,), jnp.int32),
            step=jax.ShapeDtypeStruct((c,), jnp.int32),
            n_inserts=jax.ShapeDtypeStruct((c,), jnp.int32),
            n_merges=jax.ShapeDtypeStruct((c,), jnp.int32),
            kmat=(jax.ShapeDtypeStruct((c, b.slots, b.slots), jnp.float32)
                  if b.use_kernel_cache else None)),
        (jax.eval_shape(lambda: table) if table is not None else None),
        jax.ShapeDtypeStruct((b.batch_size, dim),
                             jnp.dtype(b.sv_dtype or b.dtype)),
        jax.ShapeDtypeStruct((b.batch_size,), jnp.int32),
    )
    in_sh = (state_sh, table_sh, x_sh, y_sh)
    return step, args, in_sh, state_sh


def serve_shardings(mesh, *, binary: bool = False):
    """``layout="serve"``: the exported bank replicated per device, the
    request batch sharded over EVERY mesh axis.

    The serving contract (DESIGN.md §10): each device scores its request
    shard against its own full copy of the (C, slots, dim) bank, the
    per-class contraction and the argmax stay local, and the output labels
    inherit the batch sharding — ZERO collectives in the whole cell.  The
    bank is small by construction (the budget exists so it is), so
    replication is the right trade at serving batch sizes.
    Returns ``(model_shardings, x_sharding, labels_sharding)``.
    """
    repl = NamedSharding(mesh, P())
    batch_axes = mesh.axis_names          # e.g. ("data", "model")
    model_sh = ServeModel(sv_x=repl, alpha=repl, count=repl, gamma=repl,
                          binary=binary)
    return (model_sh, NamedSharding(mesh, P(batch_axes, None)),
            NamedSharding(mesh, P(batch_axes)))


def make_distributed_predict(mesh, *, dim: int, batch: int, slots: int,
                             n_classes: int | None = None,
                             bank_dtype="bfloat16", impl: str = "auto"):
    """The fused serve cell on the production mesh.

    ``n_classes=None`` builds the binary cell (C = 1 bank, ±1 sign labels);
    otherwise the multiclass argmax cell.  Returns ``(predict_fn,
    args_abstract, in_shardings, out_sharding)`` with ``predict_fn(model, x)
    -> labels``; jit it with the shardings and hand it to ``BatchQueue`` as
    ``predict_fn`` (wrapped to close over the resident model) — the queue's
    bucket set then bounds the pjit cache exactly as on one device.  Each
    device scores its request shard (``shard_map``), so the kernels of
    ``impl`` run unpartitioned.
    """
    binary = n_classes is None
    c = 1 if binary else n_classes
    model_sh, x_sh, y_sh = serve_shardings(mesh, binary=binary)
    predict_fn = jax.shard_map(
        lambda model, x: predict_labels(model, x, impl=impl), mesh=mesh,
        in_specs=(P(), x_sh.spec), out_specs=y_sh.spec, check_vma=False)

    args = (
        ServeModel(
            sv_x=jax.ShapeDtypeStruct((c, slots, dim), jnp.dtype(bank_dtype)),
            alpha=jax.ShapeDtypeStruct((c, slots), jnp.float32),
            count=jax.ShapeDtypeStruct((c,), jnp.int32),
            gamma=jax.ShapeDtypeStruct((), jnp.float32),
            binary=binary),
        jax.ShapeDtypeStruct((batch, dim), jnp.float32),
    )
    return predict_fn, args, (model_sh, x_sh), y_sh


def make_distributed_step(cfg, mesh, dim: int,
                          table: MergeLookupTable | None = None,
                          layout: str = "replicated", impl: str = "auto"):
    """(step_fn, args_abstract, in_shardings, out_shardings).

    ``cfg`` is a ``BSGDConfig`` for the binary layouts (``replicated`` /
    ``slots``) or a ``MulticlassSVMConfig`` for ``layout="class"``.  The
    step runs once per device (``shard_map``): under ``layout="class"`` on
    the device's own classes; under the binary layouts on the whole state,
    which the in/out shardings gather and re-shard around it.  ``impl`` is
    the kernel implementation, as in ``kernels.ops``.
    """
    if layout == "class":
        if not isinstance(cfg, MulticlassSVMConfig):
            raise TypeError("layout='class' needs a MulticlassSVMConfig, got "
                            f"{type(cfg).__name__}")
        if table is None and cfg.binary.method.startswith("lookup"):
            table = cfg.table()
        return _make_multiclass_step(cfg, mesh, dim, table, impl)
    if table is None and cfg.method.startswith("lookup"):
        table = cfg.table()
    state_sh, x_sh, y_sh = sv_shardings(cfg, mesh, dim, layout=layout)
    repl = NamedSharding(mesh, P())
    table_sh = (MergeLookupTable(h_table=repl, wd_table=repl)
                if table is not None else None)

    def whole_step(state: SVMState, table, xb, yb):
        return train_step(cfg, table, state, xb, yb, impl=impl)

    step = _per_device(whole_step, mesh,
                       jax.tree.map(lambda _: P(), state_sh))
    args = (
        SVMState(
            sv_x=jax.ShapeDtypeStruct((cfg.slots, dim),
                                      jnp.dtype(cfg.sv_dtype or cfg.dtype)),
            alpha=jax.ShapeDtypeStruct((cfg.slots,), jnp.dtype(cfg.dtype)),
            count=jax.ShapeDtypeStruct((), jnp.int32),
            step=jax.ShapeDtypeStruct((), jnp.int32),
            n_inserts=jax.ShapeDtypeStruct((), jnp.int32),
            n_merges=jax.ShapeDtypeStruct((), jnp.int32),
            kmat=(jax.ShapeDtypeStruct((cfg.slots, cfg.slots), jnp.float32)
                  if cfg.use_kernel_cache else None)),
        (jax.eval_shape(lambda: table) if table is not None else None),
        jax.ShapeDtypeStruct((cfg.batch_size, dim),
                             jnp.dtype(cfg.sv_dtype or cfg.dtype)),
        jax.ShapeDtypeStruct((cfg.batch_size,), jnp.dtype(cfg.dtype)),
    )
    in_sh = (state_sh, table_sh, x_sh, y_sh)
    out_sh = state_sh
    return step, args, in_sh, out_sh


def make_distributed_chunk_step(cfg, mesh, dim: int, chunk_steps: int,
                                table: MergeLookupTable | None = None,
                                layout: str = "replicated",
                                impl: str = "auto"):
    """Per-chunk program for the streaming path on the production mesh.

    The streaming trainers (``core.bsgd.fit_stream`` /
    ``core.multiclass.fit_multiclass_stream``) run one jitted program per
    resident chunk; this builds that program's distributed form — a
    ``chunk_steps``-long scan of the same sharded ``train_step`` the per-batch
    cell uses, with the chunk arrays sharded like the per-step minibatch along
    their batch axis (``(steps, batch, dim)`` with batch over the data axes —
    or every axis for ``layout="replicated"`` — and the SV state sharded per
    ``layout``: ``replicated`` / ``slots`` / ``class``).  Returns
    ``(chunk_fn, args_abstract, in_shardings, out_shardings)`` with
    ``chunk_fn(state, table, xc, yc) -> state``; jit with
    ``donate_argnums=(0,)`` so the budgeted state updates in place while
    chunks stream through (``launch.train.svm_stream_loop`` is the driver).
    """
    step, args, in_sh, out_sh = make_distributed_step(cfg, mesh, dim, table,
                                                      layout=layout, impl=impl)
    state_abs, table_abs, xb_abs, yb_abs = args
    state_sh, table_sh, x_sh, y_sh = in_sh

    def chunk_fn(state, table, xc, yc):
        def body(st, xy):
            return step(st, table, xy[0], xy[1]), ()

        state, _ = jax.lax.scan(body, state, (xc, yc))
        return state

    cargs = (state_abs, table_abs,
             jax.ShapeDtypeStruct((chunk_steps,) + xb_abs.shape, xb_abs.dtype),
             jax.ShapeDtypeStruct((chunk_steps,) + yb_abs.shape, yb_abs.dtype))
    cin_sh = (state_sh, table_sh,
              NamedSharding(mesh, P(None, *x_sh.spec)),
              NamedSharding(mesh, P(None, *y_sh.spec)))
    return chunk_fn, cargs, cin_sh, out_sh


def lower_svm_cell(mesh, *, budget: int = 16384, dim: int = 1024,
                   batch: int = 8192, method: str = "lookup-wd",
                   layout: str = "replicated", n_classes: int = 8,
                   stream_steps: int = 0, step: str = "train",
                   maintenance_engine: str = "xla",
                   step_engine: str = "composed", solver: str = "bsgd",
                   maintenance: str = "merge"):
    """AOT-lower the production-scale BSGD cell (the paper-technique cell).

    Production sizing: budget 16k SVs, 1k features, 8k-example global
    minibatch — the regime where the kernel matrix (batch x slots) is real
    MXU work and merging fires every step.  ``layout="class"`` lowers the
    one-vs-rest multi-class cell instead (``n_classes`` stacked problems,
    classes sharded over ``model``).  ``stream_steps > 0`` lowers the
    streaming-epoch chunk program instead — the ``stream_steps``-minibatch
    scan one resident chunk runs as (``make_distributed_chunk_step``).
    ``step="predict"`` lowers the SERVING cell instead of a training step:
    the fused multiclass scoring program on the exported bfloat16 bank,
    bank replicated and the request batch sharded over every axis
    (``layout="serve"``; ``layout="class"`` here selects the multiclass
    bank, anything else the binary one) — the dryrun roofline for
    ``launch.serve --arch svm_bsgd``.  ``maintenance_engine="pallas"``
    lowers the fused maintenance-event engine instead of the vmapped
    per-class while loop (implies the kernel cache; the event rounds stay
    collective-free under ``layout="class"`` because every array they touch
    is sharded along the class axis).  ``step_engine="pallas"`` lowers the
    fused train-step megakernel (DESIGN.md §12) — the whole step is one
    launch chain per class block; under ``layout="class"`` every array the
    fused step touches (bank, alpha, cache, counters) stays sharded along
    the class axis and the cell adds NO collectives over the §11
    event-engine cell (identical collective breakdown in the dryrun — the
    shared all-gathers belong to the kernel-cache-carrying step, not the
    fusion).  ``solver="bdca"`` lowers the dual coordinate-ascent step
    (``core.bdca``) through the SAME layouts — it implies the kernel cache
    (the ascent reads cached Gram rows) and composes with
    ``maintenance_engine`` but not with ``step_engine="pallas"``.
    ``maintenance`` selects the strategy the cell drains through (any
    ``core.budget.STRATEGIES`` entry; ``removal-project``/``quantized``
    imply the kernel cache — their coefficients are cache reads — and only
    compose with the xla engines, which ``BSGDConfig`` validation enforces
    with a clear error rather than silently lowering the wrong program).
    """
    cfg = BSGDConfig(budget=budget, lambda_=1e-6, gamma=2.0**-7, method=method,
                     batch_size=batch, dtype="float32", sv_dtype="bfloat16",
                     use_kernel_cache=(solver == "bdca"
                                       or maintenance_engine == "pallas"
                                       or step_engine == "pallas"
                                       or maintenance in ("removal-project",
                                                          "quantized")),
                     maintenance=maintenance,
                     maintenance_engine=maintenance_engine,
                     step_engine=step_engine, solver=solver)
    if layout == "class":
        cfg = MulticlassSVMConfig(n_classes=n_classes, binary=cfg)
    if step == "predict":
        b = cfg.binary if layout == "class" else cfg
        fn, args, in_sh, out_sh = make_distributed_predict(
            mesh, dim=dim, batch=batch, slots=b.slots,
            n_classes=n_classes if layout == "class" else None,
            bank_dtype=b.sv_dtype or b.dtype)
        with mesh:
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh).lower(*args)
        return lowered, cfg
    table = cfg.table()
    if stream_steps > 0:
        step, args, in_sh, out_sh = make_distributed_chunk_step(
            cfg, mesh, dim, stream_steps, table, layout=layout)
    else:
        step, args, in_sh, out_sh = make_distributed_step(cfg, mesh, dim,
                                                          table, layout=layout)
    with mesh:
        lowered = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                          donate_argnums=(0,)).lower(*args)
    return lowered, cfg
