"""Public jit'd wrappers for the Pallas kernels, with implementation dispatch.

``impl`` semantics (every op takes it):
  * ``"auto"``             — Pallas on TPU, pure-jnp reference elsewhere (XLA
                             compiles the reference well on CPU/GPU).
  * ``"pallas"``           — compiled Pallas (TPU).
  * ``"pallas_interpret"`` — Pallas in interpret mode (CPU correctness runs;
                             this is how the kernel bodies are validated here).
  * ``"ref"``              — the pure-jnp oracle from ``kernels.ref``.

Wrappers own all shape plumbing the kernels refuse to do: padding to block
multiples, re-slicing, and scalar/1-D massaging.
"""
from __future__ import annotations

import contextlib
import math
from functools import partial

import jax
import jax.numpy as jnp

from . import gss as gss_kernel
from . import merge_event as merge_event_kernel
from . import merge_lookup as merge_lookup_kernel
from . import merge_multi as merge_multi_kernel
from . import rbf_kernel
from . import ref
from . import train_step as train_step_kernel

IMPLS = ("auto", "pallas", "pallas_interpret", "ref")


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "ref"
    if impl not in IMPLS:
        raise ValueError(f"impl={impl!r} not in {IMPLS}")
    return impl


def runs_pallas(impl: str) -> bool:
    """Whether ``impl`` resolves to a Pallas kernel (compiled or
    interpreted) rather than the ``ref`` oracle."""
    return _resolve(impl) != "ref"


def _pad_to(x, axis: int, multiple: int, value=0.0):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _pad_to_lane(x, axes, multiple=128, value=0.0):
    """Pad ``axes`` of ``x`` up to tile multiples (the shared dispatcher
    plumbing: every kernel wrapper pads with this, slices back after).

    ``axes`` is an axis or tuple of axes; ``multiple`` is one int for all of
    them or a tuple matched positionally.  Padding is appended (never
    prepended) with ``value``, so ``out[..slices of the original shape..]``
    round-trips to ``x`` exactly.
    """
    axes = (axes,) if isinstance(axes, int) else tuple(axes)
    mults = ((multiple,) * len(axes) if isinstance(multiple, int)
             else tuple(multiple))
    if len(mults) != len(axes):
        raise ValueError(f"got {len(axes)} axes but {len(mults)} multiples")
    for ax, m in zip(axes, mults):
        x = _pad_to(x, ax, m, value)
    return x


# --------------------------------------------------------------------------
# RBF kernel matrix / row
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("impl", "block_n", "block_m", "block_d"))
def rbf_matrix(x, y, gamma, *, impl: str = "auto", block_n: int = 128,
               block_m: int = 128, block_d: int = 512):
    """K[i, j] = exp(-gamma ||x_i - y_j||^2); x: (n, d), y: (m, d) -> (n, m)."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.rbf_matrix(x, y, gamma)
    n, m = x.shape[0], y.shape[0]
    bd = min(block_d, max(128, x.shape[1]))
    xp = _pad_to_lane(x, (0, 1), (block_n, bd))
    yp = _pad_to_lane(y, (0, 1), (block_m, bd))
    out = rbf_kernel.rbf_matrix_pallas(
        xp, yp, gamma, block_n=block_n, block_m=block_m, block_d=bd,
        interpret=(impl == "pallas_interpret"))
    return out[:n, :m]


@partial(jax.jit, static_argnames=("impl",))
def rbf_row(sv_x, x, gamma, *, impl: str = "auto"):
    """kappa_row[j] = k(x, sv_x[j]); sv_x: (s, d), x: (d,) -> (s,)."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.rbf_row(sv_x, x, gamma)
    return rbf_matrix(x[None, :], sv_x, gamma, impl=impl)[0]


# --------------------------------------------------------------------------
# Class-batched decision scoring (the serving cell's contraction)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("impl",))
def class_scores(x, sv_x, alpha, gamma, *, impl: str = "auto"):
    """All-class decision scores from ONE kernel launch: (C, n).

    x: (n, d) request rows; sv_x: (C, slots, d) stacked SV bank; alpha:
    (C, slots) coefficients (inactive slots zeroed by the caller).  The
    class axis folds into the SV axis so the kernel block is a single
    (n, C * slots) ``rbf_matrix`` — one Pallas launch / one XLA matmul no
    matter how many classes — then a per-class contraction over slots with
    accumulation in ``alpha``'s dtype (fp32 in the serving path, so a
    bfloat16 bank only quantizes the kernel's *inputs*).  Oracle:
    ``ref.class_scores`` (C sequential kernel calls).
    """
    c, slots, d = sv_x.shape
    with jax.named_scope("class_scores"):
        k = rbf_matrix(x, sv_x.reshape(c * slots, d), gamma, impl=impl)
        k = k.reshape(x.shape[0], c, slots)
        return jnp.einsum("ncs,cs->cn", k.astype(alpha.dtype), alpha,
                          precision=jax.lax.Precision.HIGHEST)


# --------------------------------------------------------------------------
# Merge-candidate scoring against a precomputed table (Lookup-WD / Lookup-h)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("impl", "block_s"))
def merge_scores(alpha, kappa_row, valid, a_min, table, *, impl: str = "auto",
                 block_s: int = 512):
    """(wd, interp) per candidate; invalid slots get a large finite WD.

    Class-batched layout: ``alpha``/``kappa_row``/``valid`` of shape (C, s)
    with ``a_min`` (C,) scores one fixed partner *per class* in one pass —
    each class row carries its own alpha, so this is exactly the row-wise
    layout of the multi-merge kernel (one launch, both lookups from the one
    ``table``).  Returns (C, s) arrays.
    """
    impl = _resolve(impl)
    if kappa_row.ndim == 2:                     # class-batched: C rows at once
        if impl == "ref":
            return ref.multi_merge_scores_rows(alpha, kappa_row, valid, a_min,
                                               table, table)
        # clamp to the multi-row kernel's VMEM-safe block: it keeps P_PAD
        # rows of hat weights resident, unlike the single-row kernel whose
        # default this function's block_s=512 was sized for
        wd, interp = _multi_merge_rows_pallas(
            alpha, kappa_row, valid, a_min, table, table,
            block_s=min(block_s, 128),
            interpret=(impl == "pallas_interpret"))
        return wd, interp
    if impl == "ref":
        wd = ref.merge_scores(alpha, kappa_row, valid, a_min, table)
        m, kap = ref.merge_coords(a_min, alpha, kappa_row)
        interp = ref.bilinear_lookup(table, m, kap)
        return wd, interp
    s = alpha.shape[0]
    bs = min(block_s, max(128, s))
    pad = lambda a: _pad_to_lane(a, 0, bs)
    wd, interp = merge_lookup_kernel.merge_scores_pallas(
        pad(alpha), pad(kappa_row), pad(valid.astype(jnp.float32)), a_min,
        table, block_s=bs, interpret=(impl == "pallas_interpret"))
    wd = jnp.where(jnp.arange(wd.shape[0]) < s, wd, jnp.inf)[:s]
    return wd, interp[:s]


# --------------------------------------------------------------------------
# Fused maintenance event (one merge/removal per over-budget class)
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("impl", "block_s"))
def merge_event(sv_x, alpha, kmat, count, over, table, *, impl: str = "auto",
                block_s: int = 256):
    """One fused maintenance-event round over stacked classes.

    sv_x: (C, s, d); alpha: (C, s); kmat: (C, s, s) fp32 kernel cache;
    count, over: (C,) int32/bool.  Every class with ``over`` set executes one
    Lookup-WD merge event (argmin-|alpha| fixed partner, cached kappa row,
    best same-sign partner, removal fallback) exactly as
    ``core.budget._merge_once`` would on its slice; classes with ``over``
    clear return bitwise unchanged.  Returns ``(sv_x, alpha, kmat)`` — the
    caller owns ``count -= over`` and the round schedule
    (``core.budget.run_maintenance_classes``).  Oracle: ``ref.merge_event``;
    the Pallas path folds classes onto the grid axis and updates the blocks
    in place in VMEM (``merge_event.merge_event_pallas``).
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.merge_event(sv_x, alpha, kmat, count, over,
                               table.h_table, table.wd_table)
    _, s, d = sv_x.shape
    sv_p = _pad_to_lane(sv_x, (1, 2))
    al_p = _pad_to_lane(alpha, 1)
    km_p = _pad_to_lane(kmat, (1, 2))
    sv_n, al_n, km_n = merge_event_kernel.merge_event_pallas(
        sv_p, al_p[:, None, :], km_p, count.astype(jnp.int32),
        over.astype(jnp.int32), table.h_table, table.wd_table,
        block_s=block_s, interpret=(impl == "pallas_interpret"))
    return sv_n[:, :s, :d], al_n[:, 0, :s], km_n[:, :s, :s]


# --------------------------------------------------------------------------
# Batched golden section search
# --------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("impl", "n_iters"))
def gss_solve(m, kappa, *, n_iters: int, impl: str = "auto"):
    """argmax_h of the merge objective for arrays of (m, kappa); any shape."""
    impl = _resolve(impl)
    if impl == "ref":
        return ref.gss(m, kappa, n_iters)
    shape = m.shape
    flat_m = m.reshape(1, -1).astype(jnp.float32)
    flat_k = kappa.reshape(1, -1).astype(jnp.float32)
    br, bc = 1, min(512, max(128, flat_m.shape[1]))
    flat_m = _pad_to_lane(flat_m, 1, bc)
    flat_k = _pad_to_lane(flat_k, 1, bc, value=1.0)  # kappa=1: benign problem
    h = gss_kernel.gss_pallas(flat_m, flat_k, n_iters=n_iters, block=(br, bc),
                              interpret=(impl == "pallas_interpret"))
    return h[0, : math.prod(shape)].reshape(shape)


# --------------------------------------------------------------------------
# Batched multi-merge scoring (P fixed partners, both tables, one pass)
# --------------------------------------------------------------------------
def _multi_merge_rows_pallas(alpha_rows, kappa_rows, valid, a_min, h_table,
                             wd_table, *, block_s: int, interpret: bool):
    """Row-wise Pallas launches: every pair row carries its own alpha.

    Tiles the row axis: the kernel keeps all its P rows resident per grid
    step (hat-weight matrices scale with P * block_s), so one launch per
    P_PAD rows keeps VMEM bounded no matter how many rows are folded in
    (merge_batch, or n_classes * merge_batch in the class-batched layout).
    """
    p, s = kappa_rows.shape
    bs = min(block_s, max(128, s))
    pad_s = lambda a: _pad_to_lane(a, a.ndim - 1, bs)
    pad_p = lambda a: _pad_to_lane(a, 0, merge_multi_kernel.P_PAD)
    wds, hs = [], []
    for start in range(0, p, merge_multi_kernel.P_PAD):
        sl = slice(start, min(start + merge_multi_kernel.P_PAD, p))
        wd_c, h_c = merge_multi_kernel.multi_merge_scores_pallas(
            pad_p(pad_s(alpha_rows[sl])), pad_p(pad_s(kappa_rows[sl])),
            pad_p(pad_s(valid[sl].astype(jnp.float32))), pad_p(a_min[sl]),
            h_table, wd_table, block_s=bs, interpret=interpret)
        wds.append(wd_c[:sl.stop - sl.start])
        hs.append(h_c[:sl.stop - sl.start])
    return jnp.concatenate(wds)[:, :s], jnp.concatenate(hs)[:, :s]


@partial(jax.jit, static_argnames=("impl", "block_s"))
def multi_merge_scores(alpha, kappa_rows, valid, a_min, table, *,
                       impl: str = "auto", block_s: int = 128):
    """(wd, h) of shape (P, s) for P fixed merge partners at once.

    alpha: (s,); kappa_rows, valid: (P, s); a_min: (P,);
    table: a ``MergeLookupTable`` (both grids are interpolated in one pass).
    Class-batched layout: ``alpha`` (C, s); ``kappa_rows``/``valid``
    (C, P, s); ``a_min`` (C, P) -> (C, P, s) outputs.  The (C, P) pair grid
    folds onto the kernel's row axis with each class's alpha repeated across
    its P rows, so all classes' maintenance candidates score in the same
    launch sequence.
    Invalid slots get WD = +inf (ref) / 3.4e38 (pallas) — argmin-safe either way.
    """
    impl = _resolve(impl)
    if kappa_rows.ndim == 3:                    # class-batched
        c, p, s = kappa_rows.shape
        if impl == "ref":
            return ref.multi_merge_scores_classes(
                alpha, kappa_rows, valid, a_min, table.h_table, table.wd_table)
        alpha_rows = jnp.broadcast_to(alpha[:, None, :], (c, p, s))
        wd, h = _multi_merge_rows_pallas(
            alpha_rows.reshape(c * p, s), kappa_rows.reshape(c * p, s),
            valid.reshape(c * p, s), a_min.reshape(c * p),
            table.h_table, table.wd_table, block_s=block_s,
            interpret=(impl == "pallas_interpret"))
        return wd.reshape(c, p, s), h.reshape(c, p, s)
    if impl == "ref":
        return ref.multi_merge_scores(alpha, kappa_rows, valid, a_min,
                                      table.h_table, table.wd_table)
    p, s = kappa_rows.shape
    alpha_rows = jnp.broadcast_to(alpha[None, :], (p, s))
    return _multi_merge_rows_pallas(alpha_rows, kappa_rows, valid, a_min,
                                    table.h_table, table.wd_table,
                                    block_s=block_s,
                                    interpret=(impl == "pallas_interpret"))


# --------------------------------------------------------------------------
# Fused train step (margin + insert + event rounds, one launch chain)
# --------------------------------------------------------------------------
# what trained a stream (the ``kernel`` attribute of ``fit.stream``, by
# index): the jnp path or composed engine, or one of the two fused kernels
FUSED_KERNELS = ("ref", "vmem", "hbm")


def fused_kernel(s: int, d: int, g: int, b: int, itemsize: int = 4) -> str:
    """Which fused train-step kernel runs at padded slots ``s``, feature dim
    ``d``, table grid ``g`` and minibatch rows ``b`` (bank ``itemsize``):
    ``"vmem"`` (``train_step_pallas``, each class's cache a VMEM block)
    where that kernel's VMEM need is within a core's limit, else ``"hbm"``
    (``train_step_hbm_pallas``, the cache left in HBM)."""
    need = merge_event_kernel.vmem_need(
        *train_step_kernel.train_step_vmem(s, d, g, b, itemsize))
    return "vmem" if need <= merge_event_kernel.VMEM_LIMIT_BYTES else "hbm"


def pad_fused_state(sv_x, alpha, kmat):
    """The fused step's stacked state in the kernel's lane-padded layout.

    sv_x: (C, s, d); alpha: (C, s); kmat: (C, s, s) -> (C, S, D), (C, 1, S),
    (C, S, S) with S, D the multiples of 128 at or above s, d; the pad
    region is zero.  ``train_step_padded`` keeps it finite and never lets it
    reach the real region, so a scan may carry these blocks from step to
    step and slice once at the end (``unpad_fused_state``).
    """
    return (_pad_to_lane(sv_x, (1, 2)), _pad_to_lane(alpha, 1)[:, None, :],
            _pad_to_lane(kmat.astype(jnp.float32), (1, 2)))


def unpad_fused_state(sv_p, al_p, km_p, s: int, d: int):
    """Inverse of ``pad_fused_state``: the real (C, s, d), (C, s), (C, s, s)
    region, whatever the pad region holds."""
    return sv_p[:, :s, :d], al_p[:, 0, :s], km_p[:, :s, :s]


@partial(jax.jit, static_argnames=("budget", "lambda_", "gamma", "batch_size",
                                   "maintenance", "merge_batch", "impl",
                                   "block_s"))
def train_step_padded(sv_p, al_p, km_p, count, step, n_inserts, n_merges, xb,
                      yb, k_bb, table, *, budget: int, lambda_: float,
                      gamma: float, batch_size: int, maintenance: str = "merge",
                      merge_batch: int = 4, impl: str = "auto",
                      block_s: int = 256):
    """``train_step`` on state already in the lane-padded layout of
    ``pad_fused_state``: returns the updated padded blocks and counters.

    Only the minibatch (``xb``, ``yb``, ``k_bb``, a few KB) is padded here.
    The kernel is ``fused_kernel`` of the padded shapes: the whole-block
    ``train_step_pallas`` where its VMEM need fits a core, else
    ``train_step_hbm_pallas`` (named scope ``train_step.hbm``), which keeps
    the cache in HBM.  Either updates the state blocks in place, so a
    ``lax.scan`` that carries them copies nothing per step.  The pad region
    stays as the kernel leaves it: zero bank lanes and slots, zero alpha,
    and cache rows and columns of values in [0, 1] that nothing in the real
    region reads.  ``impl`` must resolve to ``"pallas"`` or
    ``"pallas_interpret"``.
    """
    impl = _resolve(impl)
    if impl == "ref":
        raise ValueError("train_step_padded runs the Pallas kernel; the ref "
                         "path takes unpadded state (train_step)")
    with jax.named_scope("train_step.pad_batch"):
        xb_p = _pad_to_lane(xb, (0, 1))
        kbb_p = _pad_to_lane(k_bb, (0, 1))
        yb_p = _pad_to_lane(yb, 1)
    _, s, d = sv_p.shape
    kernel = fused_kernel(s, d, table.h_table.shape[0], xb_p.shape[0],
                          sv_p.dtype.itemsize)
    fn = (train_step_kernel.train_step_pallas if kernel == "vmem"
          else train_step_kernel.train_step_hbm_pallas)
    scope = (contextlib.nullcontext() if kernel == "vmem"
             else jax.named_scope("train_step.hbm"))
    with scope:
        sv_n, al_n, km_n, cnt_n, nins_n, nmrg_n = fn(
            sv_p, al_p, km_p, count, step, n_inserts, n_merges, xb_p,
            yb_p[:, None, :], kbb_p, table.h_table, table.wd_table,
            budget=budget, lambda_=lambda_, gamma=gamma,
            batch_size=batch_size, rounds=batch_size,
            maintenance=maintenance, merge_batch=merge_batch,
            block_s=block_s, interpret=(impl == "pallas_interpret"))
    return sv_n, al_n, km_n, cnt_n, step + 1, nins_n, nmrg_n


@partial(jax.jit, static_argnames=("budget", "lambda_", "gamma", "batch_size",
                                   "maintenance", "merge_batch", "unroll",
                                   "impl", "block_s"))
def train_step(sv_x, alpha, kmat, count, step, n_inserts, n_merges, xb, yb,
               k_bb, table, *, budget: int, lambda_: float, gamma: float,
               batch_size: int, maintenance: str = "merge",
               merge_batch: int = 4, unroll: int = 0, impl: str = "auto",
               block_s: int = 256):
    """One WHOLE multiclass train step in one launch chain: margin rows +
    Pegasos shrink/insert + maintenance event rounds (DESIGN.md §12).

    sv_x: (C, slots, d); alpha: (C, slots); kmat: (C, slots, slots) fp32
    kernel cache (REQUIRED — the fused step maintains it: as VMEM blocks up
    to the size ``fused_kernel`` allows, in HBM past it); count /
    step / n_inserts / n_merges: (C,) int32; xb: (batch, d); yb: (C, batch)
    one-vs-rest targets; k_bb: (batch, batch) = k(xb, xb); ``table`` a
    ``MergeLookupTable``.  ``maintenance`` is ``"merge"`` or
    ``"multi-merge"`` (P = ``merge_batch`` disjoint pairs per round).
    ``unroll`` only affects the reference path's round loop (the Pallas
    kernel always inlines ``batch_size`` masked rounds — one minibatch
    bounds the excess by ``batch_size``).  Returns the updated ``(sv_x,
    alpha, kmat, count, step, n_inserts, n_merges)``.  Oracle and CPU
    production path: ``ref.train_step_fused``.

    The Pallas path pads the state, runs ``train_step_padded`` (which picks
    the kernel by ``fused_kernel``) and slices back: four copies of the
    whole state per call.  A scan of steps pads once and carries the padded
    blocks instead (``core.multiclass.scan_ovr``).
    """
    impl = _resolve(impl)
    if impl == "ref":
        return ref.train_step_fused(
            sv_x, alpha, kmat, count, step, n_inserts, n_merges, xb, yb,
            k_bb, table.h_table, table.wd_table, budget=budget,
            lambda_=lambda_, gamma=gamma, batch_size=batch_size,
            maintenance=maintenance, merge_batch=merge_batch, unroll=unroll)
    _, s, d = sv_x.shape
    with jax.named_scope("train_step.pad"):
        padded = pad_fused_state(sv_x, alpha, kmat)
    sv_n, al_n, km_n, *counters = train_step_padded(
        *padded, count, step, n_inserts, n_merges, xb, yb, k_bb, table,
        budget=budget, lambda_=lambda_, gamma=gamma, batch_size=batch_size,
        maintenance=maintenance, merge_batch=merge_batch, impl=impl,
        block_s=block_s)
    with jax.named_scope("train_step.unpad"):
        return (*unpad_fused_state(sv_n, al_n, km_n, s, d), *counters)
