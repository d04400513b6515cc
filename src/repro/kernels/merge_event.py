"""Fused budget-maintenance event — one launch per round, all classes.

The class-axis engine's maintenance hot spot (ROADMAP: "Batched maintenance
under vmap at scale") is NOT the merge math — it is the memory traffic around
it: under ``vmap`` the per-event two-row/two-column scatters on the stacked
``(C, slots, slots)`` kernel cache defeat XLA's in-place buffer aliasing, so
every event degenerates to full-matrix copies.  This kernel folds the classes
onto the grid axis (like ``merge_multi``) and executes ONE whole maintenance
event per class per launch:

  * argmin-|alpha| fixed-partner selection over the active watermark;
  * the kappa row read straight from the class's VMEM-resident cache block
    (``kmat`` is symmetric: row ``i_min`` IS ``k(x_min, .)``);
  * Lookup-WD candidate scoring with the same gather-free hat-basis bilinear
    trick as ``merge_lookup`` (one ``(bS, G) x (G, G)`` MXU matmul per table
    per chunk against the VMEM-resident tables);
  * the merged point's cache row derived IN the kernel from the two parent
    rows (the log-space combine of ``core.kernel_cache`` — the z-row never
    round-trips through HBM);
  * the merge / removal-fallback two-row + two-column update written in
    place into the class's VMEM blocks — no scatter, no full-matrix HBM copy
    (outputs alias inputs, so XLA updates the stacked state in place).

Classes whose ``over`` flag is clear skip the event: their blocks are written
back bitwise unchanged, which is what makes the sorted-excess schedule in
``core.budget.run_maintenance_classes`` correct — the engine runs exactly
``max_c(count_c - budget)`` rounds and finished classes ride along for free.

The event works on ``Ref``s, not on whole-block values: Mosaic keeps every
live ``(S, S)`` value on its VMEM stack, so a value-style update would hold
several copies of the cache at once.  Rows are read and written through
their aligned (8, W) sublane tile, columns through their aligned (S, 128)
lane tile (Mosaic indexes the lane axis only at multiples of 128), and
slot vectors are (1, S) rows.  Scalars come from one-hot reductions — the
TPU vector unit has no per-lane gather.  VMEM budget: the class's (S, S)
cache and (S, D) bank blocks, double-buffered in and out, dominate
(``fused_compiler_params`` sizes the limit from them).  Keep ``slots`` and
``dim`` at multiples of 128 in production configs to avoid the pad/slice
copy in ``ops.merge_event``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .merge_lookup import HIGHEST, WD_INVALID, _hat_weights
from .ref import NO_PARTNER, _safe_log, exp_f32

# VMEM per TensorCore on v5e and v6e is 128 MiB; leave Mosaic room for its
# own internal scratch.
VMEM_LIMIT_BYTES = 120 * 2**20
VMEM_HEADROOM_BYTES = 8 * 2**20
LANE = 128


def _first_where(pred, iota, s):
    """Smallest index with ``pred`` true (== jnp.argmin tie-breaking)."""
    return jnp.min(jnp.where(pred, iota, s)).astype(jnp.int32)


def _pick(row, iota, i):
    """``row[0, i]`` as a scalar (one-hot reduction, no lane gather)."""
    return jnp.sum(jnp.where(iota == i, row, 0.0))


def _sublanes(ref) -> int:
    return 8 * 4 // ref.dtype.itemsize         # rows per tile: 8 fp32, 16 bf16


def get_row(ref, i):
    """Row ``i`` of a 2-D ref as a (1, W) fp32 value; ``i`` is clamped."""
    t = _sublanes(ref)
    i = jnp.clip(i, 0, ref.shape[0] - 1)
    base = pl.multiple_of((i // t) * t, t)
    blk = ref[pl.ds(base, t), :].astype(jnp.float32)
    ids = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) + base
    return jnp.sum(jnp.where(ids == i, blk, 0.0), axis=0, keepdims=True)


def set_row(ref, i, row):
    """``ref[i, :] = row`` for a (1, W) row; no-op when ``i >= rows``."""
    t = _sublanes(ref)

    @pl.when(i < ref.shape[0])
    def _():
        base = pl.multiple_of((i // t) * t, t)
        blk = ref[pl.ds(base, t), :]
        ids = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) + base
        ref[pl.ds(base, t), :] = jnp.where(ids == i, row.astype(ref.dtype),
                                           blk)


def set_col(ref, j, col):
    """``ref[:, j] = col`` for an (S, 1) column; no-op when ``j >= cols``."""
    @pl.when(j < ref.shape[1])
    def _():
        base = pl.multiple_of((j // LANE) * LANE, LANE)
        blk = ref[:, pl.ds(base, LANE)]
        ids = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1) + base
        ref[:, pl.ds(base, LANE)] = jnp.where(ids == j, col.astype(ref.dtype),
                                              blk)


def set_elem(ref, i, j, v):
    """``ref[i, j] = v``; no-op when ``i`` or ``j`` is out of range."""
    t = _sublanes(ref)

    @pl.when((i < ref.shape[0]) & (j < ref.shape[1]))
    def _():
        base = pl.multiple_of((i // t) * t, t)
        blk = ref[pl.ds(base, t), :]
        rows = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) + base
        cols = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
        ref[pl.ds(base, t), :] = jnp.where((rows == i) & (cols == j),
                                           jnp.asarray(v, ref.dtype), blk)


def lookup_chunks(alpha, kappa_row, a_min, h_tab, wd_tab, *, g: int,
                  block_s: int):
    """Lookup-WD ``(wd, h)`` of every candidate against one fixed partner.

    alpha, kappa_row: (1, S) fp32; a_min: () fp32; tables (G, G).  Scored in
    ``block_s`` chunks so the (bS, G) hat-weight matrices stay small; the
    chunks are joined as (1, bS) rows along the lane axis because Mosaic
    cannot concatenate 1-D vectors.  Returns two (1, S) rows.
    """
    s = alpha.shape[1]
    wd_parts, h_parts = [], []
    for start in range(0, s, block_s):
        al_c = alpha[0, start:start + block_s]
        kap_c = kappa_row[0, start:start + block_s]
        denom = a_min + al_c
        m = jnp.clip(a_min / jnp.where(denom == 0.0, 1.0, denom), 0.0, 1.0)
        kap = jnp.clip(kap_c, 0.0, 1.0)
        w_m = _hat_weights(m, g)                         # (bS, G)
        w_k = _hat_weights(kap, g)
        rows_wd = jax.lax.dot_general(
            w_m, wd_tab, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        rows_h = jax.lax.dot_general(
            w_m, h_tab, (((1,), (0,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)
        wd_parts.append((denom * denom * jnp.sum(rows_wd * w_k, axis=1))[None])
        h_parts.append(jnp.sum(rows_h * w_k, axis=1)[None])
    return (jnp.concatenate(wd_parts, axis=1),
            jnp.concatenate(h_parts, axis=1))


def merge_event_refs(count, alpha_ref, sv_ref, km_ref, h_tab, wd_tab, *,
                     g: int, block_s: int):
    """One whole merge event, in place on one class's VMEM blocks.

    count: () int32 (over budget); alpha_ref: (1, S) storage dtype; sv_ref:
    (S, D) storage dtype; km_ref: (S, S) fp32; tables: (G, G) fp32 values.
    Every read precedes every write.  Shared by ``_merge_event_kernel`` (one
    event per launch) and the fused train-step megakernel
    (``kernels.train_step``), which chains these events as its maintenance
    rounds without leaving VMEM.
    """
    alpha = alpha_ref[...].astype(jnp.float32)           # (1, S)
    s = alpha.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    active = iota < count

    # 1. fixed partner: active argmin |alpha| (first occurrence on ties).
    abs_a = jnp.where(active, jnp.abs(alpha), jnp.inf)
    i_min = _first_where(abs_a == jnp.min(abs_a), iota, s)
    a_min = _pick(alpha, iota, i_min)

    # 2. kappa row = cache row i_min (kmat is symmetric).
    kappa_row = get_row(km_ref, i_min)                   # (1, S)

    # 3. Lookup-WD scoring in block_s chunks (hat-basis bilinear, both
    #    tables interpolated per chunk — merge_lookup's trick).
    wd, h = lookup_chunks(alpha, kappa_row, a_min, h_tab, wd_tab, g=g,
                          block_s=block_s)
    valid = active & (alpha * a_min > 0) & (iota != i_min)
    wd = jnp.where(valid, wd, WD_INVALID)

    # best partner; removal fallback when every candidate is invalid
    wd_mn = jnp.min(wd)
    j_star = _first_where(wd == wd_mn, iota, s)
    has_partner = wd_mn < NO_PARTNER
    last = count - 1

    # 4. merge math on the chosen pair
    h_m = _pick(h, iota, j_star)
    k_ij = _pick(kappa_row, iota, j_star)
    kap_m = jnp.clip(k_ij, 0.0, 1.0)
    a_j = _pick(alpha, iota, j_star)
    a_last = _pick(alpha, iota, last)
    lk = _safe_log(kap_m)
    a_z = (a_min * exp_f32((1.0 - h_m) ** 2 * lk)
           + a_j * exp_f32(h_m**2 * lk))
    row_j = get_row(km_ref, j_star)
    row_last = get_row(km_ref, last)
    x_i = get_row(sv_ref, i_min)
    x_j = get_row(sv_ref, j_star)
    v_last = get_row(sv_ref, last)
    z = h_m * x_i + (1.0 - h_m) * x_j                    # (1, D)

    # z's cache row from the parent rows (kernel_cache's log-space combine)
    lz = (h_m * _safe_log(kappa_row) + (1.0 - h_m) * _safe_log(row_j)
          - h_m * (1.0 - h_m) * _safe_log(k_ij))
    z_row = exp_f32(jnp.minimum(lz, 0.0))

    # 5. the two-row + two-column update (budget._merge_once's fused form):
    #    t1 <- z (or the old ``last`` on removal), t2 <- the old ``last``;
    #    t2 = S on removal, so its writes are skipped.  Rows first, then
    #    columns, so column values win at the intersections.
    lo = jnp.minimum(i_min, j_star)
    hi = jnp.maximum(i_min, j_star)
    z_row_l = _pick(z_row, iota, last)
    r_merge = jnp.where(iota == hi, z_row_l, z_row)
    r_merge = jnp.where(iota == lo, 1.0, r_merge)
    r_move = jnp.where(iota == hi, 1.0, row_last)
    r_move = jnp.where(iota == lo, z_row_l, r_move)
    r_remove = jnp.where(iota == i_min, 1.0, row_last)
    t1 = jnp.where(has_partner, lo, i_min)
    t2 = jnp.where(has_partner, hi, s)
    r1 = jnp.where(has_partner, r_merge, r_remove)

    set_row(km_ref, t1, r1)
    set_row(km_ref, t2, r_move)
    set_col(km_ref, t1, r1.reshape(s, 1))
    set_col(km_ref, t2, r_move.reshape(s, 1))
    set_row(sv_ref, t1, jnp.where(has_partner, z, v_last))
    set_row(sv_ref, t2, v_last)

    a1 = jnp.where(has_partner, a_z, a_last)
    al = jnp.where(iota == t1, a1, alpha)
    al = jnp.where(iota == t2, a_last, al)
    al = jnp.where(iota == last, 0.0, al)
    alpha_ref[...] = al.astype(alpha_ref.dtype)


def _merge_event_kernel(count_ref, over_ref, alpha_ref, sv_ref, kmat_ref,
                        h_tab_ref, wd_tab_ref, alpha_out, sv_out, kmat_out,
                        *, g: int, block_s: int):
    c = pl.program_id(0)
    alpha_out[...] = alpha_ref[...]
    sv_out[...] = sv_ref[...]
    kmat_out[...] = kmat_ref[...]

    @pl.when(over_ref[c] > 0)
    def _():
        merge_event_refs(count_ref[c], alpha_out, sv_out, kmat_out,
                         h_tab_ref[...], wd_tab_ref[...], g=g,
                         block_s=block_s)


def fused_compiler_params(name: str, *, block_bytes: int, temp_bytes: int):
    """Mosaic parameters for a fused kernel that holds one class per step.

    The pipeline double-buffers every block, and the body keeps about
    ``temp_bytes`` of values live.  Asks for that much VMEM, and raises,
    naming the limit, where it exceeds what a TPU core has.
    """
    need = 2 * block_bytes + temp_bytes + VMEM_HEADROOM_BYTES
    if need > VMEM_LIMIT_BYTES:
        raise ValueError(
            f"{name}: one class block needs ~{need / 2**20:.0f} MiB of VMEM, "
            f"over the {VMEM_LIMIT_BYTES / 2**20:.0f} MiB limit of a TPU "
            "core; lower the budget (slots) or the feature dim")
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=need)


def merge_event_vmem(s: int, d: int, g: int, sv_itemsize: int = 4) -> tuple:
    """``(block_bytes, temp_bytes)`` of one class at padded widths: the in
    and out blocks of the cache, bank and alpha plus both tables, and the
    scoring chunk's (bS <= 256, G) hat-weight values with the (S, 128)
    column tiles."""
    block = 2 * (s * s * 4 + s * d * sv_itemsize + 8 * s * 4) + 2 * g * g * 4
    return block, 8 * 256 * g * 4 + 4 * s * LANE * 4


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def merge_event_pallas(sv_x, alpha, kmat, count, over, h_table, wd_table, *,
                       block_s: int = 256, interpret: bool = False):
    """One maintenance event per over-budget class, one launch for them all.

    sv_x: (C, S, D); alpha: (C, 1, S); kmat: (C, S, S) fp32; count, over:
    (C,) int32, prefetched into SMEM; tables: (G, G).  S and D must be
    multiples of the tile sizes (``ops.merge_event`` pads).  Returns
    ``(sv_x, alpha, kmat)`` with classes where ``over == 0`` bitwise
    unchanged; outputs alias the inputs so the whole stacked state updates
    in place.  Oracle: ``ref.merge_event``.
    """
    c, s, d = sv_x.shape
    g = h_table.shape[0]
    # scoring chunk must divide the (padded) slot count; ops pads s to a
    # multiple of 128, so 128 always works when block_s does not divide s
    bs = block_s if s % block_s == 0 else (128 if s % 128 == 0 else s)
    block_bytes, temp_bytes = merge_event_vmem(s, d, g, sv_x.dtype.itemsize)
    row = pl.BlockSpec((None, 1, s), lambda i, *_: (i, 0, 0))
    bank = pl.BlockSpec((None, s, d), lambda i, *_: (i, 0, 0))
    cache = pl.BlockSpec((None, s, s), lambda i, *_: (i, 0, 0))
    table = pl.BlockSpec((g, g), lambda i, *_: (0, 0))   # whole, every step
    alpha_new, sv_new, kmat_new = pl.pallas_call(
        functools.partial(_merge_event_kernel, g=g, block_s=bs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                       # count, over
            grid=(c,),
            in_specs=[row, bank, cache, table, table],
            out_specs=[row, bank, cache]),
        out_shape=[
            jax.ShapeDtypeStruct((c, 1, s), alpha.dtype),
            jax.ShapeDtypeStruct((c, s, d), sv_x.dtype),
            jax.ShapeDtypeStruct((c, s, s), kmat.dtype),
        ],
        input_output_aliases={2: 0, 3: 1, 4: 2},
        compiler_params=fused_compiler_params(
            "merge_event", block_bytes=block_bytes, temp_bytes=temp_bytes),
        interpret=interpret,
    )(count.astype(jnp.int32), over.astype(jnp.int32), alpha, sv_x,
      kmat.astype(jnp.float32), h_table.astype(jnp.float32),
      wd_table.astype(jnp.float32))
    return sv_new, alpha_new, kmat_new
