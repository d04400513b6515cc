"""Fused multi-merge candidate scoring — P fixed partners in one VMEM pass.

Multi-merge budget maintenance (Qaadan & Glasmachers 2018) executes the P
cheapest merges per maintenance event instead of one.  Scoring then needs a
(P, S) sweep: for each fixed partner ``a_p`` (the P smallest-|alpha| SVs) and
every candidate ``j``, the bilinearly-interpolated table values at
``(m_pj, kappa_pj) = (a_p / (a_p + alpha_j), k(x_p, x_j))`` — with the kappa
rows read from the persistent kernel cache (``core.kernel_cache``), not
recomputed.

This kernel extends ``merge_lookup`` along two axes:

  * the P fixed-partner rows are scored together — the hat-basis weight
    matrices are built for all P*bS coordinates and hit the MXU as ONE
    (P*bS, G) x (G, G) matmul per table;
  * BOTH tables (WD_norm for scoring, h for executing the winners) are
    interpolated in the same pass, so the strategy layer gets everything a
    fused multi-merge scatter needs from a single kernel launch.

Same gather-free bilinear trick as ``merge_lookup``: f(u, v) = w(u)^T T w(v)
with the piecewise-linear hat basis materialized via ``broadcasted_iota``.
Default ``block_s`` is 128 (vs 512 for the single-row kernel): the weight
matrices are (8*bS, G) here, and 8 * 128 * 400 fp32 * 4 buffers ~ 6.5 MB
keeps comfortably under the ~16 MB VMEM budget with both tables resident.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .merge_lookup import HIGHEST, WD_INVALID, _hat_weights

P_PAD = 8  # fp32 sublane multiple; ops pads the pair axis to this


def _multi_merge_kernel(alpha_ref, kappa_ref, valid_ref, amin_ref,
                        h_tab_ref, wd_tab_ref, wd_ref, h_ref, *, g: int):
    alpha = alpha_ref[...].astype(jnp.float32)         # (P, bS) — per-row
    kappa = kappa_ref[...].astype(jnp.float32)         # (P, bS)
    valid = valid_ref[...]                             # (P, bS)
    a_min = amin_ref[:, 0].astype(jnp.float32)         # (P,)
    p, bs = kappa.shape

    denom = a_min[:, None] + alpha                     # (P, bS)
    m = jnp.clip(a_min[:, None] / jnp.where(denom == 0.0, 1.0, denom), 0.0, 1.0)
    kap = jnp.clip(kappa, 0.0, 1.0)

    w_m = _hat_weights(m.reshape(p * bs), g)           # (P*bS, G)
    w_k = _hat_weights(kap.reshape(p * bs), g)         # (P*bS, G)
    # One MXU matmul per table for all P rows; rowsum against w_k finishes
    # the bilinear interpolation without a single gather.
    rows_wd = jax.lax.dot_general(w_m, wd_tab_ref[...], (((1,), (0,)), ((), ())),
                                  precision=HIGHEST,
                                  preferred_element_type=jnp.float32)
    rows_h = jax.lax.dot_general(w_m, h_tab_ref[...], (((1,), (0,)), ((), ())),
                                 precision=HIGHEST,
                                 preferred_element_type=jnp.float32)
    interp_wd = jnp.sum(rows_wd * w_k, axis=1).reshape(p, bs)
    interp_h = jnp.sum(rows_h * w_k, axis=1).reshape(p, bs)

    wd = denom * denom * interp_wd
    wd_ref[...] = jnp.where(valid > 0, wd, WD_INVALID)
    h_ref[...] = interp_h


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def multi_merge_scores_pallas(alpha, kappa_rows, valid, a_min, h_table,
                              wd_table, *, block_s: int = 128,
                              interpret: bool = False):
    """(wd, h) of shape (P, s) for P fixed partners against all candidates.

    alpha, kappa_rows, valid: (P, s); a_min: (P,); tables: (G, G).
    Each pair row carries its OWN candidate-alpha row — in the binary engine
    all P rows are the same broadcast alpha, while the class-batched layout
    folds ``(C, P)`` pairs into the row axis with per-class alphas
    (``kernels.ops.multi_merge_scores``).  P must be a multiple of ``P_PAD``
    and s of ``block_s`` (ops pads).
    Invalid slots get WD = 3.4e38 (argmin-safe, finite for bf16 casts).
    """
    p, s = kappa_rows.shape
    assert s % block_s == 0 and p % P_PAD == 0, "pad first (see kernels.ops)"
    assert alpha.shape == (p, s), "alpha must be per-row (broadcast upstream)"
    g = h_table.shape[0]
    wd, h = pl.pallas_call(
        functools.partial(_multi_merge_kernel, g=g),
        grid=(s // block_s,),
        in_specs=[
            pl.BlockSpec((p, block_s), lambda i: (0, i)),
            pl.BlockSpec((p, block_s), lambda i: (0, i)),
            pl.BlockSpec((p, block_s), lambda i: (0, i)),
            pl.BlockSpec((p, 1), lambda i: (0, 0)),
            pl.BlockSpec((g, g), lambda i: (0, 0)),    # tables: whole, every step
            pl.BlockSpec((g, g), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((p, block_s), lambda i: (0, i)),
            pl.BlockSpec((p, block_s), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((p, s), jnp.float32),
            jax.ShapeDtypeStruct((p, s), jnp.float32),
        ],
        interpret=interpret,
    )(alpha.astype(jnp.float32), kappa_rows.astype(jnp.float32),
      valid.astype(jnp.float32), a_min[:, None].astype(jnp.float32),
      h_table.astype(jnp.float32), wd_table.astype(jnp.float32))
    return wd, h
