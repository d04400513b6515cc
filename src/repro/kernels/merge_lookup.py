"""Fused budget-maintenance candidate scoring — the paper's lookup, TPU-native.

Given the fixed merge partner's coefficient ``a_min`` and, per candidate j,
its coefficient ``alpha_j`` and kernel value ``kappa_j = k(x_min, x_j)``, this
kernel computes the bilinearly-interpolated table value at
``(m_j, kappa_j) = (a_min/(a_min+alpha_j), kappa_j)`` for ALL candidates in one
VMEM pass — replacing the per-candidate golden section search (paper §3).

TPU adaptation — gather-free bilinear interpolation:
  a 2-D bilinear lookup is  f(u, v) = w(u)^T  T  w(v)  where ``w(u)`` is the
  piecewise-linear *hat* basis:  w_i(u) = max(0, 1 - |u*(G-1) - i|)  (exactly
  two nonzeros).  Instead of per-lane gathers (weakly supported on the TPU
  vector unit), we materialize the hat weights densely with ``broadcasted_iota``
  and evaluate  rowsum((W_u @ T) * W_v)  — one (bS, G) x (G, G) MXU matmul per
  block against the VMEM-resident table (400x400 fp32 = 640 KB).  This turns
  the paper's "fast lookup" into systolic-array work with zero HBM traffic per
  candidate, and removes the ~10-step sequential dependency chain GSS needs.

The same kernel interpolates either table (WD_norm for Lookup-WD scoring, or
h for Lookup-h), selected by what the caller passes as ``table``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

WD_INVALID = 3.4e38  # python float: jnp constants would be captured by the kernel
# Every matmul of this package that carries values (not 0/1 counts) asks
# for an fp32 contraction rather than the backend's default, which on a TPU
# may be a single bf16 pass.
HIGHEST = jax.lax.Precision.HIGHEST


def _hat_weights(coord, g: int):
    """(bS,) unit-interval coords -> (bS, G) hat-basis weights (2 nonzeros/row)."""
    u = jnp.clip(coord, 0.0, 1.0) * (g - 1)
    # Mosaic only builds integer iotas: make an int32 one and cast it
    iota = jax.lax.broadcasted_iota(jnp.int32, (coord.shape[0], g),
                                    1).astype(jnp.float32)
    return jnp.maximum(0.0, 1.0 - jnp.abs(u[:, None] - iota))


def _merge_score_kernel(alpha_ref, kappa_ref, valid_ref, amin_ref, table_ref,
                        wd_ref, interp_ref, *, g: int):
    alpha = alpha_ref[0, :].astype(jnp.float32)       # (bS,)
    kappa = kappa_ref[0, :].astype(jnp.float32)
    valid = valid_ref[0, :]
    a_min = amin_ref[0, 0]
    table = table_ref[...]                            # (G, G) resident in VMEM

    denom = a_min + alpha
    m = jnp.clip(a_min / jnp.where(denom == 0.0, 1.0, denom), 0.0, 1.0)
    kap = jnp.clip(kappa, 0.0, 1.0)

    w_m = _hat_weights(m, g)                          # (bS, G)
    w_k = _hat_weights(kap, g)                        # (bS, G)
    # Gather-free bilinear: rowsum((W_m @ T) * W_k); the matmul hits the MXU.
    rows = jax.lax.dot_general(w_m, table, (((1,), (0,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)  # (bS, G)
    interp = jnp.sum(rows * w_k, axis=1)              # (bS,)

    wd = denom * denom * interp
    wd_ref[0, :] = jnp.where(valid > 0, wd, WD_INVALID)
    interp_ref[0, :] = interp


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def merge_scores_pallas(alpha, kappa_row, valid, a_min, table, *,
                        block_s: int = 512, interpret: bool = False):
    """Score all merge candidates against a precomputed table.

    alpha, kappa_row, valid: (s,) with s % block_s == 0 (ops pads);
    a_min: scalar; table: (G, G).  Returns ``(wd, interp)`` of shape (s,)
    where invalid slots get WD = 3.4e38 (argmin-safe, finite for bf16 casts).
    """
    (s,) = alpha.shape
    assert s % block_s == 0, "pad to block multiple (see kernels.ops)"
    g = table.shape[0]
    amin_arr = jnp.full((1, 1), a_min, jnp.float32)
    wd, interp = pl.pallas_call(
        functools.partial(_merge_score_kernel, g=g),
        grid=(s // block_s,),
        in_specs=[
            pl.BlockSpec((1, block_s), lambda i: (0, i)),
            pl.BlockSpec((1, block_s), lambda i: (0, i)),
            pl.BlockSpec((1, block_s), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((g, g), lambda i: (0, 0)),     # whole table, every step
        ],
        out_specs=[
            pl.BlockSpec((1, block_s), lambda i: (0, i)),
            pl.BlockSpec((1, block_s), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, s), jnp.float32),
            jax.ShapeDtypeStruct((1, s), jnp.float32),
        ],
        interpret=interpret,
    )(alpha[None, :], kappa_row[None, :], valid[None, :].astype(jnp.float32),
      amin_arr, table.astype(jnp.float32))
    return wd[0], interp[0]
