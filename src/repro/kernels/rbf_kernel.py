"""Tiled Gaussian (RBF) kernel-matrix Pallas kernel — the BSGD per-step hot spot.

Computes K[i, j] = exp(-gamma * ||x_i - y_j||^2) for x: (n, d), y: (m, d) via
the matmul decomposition  ||x-y||^2 = ||x||^2 + ||y||^2 - 2 x.y :

  * grid (n/bn, m/bm, d/bd); the d axis is innermost and accumulates the
    squared distance into the output block (revisited across k steps — the
    standard Pallas accumulate-into-output matmul pattern).
  * the -2 x yT term runs on the MXU (jnp.dot with fp32 accumulation);
    the per-block norm terms are rank-1 VPU adds.
  * exp(-gamma * acc) is applied once, on the last k step (VPU transcendental).

VMEM footprint per step = bn*bd + bm*bd inputs + bn*bm fp32 output block;
defaults (128, 128, 512) use ~0.6 MB — far below the ~16 MB/core budget, and
every matmul dim is a multiple of the 128x128 MXU tile.

Callers use ``repro.kernels.ops.rbf_matrix``, which pads to block multiples
(TPU Pallas requires block-divisible shapes), selects interpret mode off-TPU,
and slices the result back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rbf_block_kernel(x_ref, y_ref, gamma_ref, o_ref, *, n_k: int):
    """One (bn, bm) output block; accumulates squared distance over k steps."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...].astype(jnp.float32)  # (bn, bd)
    y = y_ref[...].astype(jnp.float32)  # (bm, bd)
    # Partial squared distance over this feature block:
    #   ||x_blk||^2 + ||y_blk||^2 - 2 x_blk . y_blk
    xn = jnp.sum(x * x, axis=1, keepdims=True)          # (bn, 1)   VPU
    yn = jnp.sum(y * y, axis=1, keepdims=True).T        # (1, bm)   VPU
    xy = jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # (bn, bm) MXU
    o_ref[...] += xn + yn - 2.0 * xy

    @pl.when(k == n_k - 1)
    def _finish():
        gamma = gamma_ref[0, 0]
        d2 = jnp.maximum(o_ref[...], 0.0)
        o_ref[...] = jnp.exp(-gamma * d2)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_m", "block_d", "interpret"))
def rbf_matrix_pallas(x, y, gamma, *, block_n: int = 128, block_m: int = 128,
                      block_d: int = 512, interpret: bool = False):
    """Pallas RBF kernel matrix.  Shapes must be multiples of the block sizes
    (``ops.rbf_matrix`` handles padding)."""
    n, d = x.shape
    m, d2 = y.shape
    assert d == d2, (x.shape, y.shape)
    assert n % block_n == 0 and m % block_m == 0 and d % block_d == 0, (
        "pad inputs to block multiples (see kernels.ops.rbf_matrix)")
    n_k = d // block_d
    gamma_arr = jnp.full((1, 1), gamma, jnp.float32)
    return pl.pallas_call(
        functools.partial(_rbf_block_kernel, n_k=n_k),
        grid=(n // block_n, m // block_m, n_k),
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_m, block_d), lambda i, j, k: (j, k)),
            pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, m), jnp.float32),
        interpret=interpret,
    )(x, y, gamma_arr)
