"""Inputs made from the seed on the device, in one jitted call each.

Copies of the program's synthetic generators (``repro.data.synthetic``
``make_susy_like`` and ``make_blobs_multiclass``), kept here so that no
change to the program can change what the benchmark feeds it.  A
configuration names its generator and its arguments under ``data``.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.jit, static_argnames=("n", "dim", "flip"))
def susy_like(key, n: int, dim: int, flip: float = 0.2):
    """Overlapping classes (~20% label noise), labels in {-1, +1}: a
    quadratic boundary in a random subspace, like the SUSY physics set."""
    k1, k2, k3 = jax.random.split(key, 3)
    x = jax.random.normal(k1, (n, dim))
    w = jax.random.normal(k2, (dim,))
    score = (jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
             + 0.5 * jnp.sum(x[:, : dim // 2] ** 2, axis=1) - dim // 4)
    y = jnp.where(score > 0, 1.0, -1.0)
    do_flip = jax.random.bernoulli(k3, flip, (n,))
    return x, jnp.where(do_flip, -y, y).astype(jnp.float32)


@partial(jax.jit, static_argnames=("n", "dim", "n_classes", "sep", "noise"))
def blobs(key, n: int, dim: int, n_classes: int, sep: float,
          noise: float = 1.0):
    """``n_classes`` Gaussian blobs at centers ``sep * N(0, I)``; int32
    labels in [0, n_classes).  The centers depend on ``key`` only, so rows
    drawn with ``fold_in``s of one key share them."""
    kc, ky, kx = jax.random.split(key, 3)
    centers = sep * jax.random.normal(kc, (n_classes, dim))
    y = jax.random.randint(ky, (n,), 0, n_classes, dtype=jnp.int32)
    x = centers[y] + noise * jax.random.normal(kx, (n, dim))
    return x, y


def make(data: dict, key, n: int):
    """``n`` rows of the configuration's data: ``(x f32 (n, dim), y)``."""
    if data["generator"] == "susy_like":
        return susy_like(key, n, data["dim"], data.get("flip", 0.2))
    if data["generator"] == "blobs":
        return blobs(key, n, data["dim"], data["n_classes"], data["sep"],
                     data.get("noise", 1.0))
    raise ValueError(f"unknown generator {data['generator']!r}")
