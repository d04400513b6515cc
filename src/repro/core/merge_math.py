"""Closed-form math of the support-vector merging problem (paper §2-3).

Merging two support vectors ``(alpha_a, x_a)`` and ``(alpha_b, x_b)`` under a
Gaussian kernel ``k(x, x') = exp(-gamma * ||x - x'||^2)`` reduces to a 1-D
problem on the segment ``z = h * x_a + (1 - h) * x_b``.  With

    m     = alpha_a / (alpha_a + alpha_b)        (relative coefficient mass)
    kappa = k(x_a, x_b)                          (cosine of the RKHS angle)

the objective (paper Alg. 1, line 7) is

    h*(m, kappa) = argmax_{h in [0,1]}  s_{m,kappa}(h)
    s_{m,kappa}(h) = m * kappa^{(1-h)^2} + (1-m) * kappa^{h^2}

and the optimal merged coefficient / weight degradation follow in closed form:

    alpha_z = alpha_a * kappa^{(1-h)^2} + alpha_b * kappa^{h^2}
    WD      = alpha_a^2 + alpha_b^2 + 2*alpha_a*alpha_b*kappa - alpha_z^2

``WD`` normalized by ``(alpha_a + alpha_b)^2`` depends only on ``(m, kappa)``:

    WD_norm(m, kappa) = m^2 + (1-m)^2 + 2*m*(1-m)*kappa - s_{m,kappa}(h*)^2

Everything here is pure jnp and differentiable; the golden section search is a
fixed-iteration ``lax.fori_loop`` (iteration count derived from the target
precision), so it jits, vmaps and lowers to TPU without dynamic shapes.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ..kernels import ref

# Golden ratio constants.
INVPHI = (math.sqrt(5.0) - 1.0) / 2.0  # 1/phi ~ 0.618034
# kappa = exp(-gamma d^2) is clipped away from 0 so log(kappa) stays finite.
KAPPA_MIN = 1e-30
# Paper Lemma 1: s_{m,kappa} is unimodal iff kappa > e^{-2}.
KAPPA_UNIMODAL = math.exp(-2.0)

# Paper precisions: runtime GSS eps=0.01, table-build GSS eps=1e-10.
EPS_STANDARD = 1e-2
EPS_PRECISE = 1e-10


def gss_num_iters(eps: float) -> int:
    """Iterations for the bracket [0,1] to shrink below ``eps`` (width *= 1/phi)."""
    return int(math.ceil(math.log(eps) / math.log(INVPHI)))


def kappa_pow(kappa, expo):
    """kappa**expo computed as exp(expo * log kappa), safe at kappa -> 0
    (with ``kernels.ref``'s few-ulp exp and log)."""
    kappa = jnp.clip(kappa, KAPPA_MIN, 1.0)
    return ref.exp_f32(expo * ref.log_f32(kappa))


def s_objective(h, m, kappa):
    """s_{m,kappa}(h) = m kappa^{(1-h)^2} + (1-m) kappa^{h^2} (to maximize)."""
    return m * kappa_pow(kappa, (1.0 - h) ** 2) + (1.0 - m) * kappa_pow(kappa, h**2)


@partial(jax.jit, static_argnames=("eps",))
def golden_section_search(m, kappa, eps: float = EPS_STANDARD):
    """Maximize ``s_{m,kappa}`` over [0, 1] by golden section search.

    Fully vectorized over the (broadcasted) shapes of ``m`` and ``kappa``; the
    iteration count is static (derived from ``eps``) so the loop unrolls into a
    fixed-depth dependency chain, exactly like the reference solver's cost
    model (~10 sequential evaluations for eps=0.01, ~48 for eps=1e-10).
    """
    m, kappa = jnp.broadcast_arrays(jnp.asarray(m, jnp.float64 if jax.config.jax_enable_x64 else jnp.float32),
                                    jnp.asarray(kappa))
    n_iters = gss_num_iters(eps)
    a = jnp.zeros_like(m)
    b = jnp.ones_like(m)

    def body(_, ab):
        a, b = ab
        span = b - a
        c = b - span * INVPHI
        d = a + span * INVPHI
        fc = s_objective(c, m, kappa)
        fd = s_objective(d, m, kappa)
        go_left = fc > fd  # keep [a, d] if the left probe wins, else [c, b]
        return jnp.where(go_left, a, c), jnp.where(go_left, d, b)

    a, b = jax.lax.fori_loop(0, n_iters, body, (a, b))
    return 0.5 * (a + b)


def wd_norm_at(h, m, kappa):
    """Normalized weight degradation at merge coefficient ``h``.

    WD / (alpha_a + alpha_b)^2 = m^2 + (1-m)^2 + 2 m (1-m) kappa - s(h)^2.
    """
    s = s_objective(h, m, kappa)
    return m**2 + (1.0 - m) ** 2 + 2.0 * m * (1.0 - m) * kappa - s**2


@partial(jax.jit, static_argnames=("eps",))
def solve_merge(m, kappa, eps: float = EPS_STANDARD):
    """(h*, WD_norm(m, kappa)) via golden section search."""
    h = golden_section_search(m, kappa, eps=eps)
    return h, wd_norm_at(h, m, kappa)


def merge_alpha_z(alpha_a, alpha_b, kappa, h):
    """Optimal merged coefficient for z = h x_a + (1-h) x_b (paper Alg.1 l.8)."""
    return alpha_a * kappa_pow(kappa, (1.0 - h) ** 2) + alpha_b * kappa_pow(kappa, h**2)


def weight_degradation(alpha_a, alpha_b, kappa, alpha_z):
    """||Delta||^2 = alpha_a^2 + alpha_b^2 + 2 alpha_a alpha_b kappa - alpha_z^2."""
    return alpha_a**2 + alpha_b**2 + 2.0 * alpha_a * alpha_b * kappa - alpha_z**2


def merge_point(h, x_a, x_b):
    """z = h * x_a + (1 - h) * x_b."""
    return h * x_a + (1.0 - h) * x_b


def gss_numpy(m, kappa, eps: float = EPS_PRECISE):
    """float64 numpy golden section search (vectorized), for table precompute.

    fp32 GSS saturates at ~sqrt(eps_f32) ~ 3e-4 argmax precision near a smooth
    maximum (function-value comparisons drown in rounding noise), so the
    paper's eps=1e-10 table build runs in doubles — exactly like the reference
    C++ implementation.  One-time offline cost, not a runtime path.
    """
    import numpy as np

    m = np.asarray(m, np.float64)
    kappa = np.clip(np.asarray(kappa, np.float64), KAPPA_MIN, 1.0)
    lk = np.log(kappa)

    def s(h):
        return m * np.exp((1.0 - h) ** 2 * lk) + (1.0 - m) * np.exp(h**2 * lk)

    a = np.zeros_like(m)
    b = np.ones_like(m)
    for _ in range(gss_num_iters(eps)):
        span = b - a
        c = b - span * INVPHI
        d = a + span * INVPHI
        go_left = s(c) > s(d)
        a = np.where(go_left, a, c)
        b = np.where(go_left, d, b)
    return 0.5 * (a + b)


def brute_force_h(m, kappa, n_grid: int = 200_001):
    """Dense-grid argmax oracle for tests (not jitted on purpose: fp64 numpy)."""
    import numpy as np

    hs = np.linspace(0.0, 1.0, n_grid)
    kk = max(float(kappa), KAPPA_MIN)
    vals = float(m) * kk ** ((1.0 - hs) ** 2) + (1.0 - float(m)) * kk ** (hs**2)
    return float(hs[int(np.argmax(vals))])


def s_second_derivative_at_half(kappa):
    """d^2/dh^2 s_{1/2,kappa}(h) at h = 1/2 (sign flips at kappa = e^{-2}).

    For m = 1/2:  s(h) = (kappa^{(1-h)^2} + kappa^{h^2}) / 2, and
    s''(1/2) = kappa^{1/4} * log(kappa) * (2 + log(kappa))  (paper Lemma 1:
    s''_{1/2,kappa}(1/2) > 0  <=>  kappa < e^{-2}).
    """
    lk = jnp.log(jnp.clip(kappa, KAPPA_MIN, 1.0))
    return kappa_pow(kappa, 0.25) * lk * (2.0 + lk)
