"""Plain float32 ``jax.numpy`` reference of budgeted SGD with Lookup-WD
merging, one-vs-rest over C classes (C = 1 is the binary problem).

Written from the paper's definitions (Pegasos step, Alg. 1 merging, the
precomputed golden-section tables of §3) and imports nothing of the
program.  Kernel values are recomputed from the support vectors whenever
they are needed: no kernel cache, no fused kernels.  The slot layout
(violators appended at the watermark; a merge writes the merged point at
the lower of the two slots and moves the last active slot into the higher
one; a removal moves the last slot into the hole) follows the program's
documented layout, because the choice of the smallest ``|alpha|`` breaks
ties by slot.

``prec="bf16"`` computes every kernel value from bfloat16 operands with a
bfloat16 matrix product: the control, one precision below the float32 the
configurations state.  The rounding is explicit (``lax.reduce_precision``):
a float32 -> bfloat16 -> float32 round trip may be dropped by the TPU
compiler as excess precision, and on the chip it was, for the 18-feature
rows of a batch-1 step.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
INVPHI = (math.sqrt(5.0) - 1.0) / 2.0
KAPPA_MIN = 1e-30
NO_PARTNER = 1e30


class State(NamedTuple):
    sv: jax.Array        # (C, S, d)
    alpha: jax.Array     # (C, S)
    count: jax.Array     # (C,) int32
    t: jax.Array         # () int32, Pegasos step (starts at 1)
    n_inserts: jax.Array  # (C,) int32
    n_merges: jax.Array   # (C,) int32 maintenance events


def merge_tables(grid: int = 400, eps: float = 1e-10):
    """h*(m, kappa) and WD_norm(m, kappa) on a ``grid x grid`` lattice of
    the unit square, by float64 golden-section search to ``eps`` (paper
    §3), with the closed forms on the degenerate columns kappa = 0, 1."""
    g = np.linspace(0.0, 1.0, grid)
    m, k = np.meshgrid(g, g, indexing="ij")
    lk = np.log(np.clip(k, KAPPA_MIN, 1.0))

    def s(h):
        return m * np.exp((1.0 - h) ** 2 * lk) + (1.0 - m) * np.exp(h ** 2 * lk)

    a, b = np.zeros_like(m), np.ones_like(m)
    for _ in range(int(math.ceil(math.log(eps) / math.log(INVPHI)))):
        span = b - a
        c, d = b - span * INVPHI, a + span * INVPHI
        left = s(c) > s(d)
        a, b = np.where(left, a, c), np.where(left, d, b)
    h = 0.5 * (a + b)
    wd = m ** 2 + (1.0 - m) ** 2 + 2.0 * m * (1.0 - m) * k - s(h) ** 2
    h[:, -1], wd[:, -1] = g, 0.0                      # kappa = 1: h = m
    h[:, 0] = np.where(g >= 0.5, 1.0, 0.0)            # kappa = 0: removal
    wd[:, 0] = np.minimum(g, 1.0 - g) ** 2
    return jnp.asarray(h, jnp.float32), jnp.asarray(wd, jnp.float32)


def bilinear(table, u, v):
    g = table.shape[0]
    u = jnp.clip(u, 0.0, 1.0) * (g - 1)
    v = jnp.clip(v, 0.0, 1.0) * (g - 1)
    i = jnp.clip(jnp.floor(u).astype(jnp.int32), 0, g - 2)
    j = jnp.clip(jnp.floor(v).astype(jnp.int32), 0, g - 2)
    du, dv = u - i, v - j
    top = table[i, j] * (1.0 - dv) + table[i, j + 1] * dv
    bot = table[i + 1, j] * (1.0 - dv) + table[i + 1, j + 1] * dv
    return top * (1.0 - du) + bot * du


def low(x, prec: str):
    """``x`` rounded to bfloat16 (kept in float32) when ``prec == "bf16"``.
    Products of two such values are exact in float32, so a float32 matrix
    product of them is the bfloat16 product with float32 accumulation."""
    if prec == "bf16":
        return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return x


def matvec(k, a, prec: str):
    return jnp.matmul(low(k, prec), low(a, prec), precision=HIGHEST)


def sqdist(x, s, prec: str):
    """||x_i - s_j||^2, (n, d) x (m, d) -> (n, m)."""
    x, s = low(x, prec), low(s, prec)
    xs = jnp.matmul(x, s.T, precision=HIGHEST)
    xn, sn = jnp.sum(x * x, -1), jnp.sum(s * s, -1)
    return jnp.maximum(xn[:, None] + sn[None, :] - 2.0 * xs, 0.0)


def init(n_classes: int, slots: int, dim: int) -> State:
    z = jnp.zeros((n_classes,), jnp.int32)
    return State(sv=jnp.zeros((n_classes, slots, dim), jnp.float32),
                 alpha=jnp.zeros((n_classes, slots), jnp.float32),
                 count=z, t=jnp.ones((), jnp.int32), n_inserts=z,
                 n_merges=z)


def _maintain(sv, alpha, count, n_merges, over, gamma, h_tab, wd_tab, prec):
    """One masked event for one class: merge the smallest-|alpha| SV with
    its best same-sign partner by Lookup-WD, or remove it when it has
    none; a no-op unless ``over``."""
    slots = alpha.shape[0]
    idx = jnp.arange(slots)
    active = idx < count
    i = jnp.argmin(jnp.where(active, jnp.abs(alpha), jnp.inf))
    a_i = alpha[i]
    d2 = sqdist(sv[i][None], sv, prec)[0]
    kap = jnp.clip(jnp.exp(-gamma * d2), 0.0, 1.0)
    valid = active & (alpha * a_i > 0) & (idx != i)
    den = a_i + alpha
    m = jnp.clip(a_i / jnp.where(den == 0, 1.0, den), 0.0, 1.0)
    wd = jnp.where(valid, (a_i + alpha) ** 2 * bilinear(wd_tab, m, kap),
                   jnp.inf)
    j = jnp.argmin(wd)
    merge = wd[j] < NO_PARTNER
    h = bilinear(h_tab, m[j], kap[j])
    # kappa^p = exp(-gamma p d^2): the merged coefficient of paper Alg. 1
    a_z = (a_i * jnp.exp(-gamma * (1.0 - h) ** 2 * d2[j])
           + alpha[j] * jnp.exp(-gamma * h ** 2 * d2[j]))
    z = h * sv[i] + (1.0 - h) * sv[j]
    last = count - 1
    lo, hi = jnp.minimum(i, j), jnp.maximum(i, j)
    sv_m = sv.at[lo].set(z).at[hi].set(sv[last])
    al_m = alpha.at[lo].set(a_z).at[hi].set(alpha[last]).at[last].set(0.0)
    sv_r = sv.at[i].set(sv[last])
    al_r = alpha.at[i].set(alpha[last]).at[last].set(0.0)
    sv2 = jnp.where(merge, sv_m, sv_r)
    al2 = jnp.where(merge, al_m, al_r)
    return (jnp.where(over, sv2, sv), jnp.where(over, al2, alpha),
            count - over.astype(jnp.int32),
            n_merges + over.astype(jnp.int32))


def _class_step(sv, alpha, count, n_ins, n_mrg, t, xb, yb, *, budget,
                lambda_, gamma, h_tab, wd_tab, prec):
    """The Pegasos minibatch step of one class, then maintenance until the
    class is back at its budget."""
    slots, batch = alpha.shape[0], xb.shape[0]
    k_b = jnp.exp(-gamma * sqdist(xb, sv, prec))              # (B, S)
    active = jnp.arange(slots) < count
    a_act = jnp.where(active, alpha, 0.0)
    f = matvec(k_b, a_act, prec)
    eta = 1.0 / (lambda_ * t)
    alpha = alpha * (1.0 - eta * lambda_)
    viol = yb * f < 1.0
    pos = count + jnp.cumsum(viol.astype(jnp.int32)) - 1
    slot = jnp.where(viol, pos, slots)
    sv = sv.at[slot].set(xb, mode="drop")
    alpha = alpha.at[slot].set((eta * yb / batch).astype(alpha.dtype),
                               mode="drop")
    n_new = jnp.sum(viol).astype(jnp.int32)
    count, n_ins = count + n_new, n_ins + n_new
    for _ in range(batch):
        sv, alpha, count, n_mrg = _maintain(
            sv, alpha, count, n_mrg, count > budget, gamma, h_tab, wd_tab,
            prec)
    return sv, alpha, count, n_ins, n_mrg


@partial(jax.jit, static_argnames=("budget", "lambda_", "gamma", "prec"))
def run_chunk(state: State, xc, yc, h_tab, wd_tab, *, budget: int,
              lambda_: float, gamma: float, prec: str = "f32") -> State:
    """Train over one chunk: ``xc (steps, B, d)``, ``yc (steps, C, B)``
    one-vs-rest targets in {-1, +1}."""
    step = partial(_class_step, budget=budget, lambda_=lambda_, gamma=gamma,
                   h_tab=h_tab, wd_tab=wd_tab, prec=prec)
    all_classes = jax.vmap(step, in_axes=(0, 0, 0, 0, 0, None, None, 0))

    def body(st, xy):
        xb, yb = xy
        sv, al, cnt, nin, nmg = all_classes(st.sv, st.alpha, st.count,
                                            st.n_inserts, st.n_merges, st.t,
                                            xb, yb)
        return State(sv, al, cnt, st.t + 1, nin, nmg), ()

    state, _ = jax.lax.scan(body, state, (xc, yc))
    return state


def ovr_targets(y, n_classes: int):
    """Labels -> (..., C, B) targets: binary labels pass through as C = 1,
    class ids become +1 for their class and -1 for the rest."""
    y = jnp.asarray(y)
    if n_classes == 1:
        return y.astype(jnp.float32)[..., None, :]
    onehot = jnp.arange(n_classes)[:, None] == y[..., None, :].astype(
        jnp.int32)
    return jnp.where(onehot, 1.0, -1.0)


# --------------------------------------------------------------------------
# evaluation of a model (program's or reference's) by the same plain code
# --------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("gamma",))
def decision(sv, alpha, count, x, *, gamma: float):
    """f_c(x) for every class: (C, S, d), (C, S), (C,), (n, d) -> (C, n)."""
    def one(s, a, c):
        a = jnp.where(jnp.arange(a.shape[0]) < c, a, 0.0)
        return jnp.matmul(jnp.exp(-gamma * sqdist(x, s, "f32")), a,
                          precision=HIGHEST)
    return jax.vmap(one)(sv, alpha, count)


@partial(jax.jit, static_argnames=("gamma",))
def rkhs_norm(sv, alpha, count, *, gamma: float):
    """||w_c||_H = sqrt(alpha^T K alpha) per class: (C,)."""
    def one(s, a, c):
        a = jnp.where(jnp.arange(a.shape[0]) < c, a, 0.0)
        k = jnp.exp(-gamma * sqdist(s, s, "f32"))
        return jnp.sqrt(jnp.maximum(
            jnp.dot(a, jnp.matmul(k, a, precision=HIGHEST),
                    precision=HIGHEST), 0.0))
    return jax.vmap(one)(sv, alpha, count)


@partial(jax.jit, static_argnames=("gamma", "prec"))
def gram(sv, count, *, gamma: float, prec: str = "f32"):
    """K(s_i, s_j) over each class's active slots, 0 elsewhere:
    (C, S, d), (C,) -> (C, S, S)."""
    def one(s, c):
        act = jnp.arange(s.shape[0]) < c
        k = jnp.exp(-gamma * sqdist(s, s, prec))
        return jnp.where(act[:, None] & act[None, :], k, 0.0)
    return jax.vmap(one)(sv, count)

