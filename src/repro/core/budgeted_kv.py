"""Beyond-paper transfer: budgeted KV cache with merge-based maintenance.

The analogy to the paper (DESIGN.md §4): a decode-time KV cache is a kernel
expansion — keys are support vectors, values are (vector-valued)
coefficients, and the attention kernel exp(q.k) is locally Gaussian in k.
Evicting cache entries = BSGD's "removal"; the paper showed *merging* is
strictly better, and that the merge coefficient can be a precomputed lookup.

Maintenance of a full cache mirrors paper Alg. 1:
  1. fix the entry with minimal importance (||v||, the alpha analogue),
  2. kappa_j = exp(-gamma ||k_min - k_j||^2) via the same rbf kernels,
  3. m = |v_min| / (|v_min| + |v_j|); h from the SAME MergeLookupTable,
  4. merged entry: k_z = h k_min + (1-h) k_j,
     v_z = v_min kappa^{(1-h)^2} + v_j kappa^{h^2}   (alpha_z, per channel).

This gives O(budget) decode attention for arbitrarily long generations —
the sub-quadratic-memory option noted for the full-attention archs.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..kernels import ref as kref
from . import merge_math
from .lookup import MergeLookupTable


class KVBudgetState(NamedTuple):
    k: jax.Array      # (B, W, H, hd)
    v: jax.Array      # (B, W, H, hd)
    count: jax.Array  # () int32 — filled slots (same across batch/heads)


def init_kv_state(batch: int, budget: int, n_heads: int, head_dim: int, dtype):
    return KVBudgetState(k=jnp.zeros((batch, budget, n_heads, head_dim), dtype),
                         v=jnp.zeros((batch, budget, n_heads, head_dim), dtype),
                         count=jnp.zeros((), jnp.int32))


def _merge_one(k_bh, v_bh, count, gamma, table: MergeLookupTable):
    """Merge the least-important pair for one (batch, head): k/v (W, hd)."""
    w = k_bh.shape[0]
    idx = jnp.arange(w)
    active = idx < count
    imp = jnp.where(active, jnp.linalg.norm(v_bh, axis=-1), jnp.inf)
    i_min = jnp.argmin(imp)
    a_min = imp[i_min]

    # a plain jnp row: this runs vmapped per (batch, head), where the Pallas
    # rbf kernel's blocks would not tile
    kappa = kref.rbf_row(k_bh, k_bh[i_min], gamma)
    a_j = jnp.where(active, jnp.linalg.norm(v_bh, axis=-1), 0.0)
    m = jnp.clip(a_min / jnp.where(a_min + a_j == 0, 1.0, a_min + a_j), 0, 1)
    kap = jnp.clip(kappa, 0.0, 1.0)
    wd = (a_min + a_j) ** 2 * table.lookup_wd_norm(m, kap)
    wd = jnp.where(active & (idx != i_min), wd, jnp.inf)
    j = jnp.argmin(wd)

    h = table.lookup_h(m[j], kap[j])
    k_z = merge_math.merge_point(h, k_bh[i_min], k_bh[j])
    # Value combination — a documented ADAPTATION of the paper's alpha_z:
    # alpha_z's kappa^h^2 decay is exact for LINEAR kernel fields (the SVM
    # case) but systematically loses value mass under softmax-normalized
    # attention; the importance-weighted convex mean preserves it and is
    # what beats eviction empirically (see examples/budgeted_kv_serve.py).
    v_z = (a_min * v_bh[i_min] + a_j[j] * v_bh[j]) / (a_min + a_j[j] + 1e-9)

    last = count - 1
    lo = jnp.minimum(i_min, j)
    hi = jnp.maximum(i_min, j)
    k_bh = k_bh.at[lo].set(k_z).at[hi].set(k_bh[last])
    v_bh = v_bh.at[lo].set(v_z).at[hi].set(v_bh[last])
    v_bh = v_bh.at[last].set(0.0)
    return k_bh, v_bh


def _evict_one(k_bh, v_bh, count):
    """Removal baseline (what the paper shows merging beats): drop min-||v||."""
    w = k_bh.shape[0]
    imp = jnp.where(jnp.arange(w) < count, jnp.linalg.norm(v_bh, axis=-1),
                    jnp.inf)
    i_min = jnp.argmin(imp)
    last = count - 1
    k_bh = k_bh.at[i_min].set(k_bh[last])
    v_bh = v_bh.at[i_min].set(v_bh[last]).at[last].set(0.0)
    return k_bh, v_bh


@partial(jax.jit, static_argnames=("policy",))
def kv_append(state: KVBudgetState, k_new, v_new, gamma, table: MergeLookupTable,
              *, policy: str = "merge"):
    """Append one token's K/V; merge (or evict) per (batch, head) at budget.

    k_new/v_new: (B, 1, H, hd).  Returns the new state (count <= budget).
    """
    budget = state.k.shape[1]

    def do_maintain(st):
        if policy == "merge":
            fn = lambda kk, vv: _merge_one(kk, vv, st.count, gamma, table)
        else:
            fn = lambda kk, vv: _evict_one(kk, vv, st.count)
        maintain = jax.vmap(jax.vmap(fn, in_axes=(1, 1), out_axes=(1, 1)),
                            in_axes=(0, 0), out_axes=(0, 0))
        k2, v2 = maintain(st.k, st.v)
        return KVBudgetState(k=k2, v=v2, count=st.count - 1)

    state = jax.lax.cond(state.count >= budget, do_maintain, lambda s: s, state)
    slot = state.count
    k = jax.lax.dynamic_update_slice(state.k, k_new.astype(state.k.dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(state.v, v_new.astype(state.v.dtype),
                                     (0, slot, 0, 0))
    return KVBudgetState(k=k, v=v, count=state.count + 1)


def kv_attend(state: KVBudgetState, q, scale: float):
    """q: (B, 1, H, hd) against the budgeted cache -> (B, 1, H, hd)."""
    valid = jnp.arange(state.k.shape[1]) < state.count
    bias = jnp.where(valid, 0.0, -1e30)[None, None, None, :]
    scores = jnp.einsum("bqhd,bwhd->bhqw", q.astype(jnp.float32),
                        state.k.astype(jnp.float32)) * scale + bias
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqw,bwhd->bqhd", probs,
                      state.v.astype(jnp.float32)).astype(q.dtype)
