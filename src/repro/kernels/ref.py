"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: each kernel's test sweeps shapes/dtypes and
asserts allclose against the function here.  They are also the production CPU
fallback (XLA compiles them well); the Pallas kernels target TPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def rbf_matrix(x, y, gamma):
    """Gaussian kernel matrix K[i, j] = exp(-gamma ||x_i - y_j||^2).

    x: (n, d), y: (m, d)  ->  (n, m), computed via the matmul decomposition
    ||x - y||^2 = ||x||^2 + ||y||^2 - 2 x.y  (clamped at 0 for fp safety).
    """
    xn = jnp.sum(x * x, axis=-1)[:, None]
    yn = jnp.sum(y * y, axis=-1)[None, :]
    # fp32 on every backend: XLA's default f32 matmul on a TPU is one bf16 pass
    xy = jnp.matmul(x, y.T, precision=jax.lax.Precision.HIGHEST)
    d2 = xn + yn - 2.0 * xy
    return jnp.exp(-gamma * jnp.maximum(d2, 0.0))


def rbf_row(sv_x, x, gamma):
    """kappa_row[j] = k(x, sv_x[j]).  sv_x: (s, d), x: (d,)  ->  (s,)."""
    d2 = jnp.sum((sv_x - x[None, :]) ** 2, axis=-1)
    return jnp.exp(-gamma * d2)


def bilinear_lookup(table, u, v):
    """Bilinear interpolation of ``table`` at unit-square coords (u, v).

    Identical semantics to ``repro.core.lookup.bilinear_lookup``; duplicated
    here (3 lines of gather math) so the kernels package stays import-clean.
    """
    g0, g1 = table.shape
    uu = jnp.clip(u, 0.0, 1.0) * (g0 - 1)
    vv = jnp.clip(v, 0.0, 1.0) * (g1 - 1)
    i0 = jnp.clip(jnp.floor(uu).astype(jnp.int32), 0, g0 - 2)
    j0 = jnp.clip(jnp.floor(vv).astype(jnp.int32), 0, g1 - 2)
    du = uu - i0
    dv = vv - j0
    top = table[i0, j0] * (1 - dv) + table[i0, j0 + 1] * dv
    bot = table[i0 + 1, j0] * (1 - dv) + table[i0 + 1, j0 + 1] * dv
    return top * (1 - du) + bot * du


def merge_coords(a_min, alpha, kappa):
    """Table coordinates ``(m, kappa)`` of the merge problem, clipped to the
    unit square.

    ``m = a_min / (a_min + alpha)``; same-sign pairs land strictly inside
    (0, 1), and the clip keeps masked-out entries finite so they cannot
    poison an argmin with NaNs.  Broadcasts: ``a_min`` may be a scalar or a
    ``(P, 1)`` column against ``(s,)`` / ``(P, s)`` candidate arrays.  This
    is the single definition shared by the core strategy layer
    (``budget.candidate_scores``) and the kernel oracles/wrappers.
    """
    denom = a_min + alpha
    m = jnp.clip(a_min / jnp.where(denom == 0, 1.0, denom), 0.0, 1.0)
    return m, jnp.clip(kappa, 0.0, 1.0)


def merge_scores(alpha, kappa_row, valid, a_min, wd_table):
    """Lookup-WD candidate scoring (paper Alg. 1 with the lookup solver).

    alpha, kappa_row, valid: (s,); a_min: scalar; wd_table: (G, G).
    Returns WD per candidate with +inf at invalid slots.
    """
    m, kap = merge_coords(a_min, alpha, kappa_row)
    wd = (a_min + alpha) ** 2 * bilinear_lookup(wd_table, m, kap)
    return jnp.where(valid, wd, jnp.inf)


def multi_merge_scores_rows(alpha_rows, kappa_rows, valid, a_min, h_table,
                            wd_table):
    """Row-wise Lookup-WD scoring: every fixed partner brings its OWN
    candidate-alpha row.

    alpha_rows, kappa_rows, valid: (P, s); a_min: (P,); tables: (G, G).
    This is the layout the class-batched engine folds into: ``(C, P)`` pairs
    flatten onto the row axis with each class's alpha repeated across its P
    rows (``kernels.ops.multi_merge_scores``).  Returns ``(wd, h)`` of shape
    (P, s) with +inf WD at invalid slots.
    """
    m, kap = merge_coords(a_min[:, None], alpha_rows, kappa_rows)
    wd = (a_min[:, None] + alpha_rows) ** 2 * bilinear_lookup(wd_table, m, kap)
    h = bilinear_lookup(h_table, m, kap)
    return jnp.where(valid, wd, jnp.inf), h


def multi_merge_scores(alpha, kappa_rows, valid, a_min, h_table, wd_table):
    """Batched Lookup-WD scoring for P fixed partners sharing one alpha.

    alpha: (s,); kappa_rows, valid: (P, s); a_min: (P,); tables: (G, G).
    Returns ``(wd, h)`` of shape (P, s): per-pair weight degradation (+inf at
    invalid slots) and the merge coefficient from the h table.
    """
    alpha_rows = jnp.broadcast_to(alpha[None, :], kappa_rows.shape)
    return multi_merge_scores_rows(alpha_rows, kappa_rows, valid, a_min,
                                   h_table, wd_table)


def class_scores(x, sv_x, alpha, gamma):
    """Per-class decision scores, scored class-by-class (the serving oracle).

    x: (n, d); sv_x: (C, slots, d); alpha: (C, slots) with inactive slots
    already zeroed -> (C, n).  C sequential kernel calls — the semantics
    the fused ``ops.class_scores`` fold is tested against.
    """
    return jnp.stack([
        jnp.matmul(rbf_matrix(x, sv_x[c], gamma).astype(alpha.dtype),
                   alpha[c], precision=jax.lax.Precision.HIGHEST)
        for c in range(sv_x.shape[0])])


def multi_merge_scores_classes(alpha, kappa_rows, valid, a_min, h_table,
                               wd_table):
    """Class-batched oracle: alpha (C, s); kappa_rows, valid (C, P, s);
    a_min (C, P) -> (wd, h) of shape (C, P, s)."""
    return jax.vmap(multi_merge_scores, in_axes=(0, 0, 0, 0, None, None))(
        alpha, kappa_rows, valid, a_min, h_table, wd_table)


# Scores at/above this mean "no valid partner" (shared with core.budget;
# the Pallas scorers use a finite 3.4e38, real WDs are << 1e30 — both lose
# every argmin and both compare < NO_PARTNER identically).
NO_PARTNER = 1e30
# kappa values are clipped away from 0 before log (core.merge_math.KAPPA_MIN;
# duplicated so the kernels package stays import-clean).
_KAPPA_MIN = 1e-30

# The merge math below derives new cache rows and alphas from old ones, merge
# after merge, so an error in exp or log is inherited by every descendant.
# A TPU's fp32 log is off by up to ~4e-4 relative (exp by ~5e-6), which
# drifts the kernel cache by ~1e-2 over 1e5 merges; these two are built from
# adds, multiplies and exponent bit moves, so they are within a few ulp on
# every backend, inside Pallas kernels too.
_LN2_HI = 0.693145751953125          # ln 2 in 15 bits: n * _LN2_HI is exact
_LN2_LO = math.log(2.0) - _LN2_HI
_SQRT2 = math.sqrt(2.0)


def _on_vectors(fn):
    """Mosaic bitcasts vectors only: lift a scalar to (1, 1) and back."""
    def wrapped(x):
        x = jnp.asarray(x, jnp.float32)
        if x.ndim:
            return fn(x)
        return fn(jnp.broadcast_to(x, (1, 1)))[0, 0]
    return functools.wraps(fn)(wrapped)


@_on_vectors
def log_f32(x):
    """Natural log of positive, normal fp32 ``x`` to a few ulp.

    ``x = m 2^e`` with ``m`` in [sqrt(1/2), sqrt(2)], read off the bits;
    ``log m = 2 atanh(s)``, ``s = (m - 1) / (m + 1)``, ``|s| <= 0.172``, by
    its series to ``s^11``.
    """
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    m = jax.lax.bitcast_convert_type((bits & 0x007FFFFF) | 0x3F800000,
                                     jnp.float32)              # [1, 2)
    big = m > _SQRT2
    m = jnp.where(big, 0.5 * m, m)
    e = ((bits >> 23) - 127 + big.astype(jnp.int32)).astype(jnp.float32)
    f = m - 1.0
    s = f / (2.0 + f)
    z = s * s
    t = z * (1 / 3 + z * (1 / 5 + z * (1 / 7 + z * (1 / 9 + z * (1 / 11)))))
    return e * _LN2_HI + (e * _LN2_LO + (2.0 * s + 2.0 * s * t))


@_on_vectors
def exp_f32(y):
    """``e**y`` in fp32 to a few ulp, for ``y`` in [-87, 88] (clamped).

    ``y = n ln2 + r`` with ``|r| <= ln2 / 2``; ``e**r`` by its Taylor
    polynomial to ``r^7``, scaled by ``2^n`` written into the exponent bits.
    """
    y = jnp.clip(y, -87.0, 88.0)
    n = jnp.floor(y * (1.0 / math.log(2.0)) + 0.5)
    r = (y - n * _LN2_HI) - n * _LN2_LO
    p = 1.0 + r * (1.0 + r * (1 / 2 + r * (1 / 6 + r * (
        1 / 24 + r * (1 / 120 + r * (1 / 720 + r * (1 / 5040)))))))
    scale = jax.lax.bitcast_convert_type(
        (n.astype(jnp.int32) + 127) << 23, jnp.float32)
    return p * scale


def _safe_log(k):
    return log_f32(jnp.clip(k.astype(jnp.float32), _KAPPA_MIN, 1.0))


def _kappa_pow(kappa, expo):
    """kappa**expo as exp(expo log kappa) — core.merge_math.kappa_pow."""
    return exp_f32(expo * _safe_log(kappa))


def merge_event(sv_x, alpha, kmat, count, over, h_table, wd_table):
    """One fused maintenance-event round over stacked classes (the oracle for
    ``merge_event.merge_event_pallas`` and the production CPU path).

    Per class ``c`` with ``over[c]`` set, executes exactly the paper's Alg. 1
    event — the same decisions and fp formulas as one cached
    ``core.budget._merge_once`` call on that class's slice:

      1. fixed partner ``i_min`` = active argmin |alpha|;
      2. kappa row read from the class's kernel cache (never recomputed);
      3. all candidates scored by the Lookup-WD tables (bilinear lookup);
      4. the merge (or the removal fallback when no same-sign partner
         exists) applied as the shared masked two-row + two-column update,
         with the merged point's cache row derived in closed form from the
         two parent rows (the log-space combine of ``core.kernel_cache``);
      5. the freed slot compacted by moving the old ``last`` row in.

    sv_x: (C, s, d); alpha: (C, s); kmat: (C, s, s) fp32 cache; count, over:
    (C,).  Classes with ``over`` False are returned BITWISE untouched (all
    their scatters are redirected out of bounds and dropped).  Returns
    ``(sv_x, alpha, kmat)``; the caller owns ``count -= over``.
    """
    c, s = alpha.shape
    idx = jnp.arange(s)
    carange = jnp.arange(c)
    active = idx[None, :] < count[:, None]                        # (C, s)

    # 1. fixed partners: per-class active min-|alpha| slot.
    abs_a = jnp.where(active, jnp.abs(alpha), jnp.inf)
    i_min = jnp.argmin(abs_a, axis=1)                             # (C,)
    a_min = jnp.take_along_axis(alpha, i_min[:, None], 1)[:, 0]   # (C,)

    # 2. kappa rows from the cache — the engine never touches sv_x for them.
    kappa_row = jnp.take_along_axis(
        kmat, i_min[:, None, None], 1)[:, 0, :].astype(alpha.dtype)  # (C, s)

    # 3. Lookup-WD scoring, identical formulas to budget.candidate_scores.
    same_sign = alpha * a_min[:, None] > 0
    valid = active & same_sign & (idx[None, :] != i_min[:, None])
    m, kap = merge_coords(a_min[:, None], alpha, kappa_row)
    wd = (a_min[:, None] + alpha) ** 2 * bilinear_lookup(wd_table, m, kap)
    h = bilinear_lookup(h_table, m, kap)
    wd = jnp.where(valid, wd, jnp.inf)
    j_star = jnp.argmin(wd, axis=1)                               # (C,)
    has_partner = jnp.take_along_axis(wd, j_star[:, None], 1)[:, 0] < NO_PARTNER

    # 4. merge math on the chosen pairs (per-class scalars).
    last = count - 1
    lo = jnp.minimum(i_min, j_star)
    hi = jnp.maximum(i_min, j_star)
    h_m = jnp.take_along_axis(h, j_star[:, None], 1)[:, 0]
    k_ij = jnp.take_along_axis(kappa_row, j_star[:, None], 1)[:, 0]
    kap_m = jnp.clip(k_ij, 0.0, 1.0)
    a_j = jnp.take_along_axis(alpha, j_star[:, None], 1)[:, 0]
    a_last = jnp.take_along_axis(alpha, last[:, None] % s, 1)[:, 0]
    a_z = (a_min * _kappa_pow(kap_m, (1.0 - h_m) ** 2)
           + a_j * _kappa_pow(kap_m, h_m**2)).astype(alpha.dtype)
    gather_row = lambda a, i: jnp.take_along_axis(
        a, (i % s)[:, None, None], 1)[:, 0]
    x_i = gather_row(sv_x, i_min)
    x_j = gather_row(sv_x, j_star)
    v_last = gather_row(sv_x, last)
    z = h_m[:, None] * x_i.astype(jnp.float32) \
        + (1.0 - h_m[:, None]) * x_j.astype(jnp.float32)

    # Merged point's cache row from the two parent rows (kernel_cache's
    # log-space combine — the z-row derivation lives inside the event).
    row_j = gather_row(kmat, j_star)
    row_last = gather_row(kmat, last)
    lz = (h_m[:, None] * _safe_log(kappa_row)
          + (1.0 - h_m[:, None]) * _safe_log(row_j)
          - (h_m * (1.0 - h_m))[:, None] * _safe_log(k_ij)[:, None])
    z_row = exp_f32(jnp.minimum(lz, 0.0)).astype(kmat.dtype)

    # 5. masked two-row + two-column update (budget._merge_once's fused
    # branch-free form, batched over classes): slot t1 <- z row (or, on the
    # removal fallback, the old ``last``); slot t2 <- the old ``last``;
    # non-executing classes scatter out of bounds and drop.
    col = idx[None, :]
    z_row_l = jnp.take_along_axis(z_row, (last % s)[:, None], 1)[:, 0]
    r_merge = jnp.where(col == hi[:, None], z_row_l[:, None], z_row)
    r_merge = jnp.where(col == lo[:, None], 1.0, r_merge)
    r_move = jnp.where(col == hi[:, None], 1.0, row_last)
    r_move = jnp.where(col == lo[:, None], z_row_l[:, None], r_move)
    r_remove = jnp.where(col == i_min[:, None], 1.0, row_last)
    t1 = jnp.where(has_partner, lo, i_min)
    t2 = jnp.where(has_partner, hi, s)          # OOB on removal -> dropped
    t1 = jnp.where(over, t1, s)                 # OOB when not over -> no-op
    t2 = jnp.where(over, t2, s)
    tt = jnp.stack([t1, t2], axis=1)                              # (C, 2)
    rows = jnp.stack([jnp.where(has_partner[:, None], r_merge, r_remove),
                      r_move], axis=1).astype(kmat.dtype)         # (C, 2, s)
    kmat = kmat.at[carange[:, None], tt, :].set(rows, mode="drop")
    kmat = kmat.at[carange[:, None], :, tt].set(rows, mode="drop")

    sv1 = jnp.where(has_partner[:, None], z.astype(sv_x.dtype), v_last)
    sv_x = sv_x.at[carange[:, None], tt, :].set(
        jnp.stack([sv1, v_last], axis=1), mode="drop")
    a1 = jnp.where(has_partner, a_z, a_last)
    alpha = alpha.at[carange[:, None], tt].set(
        jnp.stack([a1, a_last], axis=1).astype(alpha.dtype), mode="drop")
    last_t = jnp.where(over, last, s)
    alpha = alpha.at[carange, last_t].set(0.0, mode="drop")
    return sv_x, alpha, kmat


def _multi_merge_event_one(sv_x, alpha, kmat, count, h_table, wd_table, *,
                           budget: int, merge_batch: int):
    """One single-class multi-merge event off the kernel cache (the oracle's
    standalone re-statement of ``core.budget._multi_merge_once`` +
    ``core.kernel_cache.apply_multi_merge`` — the kernels package cannot
    import core, so the formulas are restated here and the engine tests pin
    the two paths against each other).

    sv_x: (s, d); alpha: (s,); kmat: (s, s) fp32 cache (REQUIRED — kappa
    rows are read, never recomputed); count: () int32.  Up to ``merge_batch``
    disjoint same-sign pairs merge in one fused scatter (greedy in |alpha|
    order, Lookup-WD scored, removal fallback per pair), then targeted-move
    compaction.  Returns ``(sv_x, alpha, kmat, new_count)``.
    """
    slots = alpha.shape[0]
    p = merge_batch
    idx = jnp.arange(slots)
    active = idx < count

    # 1. fixed partners: the P smallest-|alpha| active SVs, cheapest first.
    abs_a = jnp.where(active, jnp.abs(alpha), jnp.inf)
    _, a_idx = jax.lax.top_k(-abs_a, p)                    # (P,) |alpha| asc
    a_min = alpha[a_idx]

    # 2. kappa rows straight from the cache.
    kappa_rows = kmat[a_idx].astype(alpha.dtype)

    # 3. Lookup-WD scoring; a pair may merge with another pair's fixed slot,
    #    only its own slot is excluded.
    same_sign = a_min[:, None] * alpha[None, :] > 0
    self_mask = jnp.zeros((p, slots), bool).at[jnp.arange(p), a_idx].set(True)
    valid = active[None, :] & same_sign & ~self_mask
    wd, h = multi_merge_scores(alpha, kappa_rows, valid, a_min,
                               h_table, wd_table)

    # 4. greedy disjoint pair choice in |alpha| order (static unroll).
    excess = count - budget
    taken = jnp.zeros((slots,), bool)
    consumed = jnp.zeros((p,), bool)
    n_exec = jnp.int32(0)
    b_list, merged_list, exec_list = [], [], []
    for q in range(p):
        wd_q = jnp.where(taken, jnp.inf, wd[q])
        j_q = jnp.argmin(wd_q)
        exec_q = ~consumed[q] & (n_exec < excess)
        merged_q = exec_q & (wd_q[j_q] < NO_PARTNER)
        b_list.append(j_q)
        merged_list.append(merged_q)
        exec_list.append(exec_q)
        taken = taken | ((idx == j_q) & merged_q) | ((idx == a_idx[q]) & exec_q)
        consumed = consumed | ((a_idx == j_q) & merged_q)
        n_exec = n_exec + exec_q.astype(jnp.int32)
    b_idx = jnp.stack(b_list)
    merged = jnp.stack(merged_list)
    execute = jnp.stack(exec_list)

    # 5. merge math + one fused scatter (z_q overwrites a_q; b_q — or a_q on
    #    the removal fallback — becomes a hole).
    h_star = h[jnp.arange(p), b_idx]
    kap = jnp.clip(kappa_rows[jnp.arange(p), b_idx], 0.0, 1.0)
    a_z = (a_min * _kappa_pow(kap, (1.0 - h_star) ** 2)
           + alpha[b_idx] * _kappa_pow(kap, h_star**2))
    z = h_star[:, None] * sv_x[a_idx] + (1.0 - h_star[:, None]) * sv_x[b_idx]
    write_idx = jnp.where(merged, a_idx, slots)            # OOB -> dropped
    hole_idx = jnp.where(merged, b_idx,
                         jnp.where(execute, a_idx, slots))

    # cache update (kernel_cache.apply_multi_merge's formulas): the P new z
    # rows/columns in log space plus the (P, P) cross block, symmetrized.
    lk = _safe_log(kmat[jnp.concatenate([a_idx, b_idx])])
    lk_a, lk_b = lk[:p], lk[p:]
    lk_ab = lk_a[jnp.arange(p), b_idx]
    hc = h_star[:, None]
    lz = jnp.minimum(hc * lk_a + (1.0 - hc) * lk_b
                     - (h_star * (1.0 - h_star))[:, None] * lk_ab[:, None],
                     0.0)
    z_rows = exp_f32(lz).astype(kmat.dtype)
    hr = h_star[None, :]
    cross = exp_f32(jnp.minimum(
        hr * lz[:, a_idx] + (1.0 - hr) * lz[:, b_idx]
        - (h_star * (1.0 - h_star))[None, :] * lk_ab[None, :], 0.0))
    cross = 0.5 * (cross + cross.T)
    cross = jnp.where(jnp.eye(p, dtype=bool), 1.0, cross).astype(kmat.dtype)
    kmat = kmat.at[write_idx, :].set(z_rows, mode="drop")
    kmat = kmat.at[:, write_idx].set(z_rows.T, mode="drop")
    kmat = kmat.at[write_idx[:, None], write_idx[None, :]].set(cross,
                                                              mode="drop")
    sv_x = sv_x.at[write_idx].set(z.astype(sv_x.dtype), mode="drop")
    alpha = alpha.at[write_idx].set(a_z.astype(alpha.dtype), mode="drop")

    # 6. targeted-move compaction: k-th hole below the new watermark takes
    #    the k-th surviving slot above it.
    hole_mask = jnp.zeros((slots,), bool).at[hole_idx].set(True, mode="drop")
    new_count = count - n_exec
    front_hole = hole_mask & (idx < new_count)
    tail_surv = active & ~hole_mask & (idx >= new_count)
    dst = jnp.sort(jnp.where(front_hole, idx, slots))[:p]     # OOB-padded
    src = jnp.sort(jnp.where(tail_surv, idx, slots))[:p]
    src_c = jnp.minimum(src, slots - 1)
    rows = kmat[src_c]
    kmat = kmat.at[dst, :].set(rows, mode="drop")
    kmat = kmat.at[:, dst].set(rows.T, mode="drop")
    kmat = kmat.at[dst[:, None], dst[None, :]].set(rows[:, src_c],
                                                   mode="drop")
    sv_x = sv_x.at[dst].set(sv_x[src_c], mode="drop")
    alpha = alpha.at[dst].set(alpha[src_c], mode="drop")
    alpha = jnp.where(idx < new_count, alpha, 0.0)
    return sv_x, alpha, kmat, new_count


def multi_merge_event(sv_x, alpha, kmat, count, over, h_table, wd_table, *,
                      budget: int, merge_batch: int):
    """One fused multi-merge maintenance round over stacked classes.

    The multi-merge counterpart of ``merge_event``: per class with ``over``
    set, up to ``merge_batch`` disjoint same-sign pairs retire in one event
    (greedy in |alpha| order, Lookup-WD scored off the resident cache);
    classes with ``over`` clear return bitwise untouched.  Returns
    ``(sv_x, alpha, kmat, count)`` — unlike ``merge_event`` the new count is
    returned (an event retires a data-dependent number of pairs).
    """
    new = jax.vmap(lambda sv, al, km, c: _multi_merge_event_one(
        sv, al, km, c, h_table, wd_table, budget=budget,
        merge_batch=merge_batch))(sv_x, alpha, kmat, count)
    ov = over.astype(bool)

    def mask(n, o):
        return jnp.where(ov.reshape((-1,) + (1,) * (n.ndim - 1)), n, o)

    return (mask(new[0], sv_x), mask(new[1], alpha), mask(new[2], kmat),
            jnp.where(ov, new[3], count))


def train_step_fused(sv_x, alpha, kmat, count, step, n_inserts, n_merges,
                     xb, yb, k_bb, h_table, wd_table, *, budget: int,
                     lambda_: float, gamma: float, batch_size: int,
                     maintenance: str = "merge", merge_batch: int = 4,
                     unroll: int = 0):
    """Whole fused multiclass train step: margin + insert + event rounds
    (the oracle for ``train_step.train_step_pallas`` AND the production CPU
    path behind ``ops.train_step``).

    Executes, for every class at once, exactly what the composed engine does
    in three phase launches:

      1. the RBF margin rows ``k(xb, sv_c)`` from ONE flattened kernel call
         (identical fp path to ``core.multiclass.class_kernel_rows``);
      2. the Pegasos shrink + masked violator insert, reusing the margin
         rows as the new cache rows/columns (``bsgd.insert_from_rows`` +
         ``kernel_cache.insert_rows`` semantics, vmapped);
      3. masked maintenance event rounds until no class is over budget —
         ``merge_event`` rounds for ``maintenance="merge"``,
         ``multi_merge_event`` rounds for ``"multi-merge"`` (``unroll > 0``
         inlines that many masked rounds instead of the while loop, same
         contract as ``core.budget.run_maintenance``).

    sv_x: (C, s, d); alpha: (C, s); kmat: (C, s, s) fp32 (REQUIRED); count /
    step / n_inserts / n_merges: (C,) int32; xb: (batch, d); yb: (C, batch)
    one-vs-rest targets in {-1, +1}; k_bb: (batch, batch) = k(xb, xb).
    Returns the updated ``(sv_x, alpha, kmat, count, step, n_inserts,
    n_merges)``.
    """
    c, s, d = sv_x.shape
    slots = s
    k = rbf_matrix(xb, sv_x.reshape(c * s, d), gamma)
    k_b = jnp.moveaxis(k.reshape(xb.shape[0], c, s), 1, 0)    # (C, batch, s)

    def insert_one(sv, al, km, cnt, t, nin, yc, kb):
        eta = 1.0 / (lambda_ * t)
        active = jnp.arange(slots) < cnt
        f = jnp.matmul(kb.astype(al.dtype), jnp.where(active, al, 0.0),
                       precision=jax.lax.Precision.HIGHEST)
        margin = yc * f
        al = al * (1.0 - eta * lambda_)
        viol = margin < 1.0
        pos = cnt + jnp.cumsum(viol.astype(jnp.int32)) - 1
        tgt = jnp.where(viol, pos, slots)                 # OOB -> dropped
        sv = sv.at[tgt].set(xb.astype(sv.dtype), mode="drop")
        new_alpha = (eta * yc / batch_size).astype(al.dtype)
        al = al.at[tgt].set(new_alpha, mode="drop")
        n_new = jnp.sum(viol).astype(jnp.int32)
        # cache insert: the margin rows double as the new rows/columns, with
        # the new-vs-new block patched in at the inserted slots
        rows = kb.astype(km.dtype).at[:, tgt].set(k_bb.astype(km.dtype),
                                                  mode="drop")
        km = km.at[tgt, :].set(rows, mode="drop")
        km = km.at[:, tgt].set(rows.T, mode="drop")
        km = km.at[tgt, tgt].set(1.0, mode="drop")
        return sv, al, km, cnt + n_new, t + 1, nin + n_new

    sv_x, alpha, kmat, count, step, n_inserts = jax.vmap(insert_one)(
        sv_x, alpha, kmat, count, step, n_inserts, yb, k_b)

    if maintenance == "merge":
        def round_(carry):
            sv, al, km, cnt, n = carry
            ov = cnt > budget
            sv, al, km = merge_event(sv, al, km, cnt, ov, h_table, wd_table)
            return (sv, al, km, cnt - ov.astype(cnt.dtype),
                    n + ov.astype(n.dtype))
    else:
        def round_(carry):
            sv, al, km, cnt, n = carry
            ov = cnt > budget
            sv, al, km, cnt = multi_merge_event(
                sv, al, km, cnt, ov, h_table, wd_table, budget=budget,
                merge_batch=merge_batch)
            return sv, al, km, cnt, n + ov.astype(n.dtype)

    carry = (sv_x, alpha, kmat, count, n_merges)
    if unroll:
        for _ in range(unroll):
            carry = round_(carry)
    else:
        carry = jax.lax.while_loop(lambda cr: jnp.any(cr[3] > budget),
                                   round_, carry)
    sv_x, alpha, kmat, count, n_merges = carry
    return sv_x, alpha, kmat, count, step, n_inserts, n_merges


def gss(m, kappa, n_iters: int):
    """Vectorized golden section search maximizing the merge objective.

    Mirrors ``repro.core.merge_math.golden_section_search`` but parameterized
    by iteration count (the kernel's static parameter).
    """
    invphi = (5.0**0.5 - 1.0) / 2.0
    m = jnp.asarray(m, jnp.float32)
    kappa = jnp.clip(jnp.asarray(kappa, jnp.float32), 1e-30, 1.0)
    lk = jnp.log(kappa)

    def s(h):
        return m * jnp.exp((1.0 - h) ** 2 * lk) + (1.0 - m) * jnp.exp(h**2 * lk)

    a = jnp.zeros_like(m)
    b = jnp.ones_like(m)

    def body(_, ab):
        a, b = ab
        span = b - a
        c = b - span * invphi
        d = a + span * invphi
        go_left = s(c) > s(d)
        return jnp.where(go_left, a, c), jnp.where(go_left, d, b)

    a, b = jax.lax.fori_loop(0, n_iters, body, (a, b))
    return 0.5 * (a + b)
