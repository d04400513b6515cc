"""Fused multiclass train-step megakernel — one launch chain per minibatch.

A composed multiclass train step is three separately-launched phases: the
fused-rbf margin block, the vmapped shrink+insert, and the maintenance event
rounds (``merge_event``).  Each phase boundary re-streams the stacked SV bank
and ``(C, S, S)`` kernel cache through HBM.  This kernel folds all three onto
``merge_event``'s class grid and runs the WHOLE step per class block without
leaving VMEM:

  1. **margin** — the class's RBF margin rows ``k(xb, sv_c)`` from the
     resident ``(S, D)`` SV block (``rbf_matrix``'s matmul decomposition,
     in-kernel, MXU);
  2. **insert** — Pegasos shrink + insert of violating rows, with the
     margin rows reused as the new cache rows/columns — the I1-I4 cache
     invariants are maintained in VMEM (no host round-trip, no HBM gather);
  3. **events** — up to ``rounds`` maintenance event rounds chained on the
     same resident blocks in a loop: single-pair rounds run
     ``merge_event.merge_event_refs`` verbatim; multi-merge rounds retire up
     to P disjoint same-sign pairs per round (top-P smallest |alpha| fixed
     partners, Lookup-WD scored against the VMEM-resident tables, greedy
     disjoint choice, fused z-row writes + targeted-move compaction — the
     in-kernel restatement of ``core.budget._multi_merge_once``).

Classes at or under budget skip the event rounds, so a static ``rounds =
batch_size`` always suffices (one minibatch bounds the excess by
``batch_size`` and every round retires >= 1 SV per over class).  Class
blocks are double-buffered through the grid by the Pallas pipeline; outputs
alias inputs so the whole stacked state updates in place.

In-place idioms as in ``merge_event``: the body copies each class's blocks
into its output refs and updates rows and columns there through aligned
tiles (``get_row``/``set_row``/``set_col``/``set_elem``); scalars come from
one-hot reductions, and the inclusive cumsum from a triangular ones matmul.
Oracle and production CPU path: ``ref.train_step_fused``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .merge_event import (_first_where, _pick, fused_compiler_params,
                          get_row, lookup_chunks, merge_event_refs,
                          merge_event_vmem, set_col, set_elem, set_row)
from .merge_lookup import HIGHEST, WD_INVALID
from .ref import NO_PARTNER, _safe_log, exp_f32


def _insert_refs(count, t, nins, yb, xb, kbb, alpha_ref, sv_ref, km_ref, *,
                 lambda_: float, gamma: float, batch_size: int):
    """Margin + shrink + violator insert, in place on one class's blocks.

    count/t/nins: () int32; yb: (1, B) one-vs-rest targets; xb: (B, D)
    minibatch (rows >= batch_size are zero padding); kbb: (B, B) =
    ``k(xb, xb)``; alpha_ref: (1, S) storage dtype; sv_ref: (S, D); km_ref:
    (S, S) fp32.  Returns ``(count, nins)`` with exactly
    ``bsgd.insert_from_rows`` + ``kernel_cache.insert_rows`` semantics.
    """
    alpha = alpha_ref[...].astype(jnp.float32)                # (1, S)
    s = alpha.shape[1]
    b = xb.shape[0]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    bcol = jax.lax.broadcasted_iota(jnp.int32, (b, 1), 0)
    sv_f = sv_ref[...].astype(jnp.float32)
    xb_f = xb.astype(jnp.float32)
    y_col = yb.astype(jnp.float32).reshape(b, 1)

    # 1. margin rows k(xb, sv) — rbf_matrix's matmul decomposition, in-kernel
    xn = jnp.sum(xb_f * xb_f, axis=1, keepdims=True)          # (B, 1)
    yn = jnp.sum(sv_f * sv_f, axis=1, keepdims=True)          # (S, 1)
    prod = jax.lax.dot_general(xb_f, sv_f, (((1,), (1,)), ((), ())),
                               precision=HIGHEST,
                               preferred_element_type=jnp.float32)
    k_b = jnp.exp(-gamma * jnp.maximum(xn + yn.T - 2.0 * prod, 0.0))

    active = iota < count
    f = jax.lax.dot_general(k_b, jnp.where(active, alpha, 0.0),
                            (((1,), (1,)), ((), ())), precision=HIGHEST,
                            preferred_element_type=jnp.float32)  # (B, 1)
    margin = y_col * f

    # 2. Pegasos shrink + watermark insert of the violating rows.  Padding
    #    rows (>= batch_size) never violate; the inclusive cumsum over the
    #    violation mask is a lower-triangular ones matmul.
    eta = 1.0 / (lambda_ * t.astype(jnp.float32))
    alpha = alpha * (1.0 - eta * lambda_)
    viol = (margin < 1.0) & (bcol < batch_size)               # (B, 1)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (b, b), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (b, b), 1))
    csum = jax.lax.dot_general(tri.astype(jnp.float32),
                               viol.astype(jnp.float32),
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    idx = jnp.where(viol, count + csum.astype(jnp.int32) - 1, s)  # S = drop
    sel = (idx == iota).astype(jnp.float32)                   # (B, S)
    written = jnp.sum(sel, axis=0, keepdims=True) > 0.0       # (1, S)

    # 3. cache insert (kernel_cache.insert_rows): the margin rows ARE the new
    #    rows/columns, with the new-vs-new block patched in at the inserted
    #    slots; rows -> columns -> diagonal so column values win at
    #    intersections, exactly like the scatter form.
    repl = jax.lax.dot_general(kbb.astype(jnp.float32), sel,
                               (((1,), (0,)), ((), ())), precision=HIGHEST,
                               preferred_element_type=jnp.float32)   # (B, S)
    rows = jnp.where(written, repl, k_b)
    new_a = eta * y_col / batch_size                          # (B, 1)
    slot = [jnp.sum(jnp.where(bcol == i, idx, 0)) for i in range(batch_size)]
    for i in range(batch_size):
        set_row(km_ref, slot[i], rows[i:i + 1])
        set_row(sv_ref, slot[i], xb_f[i:i + 1])
        alpha = jnp.where(iota == slot[i], jnp.sum(new_a[i:i + 1]), alpha)
    for i in range(batch_size):
        set_col(km_ref, slot[i], rows[i:i + 1].reshape(s, 1))
    for i in range(batch_size):
        set_elem(km_ref, slot[i], slot[i], 1.0)
    alpha_ref[...] = alpha.astype(alpha_ref.dtype)

    n_new = jnp.sum(viol.astype(jnp.int32))
    return count + n_new, nins + n_new


def _multi_merge_refs(count, alpha_ref, sv_ref, km_ref, h_tab, wd_tab, *,
                      budget: int, p: int, g: int, block_s: int):
    """One multi-merge event, in place on one (over-budget) class's blocks.

    The in-kernel restatement of ``core.budget._multi_merge_once`` +
    ``kernel_cache.apply_multi_merge`` (oracle: ``ref.multi_merge_event``):
    up to ``p`` disjoint same-sign pairs merge in one fused pass, then the
    targeted-move compaction repairs the watermark.  P is small and static,
    so the per-pair work unrolls.  Returns the new count.
    """
    alpha = alpha_ref[...].astype(jnp.float32)                # (1, S)
    s = alpha.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1)
    active = iota < count
    false = count < 0                                          # scalar False

    # 1. P fixed partners: |alpha| ascending, first index on ties (the
    #    iterative masked-min extraction matches lax.top_k's tie order).
    rem = jnp.where(active, jnp.abs(alpha), jnp.inf)
    a_idx, a_min = [], []
    for _ in range(p):
        iq = _first_where(rem == jnp.min(rem), iota, s)
        a_idx.append(iq)
        a_min.append(_pick(alpha, iota, iq))
        rem = jnp.where(iota == iq, jnp.inf, rem)

    # 2. kappa rows straight from the resident cache.
    kappa_rows = [get_row(km_ref, a) for a in a_idx]

    # 3. Lookup-WD scoring per pair row (merge_lookup's gather-free
    #    hat-basis bilinear against the resident tables).
    wd_rows, h_rows = [], []
    for q in range(p):
        valid_q = active & (alpha * a_min[q] > 0) & (iota != a_idx[q])
        wd_q, h_q = lookup_chunks(alpha, kappa_rows[q], a_min[q], h_tab,
                                  wd_tab, g=g, block_s=block_s)
        wd_rows.append(jnp.where(valid_q, wd_q, WD_INVALID))
        h_rows.append(h_q)

    # 4. greedy disjoint pair choice in |alpha| order (budget's loop).
    excess = count - budget
    taken = iota < 0                                           # all-False
    consumed = [false] * p
    n_exec = jnp.int32(0)
    b_idx, merged, execute = [], [], []
    for q in range(p):
        wd_q = jnp.where(taken, WD_INVALID, wd_rows[q])
        mnq = jnp.min(wd_q)
        j_q = _first_where(wd_q == mnq, iota, s)
        exec_q = ~consumed[q] & (n_exec < excess)
        merged_q = exec_q & (mnq < NO_PARTNER)
        b_idx.append(j_q)
        merged.append(merged_q)
        execute.append(exec_q)
        taken = taken | ((iota == j_q) & merged_q) | \
            ((iota == a_idx[q]) & exec_q)
        for r in range(q + 1, p):
            consumed[r] = consumed[r] | ((a_idx[r] == j_q) & merged_q)
        n_exec = n_exec + exec_q.astype(jnp.int32)

    # 5. merge math + fused cache/sv/alpha writes.  All reads happen before
    #    any write.
    rows_b = [get_row(km_ref, j) for j in b_idx]
    x_a = [get_row(sv_ref, a) for a in a_idx]
    x_b = [get_row(sv_ref, j) for j in b_idx]
    h_star, lk_ab, az, z_pts, lz_rows, write_i, hole_i = \
        [], [], [], [], [], [], []
    for q in range(p):
        hq = _pick(h_rows[q], iota, b_idx[q])
        k_ab = _pick(kappa_rows[q], iota, b_idx[q])
        a_b = _pick(alpha, iota, b_idx[q])
        lkq = _safe_log(jnp.clip(k_ab, 0.0, 1.0))
        az.append(a_min[q] * exp_f32((1.0 - hq) ** 2 * lkq)
                  + a_b * exp_f32(hq**2 * lkq))
        z_pts.append(hq * x_a[q] + (1.0 - hq) * x_b[q])
        # the z row's log-space combine (kernel_cache's identity)
        lz_rows.append(jnp.minimum(
            hq * _safe_log(kappa_rows[q]) + (1.0 - hq) * _safe_log(rows_b[q])
            - hq * (1.0 - hq) * lkq, 0.0))
        h_star.append(hq)
        lk_ab.append(lkq)
        write_i.append(jnp.where(merged[q], a_idx[q], s))
        hole_i.append(jnp.where(merged[q], b_idx[q],
                                jnp.where(execute[q], a_idx[q], s)))

    # (P, P) cross block k(z_i, z_j): the merge identity applied a second
    # time, to the z rows; symmetrized, diagonal pinned (I2/I3).
    cross = [[exp_f32(jnp.minimum(
        h_star[j] * _pick(lz_rows[i], iota, a_idx[j])
        + (1.0 - h_star[j]) * _pick(lz_rows[i], iota, b_idx[j])
        - h_star[j] * (1.0 - h_star[j]) * lk_ab[j], 0.0))
        for j in range(p)] for i in range(p)]

    z_rows = [exp_f32(lz) for lz in lz_rows]
    for q in range(p):                       # z rows, then columns, then the
        set_row(km_ref, write_i[q], z_rows[q])     # cross block: scatter order
    for q in range(p):
        set_col(km_ref, write_i[q], z_rows[q].reshape(s, 1))
    for i in range(p):
        for j in range(p):
            c_ij = 1.0 if i == j else 0.5 * (cross[i][j] + cross[j][i])
            set_elem(km_ref, write_i[i], write_i[j], c_ij)
    al = alpha
    for q in range(p):
        set_row(sv_ref, write_i[q], z_pts[q])
        al = jnp.where(iota == write_i[q], az[q], al)

    # 6. targeted-move compaction: the k-th hole below the new watermark
    #    takes the k-th surviving slot above it (budget's dst/src pairing,
    #    the sorts replaced by iterative masked-min extraction).
    hole_mask = iota < 0
    for q in range(p):
        hole_mask = hole_mask | (iota == hole_i[q])
    new_count = count - n_exec
    front_hole = hole_mask & (iota < new_count)
    tail_surv = active & ~hole_mask & (iota >= new_count)
    dst, src = [], []
    rem_d = jnp.where(front_hole, iota, s)
    rem_s = jnp.where(tail_surv, iota, s)
    for _ in range(p):
        dq = jnp.min(rem_d)
        sq = jnp.min(rem_s)
        dst.append(dq)
        src.append(jnp.minimum(sq, s - 1))
        rem_d = jnp.where(iota == dq, s, rem_d)
        rem_s = jnp.where(iota == sq, s, rem_s)

    mrows = [get_row(km_ref, sq) for sq in src]
    msv = [get_row(sv_ref, sq) for sq in src]
    mal = [_pick(al, iota, sq) for sq in src]
    for q in range(p):
        set_row(km_ref, dst[q], mrows[q])
    for q in range(p):
        set_col(km_ref, dst[q], mrows[q].reshape(s, 1))
    for i in range(p):
        for j in range(p):
            set_elem(km_ref, dst[i], dst[j], _pick(mrows[i], iota, src[j]))
    for q in range(p):
        set_row(sv_ref, dst[q], msv[q])
        al = jnp.where(iota == dst[q], mal[q], al)
    al = jnp.where(iota < new_count, al, 0.0)
    alpha_ref[...] = al.astype(alpha_ref.dtype)
    return new_count


def _train_step_kernel(count_ref, step_ref, nins_ref, nmrg_ref, yb_ref,
                       xb_ref, kbb_ref, alpha_ref, sv_ref, kmat_ref,
                       h_tab_ref, wd_tab_ref, alpha_out, sv_out, kmat_out,
                       count_out, nins_out, nmrg_out, *, budget: int,
                       lambda_: float, gamma: float, batch_size: int,
                       rounds: int, maintenance: str, merge_batch: int,
                       g: int, block_s: int):
    c = pl.program_id(0)
    alpha_out[...] = alpha_ref[...]
    sv_out[...] = sv_ref[...]
    kmat_out[...] = kmat_ref[...]

    cnt, nins = _insert_refs(
        count_ref[c], step_ref[c], nins_ref[c], yb_ref[...], xb_ref[...],
        kbb_ref[...], alpha_out, sv_out, kmat_out, lambda_=lambda_,
        gamma=gamma, batch_size=batch_size)

    def round_(_, carry):
        cnt, nmrg = carry
        over = cnt > budget
        if maintenance == "merge":
            @pl.when(over)
            def _():
                merge_event_refs(cnt, alpha_out, sv_out, kmat_out,
                                 h_tab_ref[...], wd_tab_ref[...], g=g,
                                 block_s=block_s)
            new = cnt - over.astype(jnp.int32)
        else:                                  # multi-merge
            new = jax.lax.cond(
                over,
                lambda: _multi_merge_refs(
                    cnt, alpha_out, sv_out, kmat_out, h_tab_ref[...],
                    wd_tab_ref[...], budget=budget, p=merge_batch, g=g,
                    block_s=block_s),
                lambda: cnt)
        return new, nmrg + over.astype(jnp.int32)

    cnt, nmrg = jax.lax.fori_loop(0, rounds, round_, (cnt, nmrg_ref[c]))
    count_out[c] = cnt
    nins_out[c] = nins
    nmrg_out[c] = nmrg


@functools.partial(jax.jit, static_argnames=(
    "budget", "lambda_", "gamma", "batch_size", "rounds", "maintenance",
    "merge_batch", "block_s", "interpret"))
def train_step_pallas(sv_x, alpha, kmat, count, step, n_inserts, n_merges,
                      xb, yb, k_bb, h_table, wd_table, *, budget: int,
                      lambda_: float, gamma: float, batch_size: int,
                      rounds: int, maintenance: str = "merge",
                      merge_batch: int = 4, block_s: int = 256,
                      interpret: bool = False):
    """One fused train step for every class, one launch chain.

    sv_x: (C, S, D); alpha: (C, 1, S); kmat: (C, S, S) fp32; count / step /
    n_inserts / n_merges: (C,) int32, prefetched into SMEM; xb: (B, D)
    minibatch shared across the grid (rows >= ``batch_size`` are padding);
    yb: (C, 1, B) one-vs-rest targets; k_bb: (B, B) = k(xb, xb); tables:
    (G, G).  S, D and B must be multiples of the tile sizes
    (``ops.train_step`` pads).  Returns ``(sv_x, alpha, kmat, count,
    n_inserts, n_merges)`` with the counters as (C,) int32 — the caller owns
    ``step + 1``.  The bank, alpha and cache outputs alias their inputs so
    the stacked state updates in place; class blocks are double-buffered
    through the grid.  Oracle: ``ref.train_step_fused``.
    """
    c, s, d = sv_x.shape
    b = xb.shape[0]
    g = h_table.shape[0]
    bs = block_s if s % block_s == 0 else (128 if s % 128 == 0 else s)
    block_bytes, temp_bytes = merge_event_vmem(s, d, g, sv_x.dtype.itemsize)
    block_bytes += 4 * (b * d + b * b + 8 * b)
    temp_bytes += 4 * (s * d + 4 * b * s)      # the margin block's values
    row = lambda n: pl.BlockSpec((None, 1, n), lambda i, *_: (i, 0, 0))
    whole = lambda *shape: pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))
    per_class = lambda n: pl.BlockSpec((None, s, n), lambda i, *_: (i, 0, 0))
    counter = pl.BlockSpec(memory_space=pltpu.SMEM)
    counter_shape = jax.ShapeDtypeStruct((c,), jnp.int32)
    alpha_new, sv_new, kmat_new, count_new, nins_new, nmrg_new = pl.pallas_call(
        functools.partial(_train_step_kernel, budget=budget, lambda_=lambda_,
                          gamma=gamma, batch_size=batch_size, rounds=rounds,
                          maintenance=maintenance, merge_batch=merge_batch,
                          g=g, block_s=bs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,        # count, step, n_inserts, n_merges
            grid=(c,),
            in_specs=[
                row(b),                                    # yb
                whole(b, d),                               # xb: shared
                whole(b, b),                               # k_bb: shared
                row(s),                                    # alpha
                per_class(d),                              # sv_x
                per_class(s),                              # kmat
                whole(g, g), whole(g, g),                  # tables
            ],
            out_specs=[row(s), per_class(d), per_class(s),
                       counter, counter, counter]),
        out_shape=[
            jax.ShapeDtypeStruct((c, 1, s), alpha.dtype),
            jax.ShapeDtypeStruct((c, s, d), sv_x.dtype),
            jax.ShapeDtypeStruct((c, s, s), jnp.float32),
            counter_shape, counter_shape, counter_shape,
        ],
        input_output_aliases={7: 0, 8: 1, 9: 2},
        compiler_params=fused_compiler_params(
            "train_step", block_bytes=block_bytes, temp_bytes=temp_bytes),
        interpret=interpret,
    )(count.astype(jnp.int32), step.astype(jnp.int32),
      n_inserts.astype(jnp.int32), n_merges.astype(jnp.int32), yb, xb,
      k_bb, alpha, sv_x, kmat.astype(jnp.float32),
      h_table.astype(jnp.float32), wd_table.astype(jnp.float32))
    return sv_new, alpha_new, kmat_new, count_new, nins_new, nmrg_new
