"""Persistent SV-SV kernel cache: incremental kappa rows for budget maintenance.

The budget-maintenance hot spot is the kappa row ``k(x_min, .)`` against every
SV (``O(slots * dim)`` distances + exp per event, recomputed from scratch).
This module maintains a ``(slots, slots)`` symmetric kernel matrix ``kmat``
inside ``SVMState`` so maintenance *reads* its kappa row instead:

  * **insert** — reuses the ``k(xb, sv)`` rows ``train_step`` already computed
    for the margins (zero extra kernel evaluations against the SV set; only
    the tiny ``(batch, batch)`` block among the inserted points is new);
  * **merge**  — the merged point ``z = h x_a + (1-h) x_b`` gets its row in
    closed form from cached values.  For the Gaussian kernel,

        ||z - c||^2 = h ||x_a - c||^2 + (1-h) ||x_b - c||^2
                      - h (1-h) ||x_a - x_b||^2

    so ``log k(z, c) = h log k(x_a, c) + (1-h) log k(x_b, c)
    - h (1-h) log k(x_a, x_b)`` — an ``O(slots)`` log/exp combine of two
    cached rows, **independent of dim** (vs ``O(slots * dim)`` for a direct
    recompute);
  * **removal / compaction** — pure row/column moves, no kernel math at all.

Invariants (see DESIGN.md §4):

  I1. for all ``i, j < count``:  ``kmat[i, j] == k(sv_x[i], sv_x[j])`` up to
      fp tolerance (inserts come from the matmul-decomposition ``rbf_matrix``,
      merge rows from the log-space combine; both agree to ~1e-6 in fp32).
      Merge rows descend from merge rows, so the combine takes its exp and
      log from ``kernels.ref.exp_f32`` / ``log_f32``: with a TPU's own fp32
      log the cache drifts by ~1e-2 over 1e5 merges;
  I2. ``kmat`` is exactly symmetric (every update writes row and column from
      the same values);
  I3. ``kmat[i, i] == 1`` for ``i < count`` (set explicitly, never derived);
  I4. entries with ``i >= count`` or ``j >= count`` are arbitrary stale
      values — every consumer masks by ``count``, exactly like ``sv_x``.

The cache is always fp32 regardless of ``sv_dtype`` (it is ``slots^2 * 4``
bytes — 4 MB at a 1k budget, ~1 GiB per class at 16k, so size it into the
HBM plan at production budgets — and fp32 keeps merge decisions stable when
SV rows are stored in bf16).

The fused maintenance-event engine (``kernels/merge_event.py``, DESIGN.md
§11) inlines the merge rule: the z-row log-space combine below is derived
*inside* the kernel from the two parent rows resident in VMEM, so the
per-event cache update never round-trips through this module on that path —
``z_row_from_rows`` stays the shared reference form (used by the xla engine
and the kernel's oracle, which is pinned bitwise against it).
"""
from __future__ import annotations

import jax.numpy as jnp

from ..kernels import ref
from .merge_math import KAPPA_MIN


def init_cache(slots: int, dtype=jnp.float32):
    """Fresh all-stale cache (``count = 0`` masks every entry)."""
    return jnp.zeros((slots, slots), dtype)


def exact_cache(sv_x, gamma, dtype=jnp.float32):
    """Ground-truth cache recomputed from the SV set (tests /
    cache (re)builds after checkpoint restore)."""
    x = sv_x.astype(jnp.float32)
    k = ref.rbf_matrix(x, x, gamma).astype(dtype)
    # I3: rbf_matrix yields exp(-gamma * eps) on the diagonal, not exactly 1
    return jnp.where(jnp.eye(k.shape[0], dtype=bool), 1.0, k)


def _safe_log(k):
    return ref.log_f32(jnp.clip(k.astype(jnp.float32), KAPPA_MIN, 1.0))


def _combine_rows(lk_a, lk_b, lk_ab, h):
    """Log-space kernel row of ``z = h x_a + (1-h) x_b`` (module docstring).

    The clamp at 0 enforces ``k <= 1``; without it, fp noise in the
    ``-h(1-h) log k_ab`` term could push near-duplicate entries above 1.
    """
    lz = h * lk_a + (1.0 - h) * lk_b - h * (1.0 - h) * lk_ab
    return jnp.minimum(lz, 0.0)


def z_row_from_rows(row_i, row_j, k_ij, h):
    """``k(z, .)`` from the two parents' kernel rows and their pair kernel
    (rows the caller already gathered — lets hot paths batch their gathers)."""
    lz = _combine_rows(_safe_log(row_i), _safe_log(row_j), _safe_log(k_ij), h)
    return ref.exp_f32(lz)


def merge_z_row(kmat, i, j, h):
    """``k(z, sv[q])`` for all slots ``q``, from cached rows only.

    ``z = h sv[i] + (1-h) sv[j]``; exact for the RBF kernel up to the
    ``KAPPA_MIN`` clip (entries that small are numerically zero anyway).
    """
    return z_row_from_rows(kmat[i], kmat[j], kmat[i, j], h).astype(kmat.dtype)


# --------------------------------------------------------------------------
# Incremental updates, mirroring the SV-array edits in ``core.budget``
# --------------------------------------------------------------------------
def insert_rows(kmat, idx, k_new_old, k_new_new):
    """Cache update for a minibatch insert at slots ``idx``.

    idx:       (batch,) target slots; entries ``== slots`` are dropped
               (non-violators), matching the sv_x scatter in ``train_step``.
    k_new_old: (batch, slots) ``k(xb, sv_old)`` — the rows the margin
               computation already produced (reused, not recomputed).
    k_new_new: (batch, batch) ``k(xb, xb)`` — kernel among the new points.
    """
    # Columns of the new rows at the inserted slots hold new-vs-new values
    # (k_new_old there is stale: it was computed against pre-insert sv_x).
    rows = k_new_old.astype(kmat.dtype).at[:, idx].set(
        k_new_new.astype(kmat.dtype), mode="drop")
    kmat = kmat.at[idx, :].set(rows, mode="drop")
    kmat = kmat.at[:, idx].set(rows.T, mode="drop")
    # I3: the diagonal of the inserted block is exactly 1 (rbf_matrix gives
    # exp(-gamma * eps) on the diagonal, not exactly 1).
    kmat = kmat.at[idx, idx].set(1.0, mode="drop")
    return kmat


def apply_merge(kmat, i_min, j_star, last, h):
    """Cache update for one merge, mirroring ``budget``'s compaction exactly:
    slot ``lo`` <- z, slot ``hi`` <- old slot ``last``, ``last`` retired.
    """
    z_row = merge_z_row(kmat, i_min, j_star, h)
    lo = jnp.minimum(i_min, j_star)
    hi = jnp.maximum(i_min, j_star)
    row_last = kmat[last]
    kmat = kmat.at[hi, :].set(row_last)
    kmat = kmat.at[:, hi].set(row_last)
    kmat = kmat.at[hi, hi].set(1.0)
    # z_row was computed against the pre-move layout; slot hi now holds the
    # old ``last`` vector, and the diagonal entry is k(z, z) = 1.
    z_row = z_row.at[hi].set(z_row[last]).at[lo].set(1.0)
    kmat = kmat.at[lo, :].set(z_row)
    kmat = kmat.at[:, lo].set(z_row)
    return kmat


def apply_removal(kmat, i_min, last):
    """Cache update for the removal fallback: slot ``i_min`` <- old ``last``."""
    row_last = kmat[last]
    kmat = kmat.at[i_min, :].set(row_last)
    kmat = kmat.at[:, i_min].set(row_last)
    kmat = kmat.at[i_min, i_min].set(1.0)
    return kmat


def apply_multi_merge(kmat, a_idx, b_idx, h, write_idx):
    """Batched cache update for P fused merges (pairs ``(a_p, b_p)``).

    a_idx, b_idx: (P,) slot indices of the pairs (disjoint across pairs).
    h:            (P,) merge coefficients.
    write_idx:    (P,) slot receiving ``z_p`` (``a_p``), or ``slots`` for
                  pairs that did not execute / fell back to removal (those
                  scatters drop).

    Writes the P new ``z`` rows/columns plus the (P, P) cross block
    ``k(z_p, z_q)`` — itself derived by applying the merge identity a second
    time, to the z rows.  Compaction is a separate permutation (``permute``).
    """
    p = a_idx.shape[0]
    lk = _safe_log(kmat[jnp.concatenate([a_idx, b_idx])])   # one (2P,) gather
    lk_a, lk_b = lk[:p], lk[p:]                    # (P, slots) each
    lk_ab = lk_a[jnp.arange(p), b_idx]             # (P,) log k(a_p, b_p)
    lz = _combine_rows(lk_a, lk_b, lk_ab[:, None], h[:, None])   # (P, slots)
    z_rows = ref.exp_f32(lz).astype(kmat.dtype)
    # Cross block: z_q = h_q a_q + (1-h_q) b_q, so k(z_p, z_q) combines the
    # z_p row's entries at a_q and b_q with the (a_q, b_q) pair kernel.
    cross = ref.exp_f32(_combine_rows(lz[:, a_idx], lz[:, b_idx],
                                  lk_ab[None, :], h[None, :]))
    # k(z_p, z_q) and k(z_q, z_p) take different fp paths; average to keep
    # the cache exactly symmetric (I2), and pin the diagonal (I3).
    cross = 0.5 * (cross + cross.T)
    cross = jnp.where(jnp.eye(p, dtype=bool), 1.0, cross).astype(kmat.dtype)
    kmat = kmat.at[write_idx, :].set(z_rows, mode="drop")
    kmat = kmat.at[:, write_idx].set(z_rows.T, mode="drop")
    kmat = kmat.at[write_idx[:, None], write_idx[None, :]].set(cross,
                                                              mode="drop")
    return kmat


def permute(kmat, perm):
    """Apply a slot permutation to both axes (multi-merge compaction)."""
    return kmat[perm][:, perm]


class CacheInvariantError(AssertionError):
    """A runtime I1-I3 violation detected by ``check_invariants``."""


def check_invariants(kmat, sv_x, count, gamma, *, tol: float = 5e-5,
                     context: str = "") -> None:
    """Debug-mode runtime check of cache invariants I1-I3 (DESIGN.md §4).

    Host-side and O(count^2 * dim) — strictly a debug tool, wired into the
    streaming drivers behind ``debug_invariants=True`` (DESIGN.md §16).
    Verifies, masked by the active watermark:

      I1. ``kmat[:c, :c]`` equals a from-scratch Gram rebuild within ``tol``;
      I2. the active block is exactly symmetric;
      I3. the active diagonal is exactly 1.

    Stacked multiclass arrays (3-D ``sv_x``) are checked per class.  Raises
    ``CacheInvariantError`` naming the violated invariant and the worst
    entry; I4 (stale entries past the watermark) is by definition
    uncheckable — consumers mask by ``count``.
    """
    import numpy as np

    sv = np.asarray(sv_x)
    if sv.ndim == 3:
        for q in range(sv.shape[0]):
            check_invariants(np.asarray(kmat)[q], sv[q],
                             np.asarray(count)[q], gamma, tol=tol,
                             context=f"{context}[class {q}]")
        return
    c = int(count)
    if c == 0:
        return
    got = np.asarray(kmat, np.float32)[:c, :c]
    want = np.asarray(exact_cache(jnp.asarray(sv[:c], jnp.float32), gamma))
    where = f"{context}: " if context else ""
    if not np.array_equal(got, got.T):
        i, j = np.unravel_index(np.argmax(np.abs(got - got.T)), got.shape)
        raise CacheInvariantError(
            f"{where}I2 violated: kmat[{i},{j}]={got[i, j]!r} != "
            f"kmat[{j},{i}]={got[j, i]!r}")
    diag = np.diag(got)
    if not np.array_equal(diag, np.ones(c, got.dtype)):
        i = int(np.argmax(np.abs(diag - 1.0)))
        raise CacheInvariantError(
            f"{where}I3 violated: kmat[{i},{i}]={diag[i]!r} != 1")
    err = np.abs(got - want)
    if not np.all(err <= tol):
        i, j = np.unravel_index(np.argmax(err), err.shape)
        raise CacheInvariantError(
            f"{where}I1 violated: |kmat[{i},{j}] - k(sv_{i}, sv_{j})| = "
            f"{err[i, j]:.3e} > tol {tol:g} (cached {got[i, j]!r}, "
            f"exact {want[i, j]!r})")
