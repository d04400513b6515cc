"""Maintenance-event engine: fused rounds vs the vmapped per-class engines.

The contracts pinned here (DESIGN.md §11):
  * one fused event round == ``_merge_once`` per over-budget class, bitwise
    on the ref path (the production CPU impl);
  * the three engines — xla while-loop, xla unrolled, pallas (fused events
    on the sorted-excess schedule) — make bitwise-identical maintenance
    DECISIONS through real training (integer state: counts, inserts, event
    totals) with float state inside fp32 round-off;
  * the sorted-excess schedule early-exits to a bitwise no-op when no class
    is over budget (while AND unrolled forms);
  * the removal strategy stays loop-exact under the vmapped multi-class
    step.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from helpers.invariants import assert_state_parity

from repro.core import (BSGDConfig, MulticlassSVMConfig, default_table, fit,
                        fit_multiclass, fit_multiclass_loop, kernel_cache,
                        run_maintenance_classes)
from repro.core.budget import _merge_once
from repro.data import make_blobs_multiclass, make_two_moons, train_test_split
from repro.kernels import ops as kops
from repro.kernels import ref

GAMMA = 0.5


def _stacked_over_budget(key, c, slots, dim, counts):
    """Random stacked state with exact caches; count[q] = counts[q]."""
    k1, k2 = jax.random.split(key)
    sv = jax.random.normal(k1, (c, slots, dim))
    alpha = 0.1 * jax.random.normal(k2, (c, slots))
    counts = jnp.asarray(counts, jnp.int32)
    alpha = jnp.where(jnp.arange(slots)[None, :] < counts[:, None], alpha, 0.0)
    kmat = jax.vmap(lambda s: kernel_cache.exact_cache(s, GAMMA))(sv)
    return sv, alpha, kmat, counts


# --------------------------------------------------------------------------
# one fused round == _merge_once per class
# --------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("seed", [0, 1])
def test_merge_event_round_matches_merge_once(impl, seed):
    c, slots, dim, budget = 4, 24, 6, 14
    counts = [20, 14, 24, 17]                      # classes 1: at budget
    sv, alpha, kmat, count = _stacked_over_budget(
        jax.random.PRNGKey(seed), c, slots, dim, counts)
    table = default_table()
    over = count > budget
    sv2, al2, km2 = kops.merge_event(sv, alpha, kmat, count, over, table,
                                     impl=impl)
    for q in range(c):
        if not bool(over[q]):
            # no-op classes come back BITWISE untouched
            np.testing.assert_array_equal(np.asarray(al2[q]),
                                          np.asarray(alpha[q]))
            np.testing.assert_array_equal(np.asarray(sv2[q]),
                                          np.asarray(sv[q]))
            np.testing.assert_array_equal(np.asarray(km2[q]),
                                          np.asarray(kmat[q]))
            continue
        s1, a1, k1, _, _ = _merge_once(sv[q], alpha[q], kmat[q], count[q],
                                       GAMMA, "lookup-wd", table)
        # same decisions and formulas; the class-batched ops leave XLA a
        # width-dependent FMA-contraction choice in the z-row combine, so
        # floats match to ~1 ulp, not bitwise (same envelope as the cached
        # vmap engine in test_multiclass)
        tol = 1e-7 if impl == "ref" else 1e-5
        np.testing.assert_allclose(np.asarray(al2[q]), np.asarray(a1),
                                   atol=tol)
        np.testing.assert_allclose(np.asarray(sv2[q]), np.asarray(s1),
                                   atol=tol)
        np.testing.assert_allclose(np.asarray(km2[q]), np.asarray(k1),
                                   atol=max(tol, 1e-6))


def _chosen_merge(alpha, kmat, count, table):
    """``(i, j, h, kappa)`` of the pair ``ref.merge_event`` merges in one
    class: its fixed partner, the Lookup-WD best same-sign partner, the h
    table there and their kernel value, as floats."""
    s = alpha.shape[0]
    idx = jnp.arange(s)
    active = idx < count
    i = int(jnp.argmin(jnp.where(active, jnp.abs(alpha), jnp.inf)))
    m, kap = ref.merge_coords(alpha[i], alpha, kmat[i])
    wd = (alpha[i] + alpha) ** 2 * ref.bilinear_lookup(table.wd_table, m, kap)
    valid = active & (alpha * alpha[i] > 0) & (idx != i)
    j = int(jnp.argmin(jnp.where(valid, wd, jnp.inf)))
    h = ref.bilinear_lookup(table.h_table, m, kap)[j]
    return i, j, float(h), float(kap[j])


def test_merge_event_removal_fallback_round():
    """A class whose min-|alpha| SV has no same-sign partner must fall back
    to removal inside the fused round (mixed with a merging class)."""
    slots, dim = 12, 3
    sv = jax.random.normal(jax.random.PRNGKey(5), (2, slots, dim))
    # class 0: lone positive among strong negatives -> removal fallback;
    # class 1: all same sign -> genuine merge
    a0 = jnp.full((slots,), -2.0).at[3].set(0.01)
    a1 = 0.1 * jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (slots,))) + 0.01
    alpha = jnp.stack([a0, a1])
    count = jnp.asarray([10, 10], jnp.int32)
    alpha = jnp.where(jnp.arange(slots)[None, :] < count[:, None], alpha, 0.0)
    kmat = jax.vmap(lambda s: kernel_cache.exact_cache(s, GAMMA))(sv)
    table = default_table()
    for impl in ("ref", "pallas_interpret"):
        sv2, al2, km2 = kops.merge_event(sv, alpha, kmat, count,
                                         jnp.asarray([True, True]), table,
                                         impl=impl)
        # class 0 removed its positive: survivors all negative, mass intact
        surv = np.asarray(al2[0][:9])
        assert (surv < 0).all(), impl
        # class 1 merged: the merged slot holds a_i k^((1-h)^2) + a_j k^(h^2)
        # on the pair and the h ref.merge_event chose (less than a_i + a_j
        # for k < 1, so the class's alpha sum is not kept)
        i, j, h, k = _chosen_merge(alpha[1], kmat[1], count[1], table)
        a_i, a_j = (float(alpha[1][q]) for q in (i, j))
        a_z = a_i * k ** ((1.0 - h) ** 2) + a_j * k ** (h**2)
        np.testing.assert_allclose(float(al2[1][min(i, j)]), a_z, rtol=1e-6,
                                   err_msg=impl)
        merged = []
        for q in range(2):
            s1, a1_, k1, _, info = _merge_once(sv[q], alpha[q], kmat[q],
                                               count[q], GAMMA, "lookup-wd",
                                               table)
            np.testing.assert_allclose(np.asarray(al2[q]), np.asarray(a1_),
                                       atol=1e-6, err_msg=f"{impl} c={q}")
            merged.append(bool(info.merged))
        assert merged == [False, True]     # fallback fired, merge fired


# --------------------------------------------------------------------------
# sorted-excess schedule
# --------------------------------------------------------------------------
@pytest.mark.parametrize("unroll", [0, 4])
def test_sorted_excess_early_exit_is_bitwise_noop(unroll):
    """No class over budget -> the engine returns the state BITWISE
    unchanged (while form: zero rounds; unrolled form: masked no-op rounds)."""
    c, slots, dim, budget = 3, 16, 4, 12
    sv, alpha, kmat, count = _stacked_over_budget(
        jax.random.PRNGKey(2), c, slots, dim, [12, 7, 10])
    n0 = jnp.asarray([5, 0, 2], jnp.int32)         # pre-existing event counts
    out = run_maintenance_classes(sv, alpha, kmat, count, n0,
                                  default_table(), budget=budget, impl="ref",
                                  unroll=unroll)
    for got, want in zip(out, (sv, alpha, kmat, count, n0)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("unroll", [0, 8])
def test_sorted_excess_drains_every_class_to_budget(unroll):
    """Mixed excesses: the schedule runs to the worst class's excess; every
    class lands exactly on budget and logs exactly its own excess events."""
    c, slots, dim, budget = 4, 32, 5, 20
    counts = [28, 20, 22, 26]                      # excess 8, 0, 2, 6
    key = jax.random.PRNGKey(3)
    sv, alpha, kmat, count = _stacked_over_budget(key, c, slots, dim, counts)
    # same-sign alphas so every event is a merge (event count == excess)
    alpha = jnp.abs(alpha) + jnp.where(
        jnp.arange(slots)[None, :] < count[:, None], 0.01, 0.0)
    alpha = jnp.where(jnp.arange(slots)[None, :] < count[:, None], alpha, 0.0)
    n0 = jnp.zeros((c,), jnp.int32)
    _, al2, _, c2, n2 = run_maintenance_classes(
        sv, alpha, kmat, count, n0, default_table(), budget=budget,
        impl="ref", unroll=unroll)
    np.testing.assert_array_equal(np.asarray(c2), budget)
    np.testing.assert_array_equal(np.asarray(n2),
                                  np.maximum(np.asarray(counts) - budget, 0))
    al2 = np.asarray(al2)
    assert (al2[:, budget:] == 0).all()
    assert (np.abs(al2[:, :budget]) > 0).all()


def test_engine_requires_cache_and_table():
    c, slots, dim, budget = 2, 8, 3, 4
    sv, alpha, kmat, count = _stacked_over_budget(
        jax.random.PRNGKey(0), c, slots, dim, [6, 6])
    with pytest.raises(ValueError):
        run_maintenance_classes(sv, alpha, None, count, count * 0,
                                default_table(), budget=budget)
    with pytest.raises(ValueError):
        run_maintenance_classes(sv, alpha, kmat, count, count * 0, None,
                                budget=budget)


def test_engine_config_validation():
    with pytest.raises(ValueError):
        BSGDConfig(maintenance_engine="bogus")
    # pallas needs cache + merge + lookup-wd
    with pytest.raises(ValueError):
        BSGDConfig(maintenance_engine="pallas")
    with pytest.raises(ValueError):
        BSGDConfig(maintenance_engine="pallas", use_kernel_cache=True,
                   maintenance="removal")
    with pytest.raises(ValueError):
        BSGDConfig(maintenance_engine="pallas", use_kernel_cache=True,
                   method="gss")
    BSGDConfig(maintenance_engine="pallas", use_kernel_cache=True)  # valid


# --------------------------------------------------------------------------
# decision-bitwise property across the three engines, through real training
# --------------------------------------------------------------------------
def _fit_engines(cfg_kw, x, y, n_classes=4):
    states = {}
    for name, extra in (("xla-loop", {}),
                        ("xla-unroll", {"unroll_maintenance": True}),
                        ("pallas", {"maintenance_engine": "pallas",
                                    "unroll_maintenance": True})):
        cfg = MulticlassSVMConfig.create(n_classes, **cfg_kw, **extra)
        states[name] = fit_multiclass(cfg, x, y, epochs=1, seed=0)
    return states


def test_three_engines_decision_bitwise_float_allclose():
    """xla while-loop vs xla unrolled vs the fused event engine, through a
    real multi-class fit: all integer state (counts, step, inserts, event
    totals — i.e. every merge-partner/removal decision) BITWISE identical,
    float state within fp32 round-off (the same envelope the cached vmap
    engine is pinned to in test_multiclass)."""
    x, y = make_blobs_multiclass(jax.random.PRNGKey(7), 480, 6, 4, sep=1.2)
    states = _fit_engines(dict(budget=16, lambda_=1e-3, gamma=0.3,
                               method="lookup-wd", batch_size=4,
                               use_kernel_cache=True), x, y)
    ref_st = states["xla-unroll"]
    assert int(jnp.sum(ref_st.n_merges)) > 0       # the budget actually bit
    for name, st in states.items():
        assert_state_parity(ref_st, st, context=name)


def test_binary_engine_bitwise_vs_unroll():
    """C = 1 lifts through the engine: the binary pallas path is BITWISE the
    unrolled xla path (same trace by construction — pinned so it stays so)."""
    x, y = make_two_moons(jax.random.PRNGKey(42), 600, noise=0.15)
    base = dict(budget=24, lambda_=1e-3, gamma=2.0, method="lookup-wd",
                batch_size=4, use_kernel_cache=True, unroll_maintenance=True)
    st_x = fit(BSGDConfig(**base), x, y, epochs=1, seed=0)
    st_p = fit(BSGDConfig(maintenance_engine="pallas", **base), x, y,
               epochs=1, seed=0)
    assert int(st_p.n_merges) > 0
    assert_state_parity(st_x, st_p, bitwise=True)


def test_engine_trains_bf16_bank_multiclass():
    """The fused engine end to end on a bfloat16 SV bank (fp32 cache)."""
    x, y = make_blobs_multiclass(jax.random.PRNGKey(1), 1200, 8, 4, sep=2.0)
    (xtr, ytr), (xte, yte) = train_test_split(x, y)
    cfg = MulticlassSVMConfig.create(
        4, budget=20, lambda_=1e-3, gamma=0.3, method="lookup-wd",
        batch_size=4, use_kernel_cache=True, sv_dtype="bfloat16",
        maintenance_engine="pallas")
    st = fit_multiclass(cfg, xtr, ytr, epochs=1, seed=0)
    assert st.sv_x.dtype == jnp.bfloat16 and st.kmat.dtype == jnp.float32
    assert np.all(np.asarray(st.count) <= 20)
    assert int(jnp.sum(st.n_merges)) > 0
    from repro.core import accuracy_multiclass
    assert float(accuracy_multiclass(st, xte, yte, 0.3)) > 0.9


def test_engine_cache_stays_consistent_through_training():
    """After a real fit through the fused engine, the carried cache equals a
    from-scratch rebuild on the final SV set (invariant I1)."""
    x, y = make_blobs_multiclass(jax.random.PRNGKey(9), 400, 5, 3, sep=1.5)
    cfg = MulticlassSVMConfig.create(
        3, budget=14, lambda_=1e-3, gamma=0.4, method="lookup-wd",
        batch_size=4, use_kernel_cache=True, maintenance_engine="pallas")
    st = fit_multiclass(cfg, x, y, epochs=1, seed=0)
    assert int(jnp.sum(st.n_merges)) > 0
    for q in range(3):
        n = int(st.count[q])
        got = np.asarray(st.kmat[q])[:n, :n]
        want = np.asarray(kernel_cache.exact_cache(st.sv_x[q], 0.4))[:n, :n]
        np.testing.assert_allclose(got, want, atol=5e-4)


# --------------------------------------------------------------------------
# removal strategy under the vmapped multi-class step
# --------------------------------------------------------------------------
def test_removal_strategy_vmapped_multiclass_matches_loop():
    """maintenance="removal" through the lockstep (vmapped) multi-class step
    == the per-class loop baseline, bitwise — and the budget holds."""
    x, y = make_blobs_multiclass(jax.random.PRNGKey(4), 400, 6, 4, sep=1.2)
    cfg = MulticlassSVMConfig.create(4, budget=16, lambda_=1e-3, gamma=0.2,
                                     method="lookup-wd", batch_size=4,
                                     maintenance="removal")
    st_b = fit_multiclass(cfg, x, y, epochs=1, seed=0)
    st_l = fit_multiclass_loop(cfg, x, y, epochs=1, seed=0)
    assert int(jnp.sum(st_b.n_merges)) > 0         # removal events fired
    assert np.all(np.asarray(st_b.count) <= 16)
    assert_state_parity(st_b, st_l, bitwise=True)
