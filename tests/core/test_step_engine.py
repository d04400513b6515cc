"""Train-step engine: fused megakernel step vs the composed three-phase step.

The contracts pinned here (DESIGN.md §12):
  * ``step_engine="pallas"`` makes bitwise-identical train-step DECISIONS
    (all integer state: counts, step, insert/event totals) through real
    multi-step training, with float state inside fp32 round-off — across
    maintenance strategies, class counts, and the bf16 bank;
  * the kernel cache stays exact (== rebuild from the bank) after fused
    training;
  * fused-vs-composed parity holds at one steady-state step over a grid
    of widths, budgets and class counts (C 1 and 16);
  * the BOGD-style ``maintenance="removal-project"`` strategy matches its
    closed form and stays loop-exact under the vmapped multi-class step;
  * ``kernels.ops._pad_to_lane`` round-trips (pad then slice == identity);
  * a chunk scan of the Pallas fused step carries the lane-padded state:
    bitwise the real state of a scan of per-step ``ops.train_step``, a pad
    region that stays finite, and no state pad or slice inside the loop.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (BSGDConfig, MulticlassSVMConfig, accuracy, fit,
                        fit_multiclass, fit_multiclass_loop, kernel_cache)
from repro.core.budget import _removal_all, _removal_project_all
from repro.data import make_blobs_multiclass, make_two_moons, train_test_split
from repro.kernels import ops
from repro.kernels.ops import _pad_to_lane

GAMMA = 0.5


def _binary_cfg(maintenance="merge", **kw):
    return BSGDConfig(budget=12, lambda_=1e-3, gamma=GAMMA, batch_size=8,
                      method="lookup-wd", use_kernel_cache=True,
                      maintenance=maintenance, **kw)


def _fit_mc(cfg_b, n_classes, seed=0):
    cfg = MulticlassSVMConfig(n_classes=n_classes, binary=cfg_b)
    key = jax.random.PRNGKey(seed)
    x, y = make_blobs_multiclass(key, 160, 5, n_classes=n_classes)
    return fit_multiclass(cfg, x, y, epochs=2, seed=seed, impl="ref")


# ints BITWISE, floats inside fp32 round-off — shared with the cross-solver
# harness (tests/helpers/invariants.py)
from helpers.invariants import assert_state_parity as _assert_state_parity
from helpers.hlo import ops_on_shapes


# --------------------------------------------------------------------------
# fused step == composed step through real training
# --------------------------------------------------------------------------
@pytest.mark.parametrize("n_classes", [2, 16])
@pytest.mark.parametrize("strategy", ["merge", "multi-merge"])
def test_fused_step_matches_composed_multiclass(strategy, n_classes):
    st_c = _fit_mc(_binary_cfg(strategy, step_engine="composed"), n_classes)
    st_f = _fit_mc(_binary_cfg(strategy, step_engine="pallas"), n_classes)
    assert int(jnp.sum(st_c.n_merges)) > 0         # the budget actually bit
    _assert_state_parity(st_c, st_f)


@pytest.mark.parametrize("strategy", ["merge", "multi-merge"])
def test_fused_step_matches_composed_binary(strategy):
    """C=1: the binary ``bsgd.train_step`` fused branch (no class axis)."""
    x, y = make_two_moons(jax.random.PRNGKey(0), 200)
    st_c = fit(_binary_cfg(strategy, step_engine="composed"), x, y,
               epochs=2, impl="ref")
    st_f = fit(_binary_cfg(strategy, step_engine="pallas"), x, y,
               epochs=2, impl="ref")
    assert int(st_c.n_merges) > 0
    _assert_state_parity(st_c, st_f)
    acc = float(accuracy(st_f, x, y, GAMMA))
    assert acc > 0.8, acc


def test_fused_step_bf16_bank():
    cfg_c = _binary_cfg(sv_dtype="bfloat16", step_engine="composed")
    cfg_f = _binary_cfg(sv_dtype="bfloat16", step_engine="pallas")
    st_c = _fit_mc(cfg_c, 4)
    st_f = _fit_mc(cfg_f, 4)
    assert st_f.sv_x.dtype == jnp.bfloat16
    assert st_f.kmat.dtype == jnp.float32
    _assert_state_parity(st_c, st_f)


def test_cache_matches_rebuild_after_fused_training():
    st = _fit_mc(_binary_cfg("multi-merge", step_engine="pallas"), 3)
    rebuilt = jax.vmap(
        lambda s: kernel_cache.exact_cache(s.astype(jnp.float32), GAMMA))(
            st.sv_x)
    slots = st.alpha.shape[1]
    live = jnp.arange(slots)[None, :] < st.count[:, None]
    mask = (live[:, :, None] & live[:, None, :])
    np.testing.assert_allclose(
        np.where(np.asarray(mask), np.asarray(st.kmat), 0.0),
        np.where(np.asarray(mask), np.asarray(rebuilt), 0.0), atol=5e-4)


# --------------------------------------------------------------------------
# parity over a grid of widths, budgets and class counts
# --------------------------------------------------------------------------
PARITY_CELLS = [(dim, budget, c) for dim in (64, 512)
               for budget in (256, 1024) for c in (1, 16)]


@pytest.mark.parametrize("dim,budget,n_classes", PARITY_CELLS)
def test_fused_step_parity_at_bench_cells(dim, budget, n_classes):
    """One steady-state step (count == budget, events fire) per cell of a
    grid of widths, budgets and class counts (C = 1 is the binary step)."""
    kw = dict(budget=budget, lambda_=1e-3, gamma=2.0**-7, batch_size=8,
              method="lookup-wd", use_kernel_cache=True, maintenance="merge")
    if n_classes == 1:
        from repro.core.bsgd import init_state, train_step
        cfg_c = BSGDConfig(step_engine="composed", **kw)
        cfg_f = BSGDConfig(step_engine="pallas", **kw)
        make_step = lambda cfg: lambda tbl, st, xb, yb: train_step(
            cfg, tbl, st, xb, yb, impl="ref")
        state = init_state(cfg_c, dim)
        lead = ()
    else:
        from repro.core.multiclass import (init_multiclass_state,
                                           train_step_multiclass)
        cfg_c = MulticlassSVMConfig(
            n_classes=n_classes, binary=BSGDConfig(step_engine="composed",
                                                   **kw))
        cfg_f = MulticlassSVMConfig(
            n_classes=n_classes, binary=BSGDConfig(step_engine="pallas",
                                                   **kw))
        make_step = lambda cfg: lambda tbl, st, xb, yb: train_step_multiclass(
            cfg, tbl, st, xb, yb, impl="ref")
        state = init_multiclass_state(cfg_c, dim)
        lead = (n_classes,)

    # steady state: bank full at exactly budget, same-sign alphas, exact
    # cache — every violator insert forces a maintenance event this step
    rng = np.random.default_rng(dim * 7 + budget + n_classes)
    slots = state.alpha.shape[-1]
    sv = jnp.asarray(rng.normal(size=lead + (slots, dim)), jnp.float32)
    al = jnp.asarray(0.1 * np.abs(rng.normal(size=lead + (slots,))) + 0.01,
                     jnp.float32)
    cnt = jnp.full(lead, budget, jnp.int32)
    al = jnp.where(jnp.arange(slots) < budget, al, 0.0)
    cache = kernel_cache.exact_cache if n_classes == 1 else jax.vmap(
        lambda s: kernel_cache.exact_cache(s, kw["gamma"]))
    km = cache(sv, kw["gamma"]) if n_classes == 1 else cache(sv)
    state = state._replace(sv_x=sv, alpha=al, kmat=km, count=cnt,
                           step=jnp.full(lead, 3, jnp.int32))
    xb = jnp.asarray(rng.normal(size=(8, dim)), jnp.float32)
    if n_classes == 1:
        yb = jnp.asarray(np.where(rng.random(8) < 0.5, -1.0, 1.0),
                         jnp.float32)
    else:
        yb = jnp.asarray(rng.integers(0, n_classes, size=8), jnp.int32)

    table = cfg_c.table()
    st_c = make_step(cfg_c)(table, state, xb, yb)
    st_f = make_step(cfg_f)(table, state, xb, yb)
    assert int(jnp.sum(st_c.n_merges)) > 0
    _assert_state_parity(st_c, st_f)


# --------------------------------------------------------------------------
# the chunk scan carries the fused step's lane-padded state
# --------------------------------------------------------------------------
CARRY_STEPS, CARRY_DIM, CARRY_BUDGET, CARRY_BATCH = 64, 6, 20, 8


@pytest.mark.parametrize("strategy", ["merge", "multi-merge"])
@pytest.mark.parametrize("n_classes", [1, 3])
def test_chunk_carry_matches_per_step(n_classes, strategy):
    """The chunk and epoch programs of the Pallas fused step carry the
    lane-padded state: the real state comes out bitwise as from a scan of
    per-step ``ops.train_step``, and the pad region stays finite after every
    step.  Slots 28 and d 6 are not lane multiples; most steps merge."""
    from repro.core import bsgd, multiclass
    b = BSGDConfig(budget=CARRY_BUDGET, lambda_=1e-3, gamma=GAMMA,
                   batch_size=CARRY_BATCH, method="lookup-wd",
                   use_kernel_cache=True, maintenance=strategy,
                   step_engine="pallas")
    key = jax.random.PRNGKey(n_classes)
    n = CARRY_STEPS * CARRY_BATCH
    if n_classes == 1:
        x, y = make_two_moons(key, n, noise=0.3, dim=CARRY_DIM)
        cfg, state = b, bsgd.init_state(b, CARRY_DIM)
        chunk, epoch, step = bsgd.train_chunk, bsgd.train_epoch, \
            bsgd.train_step
        targets = lambda yb: yb[None]
    else:
        x, y = make_blobs_multiclass(key, n, CARRY_DIM, n_classes=n_classes,
                                     sep=1.0)
        cfg = MulticlassSVMConfig(n_classes=n_classes, binary=b)
        state = multiclass.init_multiclass_state(cfg, CARRY_DIM)
        chunk, epoch, step = (multiclass.train_chunk_multiclass,
                              multiclass.train_epoch_multiclass,
                              multiclass.train_step_multiclass)
        targets = lambda yb: multiclass.ovr_targets(yb, n_classes)
    xc = x.reshape(CARRY_STEPS, CARRY_BATCH, CARRY_DIM)
    yc = y.reshape(CARRY_STEPS, CARRY_BATCH)
    table = cfg.table()
    interp = "pallas_interpret"

    want = jax.jit(lambda st: jax.lax.scan(
        lambda s_, xy: (step(cfg, table, s_, *xy, impl=interp), ()),
        st, (xc, yc))[0])(state)
    assert int(jnp.sum(want.n_merges)) > (CARRY_STEPS // 2) * n_classes
    got_chunk = chunk(cfg, table, jax.tree.map(jnp.array, state), xc, yc,
                      impl=interp)
    got_epoch = epoch(cfg, table, state, x, y, jnp.arange(n), impl=interp)
    for got in (got_chunk, got_epoch):
        for name, w, g in zip(want._fields, want, got):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=name)

    # the padded carry itself, step by step: finite, zero bank and alpha
    # outside the real region, cache values in [0, 1]
    st = state if n_classes > 1 else jax.tree.map(lambda a: a[None], state)
    _, s, d = st.sv_x.shape

    def body(carry, xy):
        xb, yb = xy
        k_bb = ops.rbf_matrix(xb, xb, GAMMA, impl=interp)
        carry = ops.train_step_padded(
            *carry, xb, targets(yb), k_bb, table, budget=b.budget,
            lambda_=b.lambda_, gamma=b.gamma, batch_size=b.batch_size,
            maintenance=b.maintenance, merge_batch=b.merge_batch,
            impl=interp)
        sv, al, km = carry[:3]
        finite = jnp.all(jnp.isfinite(sv)) & jnp.all(jnp.isfinite(al)) \
            & jnp.all(jnp.isfinite(km))
        pad_sv = jnp.max(jnp.abs(sv.at[:, :s, :d].set(0.0)))
        pad_al = jnp.max(jnp.abs(al.at[:, :, :s].set(0.0)))
        return carry, (finite, pad_sv, pad_al, jnp.min(km), jnp.max(km))

    carry0 = (*ops.pad_fused_state(st.sv_x, st.alpha, st.kmat), st.count,
              st.step, st.n_inserts, st.n_merges)
    carry, (finite, pad_sv, pad_al, km_lo, km_hi) = jax.jit(
        lambda c: jax.lax.scan(body, c, (xc, yc)))(carry0)
    assert carry[0].shape[1] > s and carry[0].shape[2] > d
    assert bool(jnp.all(finite))
    assert float(jnp.max(pad_sv)) == 0.0 and float(jnp.max(pad_al)) == 0.0
    assert float(jnp.min(km_lo)) >= 0.0 and float(jnp.max(km_hi)) <= 1.0
    real = ops.unpad_fused_state(*carry[:3], s, d)
    for w, g in zip((want.sv_x, want.alpha, want.kmat), real):
        np.testing.assert_array_equal(np.asarray(g).reshape(w.shape),
                                      np.asarray(w))


@pytest.mark.parametrize("n_classes", [1, 3])
def test_chunk_program_pads_state_once(n_classes):
    """The chunk program (``train_chunk_multiclass``; at C = 1 the binary
    ``bsgd.train_chunk``, which lifts the state to one class) pads the
    (C, S, S) cache and the (C, S, d) bank once before its loop and slices
    them once after it: the loop body holds no pad or slice of either."""
    from repro.core import bsgd
    from repro.core.multiclass import (init_multiclass_state,
                                       train_chunk_multiclass)
    c = n_classes
    b = _binary_cfg(step_engine="pallas")
    if c == 1:
        cfg, chunk, labels = b, bsgd.train_chunk, jnp.ones((4, 8))
        state = bsgd.init_state(b, CARRY_DIM)
    else:
        cfg = MulticlassSVMConfig(n_classes=c, binary=b)
        chunk, labels = train_chunk_multiclass, jnp.zeros((4, 8), jnp.int32)
        state = init_multiclass_state(cfg, CARRY_DIM)
    s = cfg.slots
    text = chunk.lower(
        cfg, cfg.table(), state, jnp.zeros((4, 8, CARRY_DIM)), labels,
        impl="pallas_interpret").as_text(dialect="hlo")
    count = ops_on_shapes(text, ("pad", "slice"),
                          {(c, s, s), (c, s, CARRY_DIM)})
    # [outside the loop, inside it]: one per leaf, none per step
    assert count == {"pad": [2, 0], "slice": [2, 0]}, count


# --------------------------------------------------------------------------
# removal-project (BOGD-style removal + projection, arXiv 1206.4633)
# --------------------------------------------------------------------------
def test_removal_project_matches_closed_form():
    """One event == plain removal + the documented projection formula."""
    rng = np.random.default_rng(3)
    slots, dim, budget, count = 20, 5, 14, 18
    sv = jnp.asarray(rng.normal(size=(slots, dim)), jnp.float32)
    al = jnp.asarray(rng.normal(size=(slots,)) * 0.1, jnp.float32)
    al = jnp.where(jnp.arange(slots) < count, al, 0.0)
    km = kernel_cache.exact_cache(sv, GAMMA)
    cnt = jnp.int32(count)

    sv_r, al_r, km_r, cnt_r = _removal_all(sv, al, km, cnt, budget)
    sv_p, al_p, km_p, cnt_p = _removal_project_all(sv, al, km, cnt, budget)
    assert int(cnt_p) == int(cnt_r) == budget
    # same survivors in the same order, same permuted cache
    np.testing.assert_array_equal(np.asarray(sv_p), np.asarray(sv_r))
    np.testing.assert_allclose(np.asarray(km_p), np.asarray(km_r), atol=1e-6)

    # numpy closed form: holes = smallest-|alpha| active rows
    a = np.asarray(al)
    k = np.asarray(km)
    active = np.arange(slots) < count
    order = np.argsort(np.where(active, np.abs(a), np.inf), kind="stable")
    holes = np.zeros(slots, bool)
    holes[order[:count - budget]] = True
    surv = active & ~holes
    k_hs = np.where(holes[:, None] & surv[None, :], k, 0.0)
    denom = np.maximum(k_hs.sum(axis=1), 1e-12)
    gain = (np.where(holes, a, 0.0) / denom) @ k_hs
    expect = np.where(surv, a + gain, a)
    # compaction keeps survivor order: positions [0, budget) are exactly the
    # surviving slots in slot order
    np.testing.assert_allclose(np.asarray(al_p)[:budget], expect[surv],
                               rtol=1e-6, atol=1e-7)
    assert not np.allclose(np.asarray(al_p), np.asarray(al_r))


def test_removal_project_vmap_loop_parity():
    cfg = MulticlassSVMConfig(n_classes=3, binary=BSGDConfig(
        budget=14, lambda_=1e-3, gamma=GAMMA, batch_size=8,
        method="lookup-wd", use_kernel_cache=True,
        maintenance="removal-project"))
    key = jax.random.PRNGKey(1)
    x, y = make_blobs_multiclass(key, 160, 5, n_classes=3)
    s1 = fit_multiclass(cfg, x, y, epochs=1)
    s2 = fit_multiclass_loop(cfg, x, y, epochs=1)
    np.testing.assert_array_equal(np.asarray(s1.count), np.asarray(s2.count))
    np.testing.assert_allclose(np.asarray(s1.alpha), np.asarray(s2.alpha),
                               rtol=1e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(s1.kmat), np.asarray(s2.kmat),
                               rtol=1e-5, atol=5e-5)


def test_removal_project_learns():
    from repro.data import make_blobs
    x, y = make_blobs(jax.random.PRNGKey(5), 1000, 8, sep=2.5)
    (xtr, ytr), (xte, yte) = train_test_split(x, y)
    cfg = BSGDConfig(budget=30, lambda_=1e-4, gamma=0.3, method="lookup-wd",
                     use_kernel_cache=True, maintenance="removal-project")
    st = fit(cfg, xtr, ytr, epochs=2, seed=0)
    assert int(st.count) <= cfg.budget
    acc = float(accuracy(st, xte, yte, cfg.gamma))
    assert acc > 0.9, acc


# --------------------------------------------------------------------------
# config validation + _pad_to_lane
# --------------------------------------------------------------------------
def test_step_engine_config_validation():
    with pytest.raises(ValueError, match="step_engine"):
        BSGDConfig(step_engine="bogus")
    with pytest.raises(ValueError, match="kernel cache|use_kernel_cache"):
        BSGDConfig(step_engine="pallas")                 # needs the cache
    with pytest.raises(ValueError, match="step_engine"):
        BSGDConfig(step_engine="pallas", use_kernel_cache=True,
                   method="lookup-h")                    # needs lookup-wd
    with pytest.raises(ValueError, match="step_engine"):
        BSGDConfig(step_engine="pallas", use_kernel_cache=True,
                   maintenance="removal")                # needs merge rounds
    with pytest.raises(ValueError, match="use_kernel_cache"):
        BSGDConfig(maintenance="removal-project")        # needs the cache


@pytest.mark.parametrize("shape,axes,multiple", [
    ((5,), 0, 128),
    ((5, 7), (0, 1), 128),
    ((3, 5, 7), (1, 2), (8, 128)),
    ((256, 128), (0, 1), 128),                           # already aligned
])
def test_pad_to_lane_roundtrip(shape, axes, multiple):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    p = _pad_to_lane(x, axes, multiple)
    mults = (multiple,) * len(np.atleast_1d(axes)) \
        if isinstance(multiple, int) else multiple
    for ax, m in zip(np.atleast_1d(axes), mults):
        assert p.shape[ax] % m == 0
        assert p.shape[ax] >= x.shape[ax]
    sl = tuple(slice(0, n) for n in shape)
    np.testing.assert_array_equal(np.asarray(p[sl]), np.asarray(x))
    # padding is appended zeros — the original block is untouched
    assert float(jnp.sum(jnp.abs(p))) == pytest.approx(
        float(jnp.sum(jnp.abs(x))), rel=1e-6)


def test_pad_to_lane_value():
    x = jnp.ones((3, 5))
    p = _pad_to_lane(x, 1, 8, value=1.0)
    assert p.shape == (3, 8)
    np.testing.assert_array_equal(np.asarray(p), 1.0)
