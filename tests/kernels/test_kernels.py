"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs the ref oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import default_table
from repro.kernels import ops, ref

RBF_SHAPES = [(8, 8, 4), (100, 73, 37), (128, 128, 128), (130, 257, 512),
              (1, 300, 3), (513, 5, 700)]


@pytest.mark.parametrize("n,m,d", RBF_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rbf_matrix_matches_ref(n, m, d, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(n * 31 + m))
    x = jax.random.normal(k1, (n, d), dtype)
    y = jax.random.normal(k2, (m, d), dtype)
    got = ops.rbf_matrix(x, y, 0.3, impl="pallas_interpret")
    want = ref.rbf_matrix(x.astype(jnp.float32), y.astype(jnp.float32), 0.3)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("fn", ["log_f32", "exp_f32"])
@pytest.mark.parametrize("impl", ["xla", "pallas_interpret"])
def test_exp_log_f32_within_few_ulp(fn, impl):
    """The merge math's exp and log, against float64, over the ranges the
    cache feeds them (kappa in [1e-30, 1]; log-kernel values in [-69, 0]),
    exact at 1 and 0, and the same in XLA and in a Pallas kernel."""
    rng = np.random.default_rng(0)
    if fn == "log_f32":
        x = 10.0 ** rng.uniform(-30, 0, (64, 1024))
        x[0, :512] = 1.0 - rng.uniform(0, 1e-3, 512)
        x[0, 0], exact = 1.0, np.log
    else:
        x = rng.uniform(-69, 0, (64, 1024))
        x[0, :512] = -rng.uniform(0, 1e-3, 512)
        x[0, 0], exact = 0.0, np.exp
    x = x.astype(np.float32)
    want = exact(x.astype(np.float64))
    ulp = np.spacing(np.abs(want).astype(np.float32)).astype(np.float64)
    f = getattr(ref, fn)
    if impl == "xla":
        got = jax.jit(f)(x)
    else:
        def kern(x_ref, o_ref):
            o_ref[...] = f(x_ref[...])
            o_ref[0, 1] = f(x_ref[0, 1])          # a scalar takes the same path
        got = pl.pallas_call(kern, out_shape=jax.ShapeDtypeStruct(x.shape,
                                                                  jnp.float32),
                             interpret=True)(x)
    got = np.asarray(got).astype(np.float64)
    assert np.all(np.abs(got - want) <= 4 * ulp)
    assert got[0, 0] == want[0, 0]


@pytest.mark.parametrize("gamma", [0.01, 1.0, 30.0])
def test_rbf_gamma_sweep(gamma):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 16))
    got = ops.rbf_matrix(x, x, gamma, impl="pallas_interpret")
    want = ref.rbf_matrix(x, x, gamma)
    # exp amplifies fp error by ~gamma * |eps(d^2)| — scale tolerance with it
    tol = max(1e-5, 3e-5 * gamma)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)
    # self-distance cancels to ~eps; diagonal ~= 1 up to exp(-gamma*eps)
    np.testing.assert_allclose(np.asarray(jnp.diag(got)), 1.0, atol=tol)


def test_rbf_row():
    key = jax.random.PRNGKey(1)
    sv = jax.random.normal(key, (57, 9))
    x = jax.random.normal(jax.random.PRNGKey(2), (9,))
    got = ops.rbf_row(sv, x, 0.7, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.rbf_row(sv, x, 0.7)),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("s", [16, 100, 512, 1000])
def test_merge_scores_matches_ref(s):
    tbl = default_table()
    key = jax.random.PRNGKey(s)
    alpha = jnp.abs(jax.random.normal(key, (s,))) * 0.2 + 0.01
    kappa = jax.random.uniform(jax.random.PRNGKey(s + 1), (s,))
    valid = jax.random.bernoulli(jax.random.PRNGKey(s + 2), 0.8, (s,))
    a_min = 0.05
    wd_p, int_p = ops.merge_scores(alpha, kappa, valid, a_min, tbl.wd_table,
                                   impl="pallas_interpret")
    wd_r, int_r = ops.merge_scores(alpha, kappa, valid, a_min, tbl.wd_table,
                                   impl="ref")
    mask = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(wd_p)[mask], np.asarray(wd_r)[mask],
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(int_p), np.asarray(int_r),
                               rtol=1e-4, atol=1e-6)
    # invalid slots must lose every argmin
    if (~mask).any() and mask.any():
        assert np.asarray(wd_p)[~mask].min() > np.asarray(wd_p)[mask].max()


def test_merge_scores_argmin_equals_oracle():
    """End-to-end: the kernel's argmin picks the oracle's best partner."""
    tbl = default_table()
    for seed in range(5):
        key = jax.random.PRNGKey(seed)
        alpha = jnp.abs(jax.random.normal(key, (64,))) * 0.3 + 0.02
        kappa = jax.random.uniform(jax.random.PRNGKey(seed + 9), (64,),
                                   minval=0.2, maxval=0.99)
        valid = jnp.ones((64,), bool).at[10].set(False)
        wd_p, _ = ops.merge_scores(alpha, kappa, valid, 0.04, tbl.wd_table,
                                   impl="pallas_interpret")
        wd_r, _ = ops.merge_scores(alpha, kappa, valid, 0.04, tbl.wd_table,
                                   impl="ref")
        assert int(jnp.argmin(wd_p)) == int(jnp.argmin(wd_r))


@pytest.mark.parametrize("shape", [(1, 16), (3, 100), (8, 512)])
@pytest.mark.parametrize("n_iters", [10, 48])
def test_gss_kernel_matches_ref(shape, n_iters):
    k1, k2 = jax.random.split(jax.random.PRNGKey(shape[1]))
    m = jax.random.uniform(k1, shape, minval=0.01, maxval=0.99)
    kappa = jax.random.uniform(k2, shape, minval=0.15, maxval=0.999)
    got = ops.gss_solve(m, kappa, n_iters=n_iters, impl="pallas_interpret")
    want = ref.gss(m, kappa, n_iters)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("p", [1, 3, 8])
@pytest.mark.parametrize("s", [16, 100, 512])
def test_multi_merge_scores_matches_ref(p, s):
    tbl = default_table()
    key = jax.random.PRNGKey(p * 131 + s)
    alpha = jnp.abs(jax.random.normal(key, (s,))) * 0.2 + 0.01
    kappa = jax.random.uniform(jax.random.PRNGKey(s + 1), (p, s))
    valid = jax.random.bernoulli(jax.random.PRNGKey(s + 2), 0.8, (p, s))
    a_min = jnp.abs(jax.random.normal(jax.random.PRNGKey(s + 3), (p,))) * 0.05
    wd_p, h_p = ops.multi_merge_scores(alpha, kappa, valid, a_min, tbl,
                                       impl="pallas_interpret")
    wd_r, h_r = ops.multi_merge_scores(alpha, kappa, valid, a_min, tbl,
                                       impl="ref")
    mask = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(wd_p)[mask], np.asarray(wd_r)[mask],
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_r),
                               rtol=1e-4, atol=1e-5)
    # invalid slots must lose every per-row argmin
    for q in range(p):
        if mask[q].any() and (~mask[q]).any():
            assert np.asarray(wd_p)[q][~mask[q]].min() > \
                np.asarray(wd_p)[q][mask[q]].max()


@pytest.mark.parametrize("c,p,s", [(2, 1, 16), (3, 4, 100), (5, 8, 256)])
def test_multi_merge_scores_class_batched(c, p, s):
    """(C, P, s) layout == per-class (P, s) calls, pallas vs ref."""
    tbl = default_table()
    key = jax.random.PRNGKey(c * 7 + s)
    alpha = jnp.abs(jax.random.normal(key, (c, s))) * 0.2 + 0.01
    kappa = jax.random.uniform(jax.random.PRNGKey(s + 1), (c, p, s))
    valid = jax.random.bernoulli(jax.random.PRNGKey(s + 2), 0.8, (c, p, s))
    a_min = jnp.abs(jax.random.normal(jax.random.PRNGKey(s + 3), (c, p))) * 0.05
    wd_p, h_p = ops.multi_merge_scores(alpha, kappa, valid, a_min, tbl,
                                       impl="pallas_interpret")
    wd_r, h_r = ops.multi_merge_scores(alpha, kappa, valid, a_min, tbl,
                                       impl="ref")
    assert wd_p.shape == (c, p, s)
    mask = np.asarray(valid)
    np.testing.assert_allclose(np.asarray(wd_p)[mask], np.asarray(wd_r)[mask],
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_r),
                               rtol=1e-4, atol=1e-5)
    for q in range(c):   # each class row == the unbatched call on its slice
        wd_q, h_q = ops.multi_merge_scores(alpha[q], kappa[q], valid[q],
                                           a_min[q], tbl, impl="ref")
        np.testing.assert_allclose(np.asarray(wd_r[q]), np.asarray(wd_q),
                                   rtol=1e-6, atol=0)
        np.testing.assert_allclose(np.asarray(h_r[q]), np.asarray(h_q),
                                   rtol=1e-6, atol=0)


def test_merge_scores_class_batched_matches_per_class():
    """(C, s) merge_scores == C single calls (one fixed partner per class)."""
    tbl = default_table()
    c, s = 4, 100
    alpha = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (c, s))) * 0.3 + 0.02
    kappa = jax.random.uniform(jax.random.PRNGKey(1), (c, s))
    valid = jax.random.bernoulli(jax.random.PRNGKey(2), 0.9, (c, s))
    a_min = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (c,))) * 0.05
    for impl in ("ref", "pallas_interpret"):
        wd_b, int_b = ops.merge_scores(alpha, kappa, valid, a_min,
                                       tbl.wd_table, impl=impl)
        assert wd_b.shape == (c, s)
        for q in range(c):
            wd_q, int_q = ops.merge_scores(alpha[q], kappa[q], valid[q],
                                           a_min[q], tbl.wd_table, impl=impl)
            mask = np.asarray(valid[q])
            np.testing.assert_allclose(np.asarray(wd_b[q])[mask],
                                       np.asarray(wd_q)[mask],
                                       rtol=1e-5, atol=1e-7)
            np.testing.assert_allclose(np.asarray(int_b[q]), np.asarray(int_q),
                                       rtol=1e-4, atol=1e-5)


def test_multi_merge_scores_rows_match_single_kernel():
    """Each row of the multi kernel == the single-partner kernel's output."""
    tbl = default_table()
    s, p = 100, 4
    alpha = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (s,))) * 0.3 + 0.02
    kappa = jax.random.uniform(jax.random.PRNGKey(1), (p, s))
    valid = jnp.ones((p, s), bool)
    a_min = jnp.asarray([0.01, 0.04, 0.1, 0.5])
    wd_m, h_m = ops.multi_merge_scores(alpha, kappa, valid, a_min, tbl,
                                       impl="pallas_interpret")
    for q in range(p):
        wd_s, _ = ops.merge_scores(alpha, kappa[q], valid[q], a_min[q],
                                   tbl.wd_table, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(wd_m[q]), np.asarray(wd_s),
                                   rtol=1e-5, atol=1e-7)
        np.testing.assert_allclose(
            np.asarray(h_m[q]),
            np.asarray(ref.bilinear_lookup(tbl.h_table, *ref.merge_coords(
                a_min[q], alpha, kappa[q]))), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("c,s,d", [(1, 16, 4), (3, 40, 9), (4, 130, 33)])
def test_merge_event_kernel_matches_ref(c, s, d):
    """The fused maintenance-event kernel (interpret) vs its oracle: random
    stacked over-budget states, mixed over/at-budget classes."""
    from repro.core import kernel_cache

    key = jax.random.PRNGKey(c * 31 + s)
    k1, k2, k3 = jax.random.split(key, 3)
    sv = jax.random.normal(k1, (c, s, d))
    counts = jax.random.randint(k2, (c,), s // 2, s + 1).astype(jnp.int32)
    alpha = 0.1 * jax.random.normal(k3, (c, s))
    alpha = jnp.where(jnp.arange(s)[None, :] < counts[:, None], alpha, 0.0)
    kmat = jax.vmap(lambda v: kernel_cache.exact_cache(v, 0.5))(sv)
    over = jnp.arange(c) % 2 == 0                    # every other class runs
    tbl = default_table()
    got = ops.merge_event(sv, alpha, kmat, counts, over, tbl,
                          impl="pallas_interpret")
    want = ops.merge_event(sv, alpha, kmat, counts, over, tbl, impl="ref")
    for g, w, name in zip(got, want, ("sv_x", "alpha", "kmat")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-5, err_msg=name)
    # classes with over clear are bitwise untouched on BOTH impls
    for arrs, orig in ((got, (sv, alpha, kmat)), (want, (sv, alpha, kmat))):
        for g, o in zip(arrs, orig):
            np.testing.assert_array_equal(np.asarray(g)[1::2],
                                          np.asarray(o)[1::2])


def test_merge_event_kernel_bf16_bank():
    """bf16 SV banks round-trip the kernel: untouched rows stay bitwise, the
    merged row matches the oracle's bf16 cast."""
    from repro.core import kernel_cache

    c, s, d = 2, 24, 8
    sv = jax.random.normal(jax.random.PRNGKey(0), (c, s, d)).astype(jnp.bfloat16)
    alpha = 0.1 * jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (c, s))) + 0.01
    counts = jnp.asarray([20, 18], jnp.int32)
    alpha = jnp.where(jnp.arange(s)[None, :] < counts[:, None], alpha, 0.0)
    kmat = jax.vmap(lambda v: kernel_cache.exact_cache(
        v.astype(jnp.float32), 0.5))(sv)
    tbl = default_table()
    got = ops.merge_event(sv, alpha, kmat, counts, counts > 0, tbl,
                          impl="pallas_interpret")
    want = ops.merge_event(sv, alpha, kmat, counts, counts > 0, tbl,
                           impl="ref")
    assert got[0].dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got[0]).astype(np.float32),
                               np.asarray(want[0]).astype(np.float32),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("c,slots,n,d", [(1, 16, 8, 4), (5, 33, 70, 11),
                                         (8, 128, 130, 32)])
def test_class_scores_fused_matches_per_class_oracle(c, slots, n, d):
    """The serving contraction: one fused (n, C*slots) launch == C
    sequential kernel calls, for fp32 and quantized bf16 banks."""
    keys = jax.random.split(jax.random.PRNGKey(c * 7 + n), 3)
    sv = jax.random.normal(keys[0], (c, slots, d))
    alpha = jax.random.normal(keys[1], (c, slots))
    x = jax.random.normal(keys[2], (n, d))
    for bank_dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)):
        bank = sv.astype(bank_dtype)
        for impl in ("ref", "pallas_interpret"):
            got = ops.class_scores(x, bank, alpha, 0.4, impl=impl)
            assert got.shape == (c, n) and got.dtype == alpha.dtype
            want = ref.class_scores(x, bank.astype(jnp.float32), alpha, 0.4)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=tol, atol=tol)
