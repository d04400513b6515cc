"""The merge event's h, looked up for the chosen partner only.

A merge event scores every candidate against the WD table
(``merge_event.lookup_chunks``) and interpolates the h table once, at the
chosen partner (``merge_event.lookup_h``).  Here, in interpret mode, the h
each kernel uses (``merge_event_pallas`` and both fused train-step kernels)
is held to the h that the all-slot form, which interpolated the h table for
every candidate in the scoring chunks, gives at the chosen slot; and the
traced kernels are held to one (G, G)-table contraction per scoring chunk
plus one per chosen pair.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.core import default_table, kernel_cache
from repro.kernels import merge_event, ops, ref, train_step
from repro.kernels.merge_lookup import HIGHEST, _hat_weights

GAMMA, G, BLOCK_S, DIM, BATCH = 0.5, 400, 128, 16, 8


def _all_slot_h(m, kap, h_tab, *, block_s):
    """The all-slot h row: every candidate's h, one (bS, G) x (G, G)
    contraction per scoring chunk, as the scoring loop computed it before
    h was looked up for the chosen pair alone."""
    parts = []
    for start in range(0, m.shape[1], block_s):
        m_c = m[0, start:start + block_s]
        kap_c = kap[0, start:start + block_s]
        rows = jax.lax.dot_general(
            _hat_weights(m_c, G), h_tab, (((1,), (0,)), ((), ())),
            precision=HIGHEST, preferred_element_type=jnp.float32)
        parts.append(jnp.sum(rows * _hat_weights(kap_c, G), axis=1)[None])
    return jnp.concatenate(parts, axis=1)


@pytest.fixture
def seen_h(monkeypatch):
    """Records ``(j, h, all-slot h row)`` for every h the kernels look up,
    with the all-slot row computed in the kernel from the same coordinate
    rows; compiled programs traced before the patch are dropped."""
    seen = []
    real = merge_event.lookup_h

    def probe(m, kap, j, h_tab, *, g):
        h = real(m, kap, j, h_tab, g=g)
        jax.debug.callback(
            lambda *v: seen.append(tuple(np.asarray(x) for x in v)), j, h,
            _all_slot_h(m, kap, h_tab, block_s=BLOCK_S))
        return h

    jax.clear_caches()
    monkeypatch.setattr(merge_event, "lookup_h", probe)
    yield seen
    jax.clear_caches()


def _state(s_pad, case):
    """One class of ``s_pad - 4`` active slots, lane-padded to ``s_pad``,
    one over budget: negative coefficients but for the smallest, at slot 1,
    and (unless ``case`` is ``"removal"``) one same-sign partner, in the
    first or the last scoring chunk, so the event's choice is known."""
    n = s_pad - 4
    k1, k2 = jax.random.split(jax.random.PRNGKey(s_pad))
    sv = 0.3 * jax.random.normal(k1, (1, n, DIM))
    alpha = -2.0 - jax.random.uniform(k2, (1, n))
    alpha = alpha.at[0, 1].set(1e-3)
    j_star = {"first": 3, "last": n - 2, "removal": None}[case]
    if j_star is not None:
        alpha = alpha.at[0, j_star].set(0.5)
    kmat = jax.vmap(lambda x: kernel_cache.exact_cache(x, GAMMA))(sv)
    sv_p, al_p, km_p = ops.pad_fused_state(sv, alpha, kmat)
    return sv_p, al_p, km_p, jnp.asarray([n], jnp.int32), j_star


def _run(kernel, sv, alpha, kmat, count):
    """One merge event through ``kernel``: ``merge_event_pallas`` with the
    class over budget, or one train step whose minibatch (8 bank rows of
    negative coefficient, targets -1) inserts nothing, from a count one
    over budget."""
    tbl = default_table(G)
    if kernel == "merge_event":
        return merge_event.merge_event_pallas(
            sv, alpha, kmat, count, jnp.ones_like(count), tbl.h_table,
            tbl.wd_table, block_s=BLOCK_S, interpret=True)
    xb = sv[0, 8:8 + BATCH]
    k_bb = ref.rbf_matrix(xb, xb, GAMMA)
    yb = -jnp.ones((1, 1, BATCH))
    pad = ops._pad_to_lane
    step = jnp.asarray([100], jnp.int32)
    zero = jnp.zeros((1,), jnp.int32)
    fn = {"train_step": train_step.train_step_pallas,
          "train_step_hbm": train_step.train_step_hbm_pallas}[kernel]
    out = fn(sv, alpha, kmat, count, step, zero, zero, pad(xb, (0, 1)),
             pad(yb, 2), pad(k_bb, (0, 1)), tbl.h_table, tbl.wd_table,
             budget=int(count[0]) - 1, lambda_=1e-3, gamma=GAMMA,
             batch_size=BATCH, rounds=BATCH, block_s=BLOCK_S, interpret=True)
    assert int(out[4][0]) == 0 and int(out[5][0]) == 1   # no insert, 1 event
    return out


@pytest.mark.parametrize("kernel", ["merge_event", "train_step",
                                    "train_step_hbm"])
@pytest.mark.parametrize("case", ["first", "last", "removal"])
@pytest.mark.parametrize("s_pad", [128, 384, 1152])
def test_h_at_chosen_slot_matches_all_slot_form(s_pad, case, kernel,
                                                seen_h):
    """The kernel's h equals the all-slot form's h at the chosen slot, with
    the partner in the first or the last scoring chunk, and on a removal
    fallback (no same-sign candidate; the event ignores h there).

    Bitwise on the chip, where each row of an MXU contraction is computed
    apart from the others.  On the CPU the two may part by up to 2 ulp of
    h: XLA's dot takes another kernel for 8 rows than for 128, and so may
    or may not fuse a row's two nonzero hat-weight products into one
    multiply-add; each of the two table rows the bilinear blends may so
    move by an ulp of its own, and h is their convex blend, rounded once
    more."""
    sv, alpha, kmat, count, j_want = _state(s_pad, case)
    out = _run(kernel, sv, alpha, kmat, count)
    assert len(seen_h) == 1
    j, h, h_all = seen_h[0]
    want = h_all[0, int(j)]
    assert abs(float(h) - float(want)) <= 2 * np.spacing(np.float32(want))
    al_new = np.asarray(out[1])
    if j_want is None:             # removal: the positive left, no merge
        assert (al_new[0, 0, :int(count[0]) - 1] < 0).all()
    else:
        assert int(j) == j_want
        chunk = j_want // BLOCK_S
        assert chunk == (0 if case == "first" else s_pad // BLOCK_S - 1)
        assert al_new[0, 0, 1] > 0                           # merged slot


def _gg_contractions(jaxpr) -> int:
    """``dot_general``s whose right operand is a (G, G) table, through
    every jaxpr a primitive carries (``pallas_call``, ``cond``, ``scan``,
    ``while``, ``pjit``)."""
    n = 0
    for eqn in jaxpr.eqns:
        if (eqn.primitive.name == "dot_general"
                and eqn.invars[1].aval.shape == (G, G)):
            n += 1
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else [v]:
                if isinstance(x, jcore.ClosedJaxpr):
                    n += _gg_contractions(x.jaxpr)
                elif isinstance(x, jcore.Jaxpr):
                    n += _gg_contractions(x)
    return n


@pytest.mark.parametrize("kernel,pairs", [("merge_event", 1),
                                          ("train_step", 1),
                                          ("train_step_hbm", 1),
                                          ("train_step_multi", 4)])
def test_event_contracts_each_table_chunk_once(kernel, pairs):
    """At the MNIST cell's padded shapes (S 1,152, d 896, scoring chunks of
    128) a merge event holds 9 + 1 (G, G) contractions, not 2 x 9; a
    multi-merge round of P pairs, P x (9 + 1)."""
    c, s, d, b = 10, 1152, 896, 128
    sd = lambda *shape, dt=jnp.float32: jax.ShapeDtypeStruct(shape, dt)
    counter = sd(c, dt=jnp.int32)
    table = sd(G, G)
    if kernel == "merge_event":
        fn = functools.partial(merge_event.merge_event_pallas,
                               block_s=BLOCK_S)
        args = (sd(c, s, d), sd(c, 1, s), sd(c, s, s), counter, counter,
                table, table)
    else:
        fn = functools.partial(
            train_step.train_step_hbm_pallas if kernel == "train_step_hbm"
            else train_step.train_step_pallas, budget=1024, lambda_=1e-3,
            gamma=GAMMA, batch_size=8, rounds=8,
            maintenance="multi-merge" if kernel == "train_step_multi"
            else "merge", merge_batch=pairs, block_s=BLOCK_S)
        args = (sd(c, s, d), sd(c, 1, s), sd(c, s, s), counter, counter,
                counter, counter, sd(b, d), sd(c, 1, b), sd(b, b), table,
                table)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    assert _gg_contractions(jaxpr) == pairs * (s // BLOCK_S + 1)
