"""The fused train step with the kernel cache left in HBM (interpret mode).

``train_step.train_step_hbm_pallas`` runs the whole-block kernel's body with
each class's (S, S) cache in HBM, its rows and columns moved by DMA through
small VMEM tiles.  ``ops.train_step_padded`` picks it from the padded shapes
(``ops.fused_kernel``) where the whole-block kernel's VMEM need is over a
core's limit.  Here that route is reached at small shapes by lowering
``merge_event.VMEM_LIMIT_BYTES`` to the HBM kernel's own need, and the
state it trains is held bitwise to the whole-block kernel's, pad region and
counters included: one step, and 64-step chunk and epoch scans through
``multiclass.scan_ovr``.  Slots 28 and d 6 are not lane multiples; most steps
insert and merge.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import BSGDConfig, MulticlassSVMConfig, bsgd, multiclass
from repro.data import make_blobs_multiclass, make_two_moons
from repro.data.stream import ArrayChunks
from repro.kernels import merge_event, ops, train_step

GAMMA = 0.5
BUDGET, BATCH, DIM, STEPS = 20, 8, 6, 64
INTERP = "pallas_interpret"


def _cfg(n_classes, maintenance="merge"):
    b = BSGDConfig(budget=BUDGET, lambda_=1e-3, gamma=GAMMA,
                   batch_size=BATCH, method="lookup-wd",
                   use_kernel_cache=True, maintenance=maintenance,
                   step_engine="pallas")
    return b if n_classes == 1 else MulticlassSVMConfig(n_classes=n_classes,
                                                        binary=b)


@pytest.fixture
def hbm_route(monkeypatch):
    """Lower a core's VMEM limit to the HBM kernel's need at the tests'
    padded shapes (slots 28 -> 128, d 6 -> 128, batch 8 -> 128), so that
    ``fused_kernel`` picks ``"hbm"`` there; compiled programs that chose
    before are dropped on the way in and out."""
    need = merge_event.vmem_need(*train_step.train_step_hbm_vmem(
        128, 128, 400, 128))
    assert need < merge_event.vmem_need(*train_step.train_step_vmem(
        128, 128, 400, 128))
    jax.clear_caches()
    monkeypatch.setattr(merge_event, "VMEM_LIMIT_BYTES", need)
    assert ops.fused_kernel(128, 128, 400, 128) == "hbm"
    yield
    jax.clear_caches()


def _stream(n_classes, key):
    n = STEPS * BATCH
    if n_classes == 1:
        return make_two_moons(key, n, noise=0.3, dim=DIM)
    return make_blobs_multiclass(key, n, DIM, n_classes=n_classes, sep=1.0)


def _assert_same(want, got):
    for name, w, g in zip(want._fields, want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)


def _one_step(n_classes, maintenance="merge"):
    """One padded fused step from a full-budget state, on a minibatch with
    its targets flipped, so that it inserts and merges."""
    cfg = _cfg(n_classes, maintenance)
    b = cfg if n_classes == 1 else cfg.binary
    x, y = _stream(n_classes, jax.random.PRNGKey(7))
    if n_classes == 1:
        st = jax.tree.map(lambda a: a[None],
                          bsgd.train_chunk(cfg, cfg.table(),
                                           bsgd.init_state(cfg, DIM),
                                           x[:96].reshape(12, BATCH, DIM),
                                           y[:96].reshape(12, BATCH),
                                           impl="ref"))
        yb = -y[96:104][None]
    else:
        st = multiclass.train_chunk_multiclass(
            cfg, cfg.table(), multiclass.init_multiclass_state(cfg, DIM),
            x[:96].reshape(12, BATCH, DIM), y[:96].reshape(12, BATCH),
            impl="ref")
        yb = -multiclass.ovr_targets(y[96:104], n_classes)
    xb = x[96:104]
    k_bb = ops.rbf_matrix(xb, xb, GAMMA, impl="ref")
    args = (*ops.pad_fused_state(st.sv_x, st.alpha, st.kmat), st.count,
            st.step, st.n_inserts, st.n_merges, xb, yb, k_bb, b.table())
    kw = dict(budget=b.budget, lambda_=b.lambda_, gamma=b.gamma,
              batch_size=b.batch_size, maintenance=b.maintenance,
              merge_batch=b.merge_batch, impl=INTERP)
    return st, args, kw


@pytest.mark.parametrize("n_classes", [1, 3])
def test_hbm_step_matches_whole_block(n_classes, request):
    """One step: the HBM kernel's padded state and counters are bitwise the
    whole-block kernel's, and the route really ran the HBM kernel."""
    st, args, kw = _one_step(n_classes)
    jaxpr = lambda: str(jax.make_jaxpr(
        lambda *a: ops.train_step_padded(*a, **kw))(*args))
    want = ops.train_step_padded(*args, **kw)
    assert "train_step_hbm_pallas" not in jaxpr()
    request.getfixturevalue("hbm_route")
    assert "train_step_hbm_pallas" in jaxpr()
    got = ops.train_step_padded(*args, **kw)
    for name, w, g in zip(("sv_x", "alpha", "kmat", "count", "step",
                           "n_inserts", "n_merges"), want, got):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=name)
    assert int(jnp.sum(want[5] - st.n_inserts)) > 0          # inserted
    assert int(jnp.sum(want[6] - st.n_merges)) > 0           # merged


def _scans(n_classes):
    """The chunk and epoch programs (``multiclass.scan_ovr``) over 64 steps."""
    cfg = _cfg(n_classes)
    x, y = _stream(n_classes, jax.random.PRNGKey(n_classes))
    xc = x.reshape(STEPS, BATCH, DIM)
    yc = y.reshape(STEPS, BATCH)
    n = STEPS * BATCH
    if n_classes == 1:
        state = bsgd.init_state(cfg, DIM)
        chunk, epoch = bsgd.train_chunk, bsgd.train_epoch
    else:
        state = multiclass.init_multiclass_state(cfg, DIM)
        chunk, epoch = (multiclass.train_chunk_multiclass,
                        multiclass.train_epoch_multiclass)
    table = cfg.table()
    return (chunk(cfg, table, jax.tree.map(jnp.array, state), xc, yc,
                  impl=INTERP),
            epoch(cfg, table, state, x, y, jnp.arange(n), impl=INTERP))


@pytest.mark.parametrize("n_classes", [1, 3])
def test_hbm_scan_matches_whole_block(n_classes, request):
    """64 steps of the chunk and epoch programs: the real state, counters
    included, is bitwise the whole-block kernel's, with merges in most
    steps."""
    want = _scans(n_classes)
    assert int(jnp.sum(want[0].n_merges)) > (STEPS // 2) * n_classes
    request.getfixturevalue("hbm_route")
    got = _scans(n_classes)
    for w, g in zip(want, got):
        _assert_same(w, g)


def _trace_kernel(fn, s, d=896, b=128, g=400):
    """Trace ``fn`` at one class of padded shapes (nothing compiles)."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    i32 = jax.ShapeDtypeStruct((1,), jnp.int32)
    jax.eval_shape(lambda *a: fn(*a, budget=s - 8, lambda_=1e-3,
                                 gamma=2.0**-10, batch_size=8, rounds=8),
                   f32(1, s, d), f32(1, 1, s), f32(1, s, s), i32, i32, i32,
                   i32, f32(b, d), f32(1, 1, b), f32(b, b), f32(g, g),
                   f32(g, g))


def test_fused_kernel_choice_at_the_mnist_shapes():
    """The whole-block kernel at the MNIST cell's padded shapes (slots
    1,152, d 896), the HBM kernel at budget 4,096 (slots 4,224); the choice
    turns where the whole-block kernel would refuse its VMEM need (past
    1,920 padded slots at d 896), and the HBM kernel takes that size."""
    assert ops.fused_kernel(1152, 896, 400, 128) == "vmem"
    assert ops.fused_kernel(4224, 896, 400, 128) == "hbm"
    assert ops.fused_kernel(1920, 896, 400, 128) == "vmem"
    assert ops.fused_kernel(2048, 896, 400, 128) == "hbm"
    _trace_kernel(train_step.train_step_pallas, 1920)
    with pytest.raises(ValueError, match="MiB limit of a TPU core"):
        _trace_kernel(train_step.train_step_pallas, 2048)
    _trace_kernel(train_step.train_step_hbm_pallas, 4224)


def test_hbm_kernel_runs_merge_only(hbm_route):
    """multi-merge rounds edit whole VMEM blocks: past the whole-block
    kernel's limit they are refused, naming why."""
    _, args, kw = _one_step(3, "multi-merge")
    with pytest.raises(ValueError, match="maintenance='merge' only"):
        ops.train_step_padded(*args, **kw)


def _stream_kernel_attr(cfg, n_classes, **kw):
    x, y = _stream(n_classes, jax.random.PRNGKey(3))
    fit = bsgd.fit_stream if n_classes == 1 else \
        multiclass.fit_multiclass_stream
    fit(cfg, ArrayChunks(x[:64], y[:64], 32), epochs=1, seed=0, **kw)
    recs = obs.RING.records()
    root = recs["span_id"][recs["name"] == "fit.stream"][-1]
    mine = recs["root_id"] == root
    assert list(recs["name"][mine]).count("fit.stream") == 1
    return ops.FUSED_KERNELS[int(recs["kernel"][recs["span_id"] == root][0])]


@pytest.mark.parametrize("n_classes", [1, 3])
def test_fit_stream_span_names_the_kernel(n_classes, monkeypatch):
    """``fit.stream`` carries which kernel trained the call: ``"ref"`` on
    the CPU's default path, else ``ops.fused_kernel`` of the padded
    state's shapes."""
    cfg = _cfg(n_classes)
    assert _stream_kernel_attr(cfg, n_classes) == "ref"
    assert _stream_kernel_attr(cfg, n_classes, impl=INTERP) == "vmem"
    picked = []
    monkeypatch.setattr(ops, "fused_kernel",
                        lambda *a, **k: picked.append(a) or "hbm")
    jax.clear_caches()
    try:
        assert _stream_kernel_attr(cfg, n_classes, impl=INTERP) == "hbm"
    finally:
        jax.clear_caches()
    assert (128, 128, 400, 128, 4) in picked
