"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

The interpret-mode sweeps in this directory never run the Mosaic compiler,
and Mosaic refuses what the interpreter accepts: a float iota, a 1-D
concatenate, a block off the (8, 128) tiling, a scalar stored to VMEM, more
VMEM than a core has.  These tests compile each kernel, through
``kernels.ops`` with ``impl="pallas"``, at the widths ``chip_smoke.py``
trains at, for a v5e chip described by ``jax.experimental.topologies``
(nothing runs), and check that the kernel is in the compiled program.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from helpers.hlo import ops_on_shapes
from repro.core.lookup import MergeLookupTable
from repro.kernels import ops

G = 400                       # lookup-table grid (the paper's 400 x 400)
C, S, D, B = 16, 1032, 512, 8  # class-axis phase: budget 1024 + batch 8
S1, D1 = 1025, 18              # binary SUSY phase: budget 1024 + batch 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e device, with the persistent compile cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _lower(fn, *shapes):
    return jax.jit(fn).lower(*shapes)


def _table(sd):
    return MergeLookupTable(sd((G, G)), sd((G, G)))


def _merge_event(sd, c, s, d):
    return _lower(
        lambda sv, a, km, cnt, ov, t: ops.merge_event(
            sv, a, km, cnt, ov, t, impl="pallas"),
        sd((c, s, d)), sd((c, s)), sd((c, s, s)), sd((c,), jnp.int32),
        sd((c,), jnp.bool_), _table(sd))


def _train_step(sd, c, s, d, maintenance):
    i32 = lambda: sd((c,), jnp.int32)
    return _lower(
        lambda sv, a, km, cnt, st, ni, nm, xb, yb, kbb, t: ops.train_step(
            sv, a, km, cnt, st, ni, nm, xb, yb, kbb, t, budget=s - B,
            lambda_=1e-5, gamma=2.0**-7, batch_size=B,
            maintenance=maintenance, impl="pallas"),
        sd((c, s, d)), sd((c, s)), sd((c, s, s)), i32(), i32(), i32(),
        i32(), sd((B, d)), sd((c, B)), sd((B, B)), _table(sd))


CASES = {
    "rbf_matrix-binary": lambda sd: _lower(
        lambda x, y: ops.rbf_matrix(x, y, 2.0**-7, impl="pallas"),
        sd((1, D1)), sd((S1, D1))),
    "rbf_matrix-classes": lambda sd: _lower(
        lambda x, y: ops.rbf_matrix(x, y, 2.0**-7, impl="pallas"),
        sd((B, D)), sd((C * S, D))),
    "merge_scores-row": lambda sd: _lower(
        lambda a, k, v, m, t: ops.merge_scores(a, k, v, m, t, impl="pallas"),
        sd((S1,)), sd((S1,)), sd((S1,), jnp.bool_), sd(()), sd((G, G))),
    "merge_scores-classes": lambda sd: _lower(
        lambda a, k, v, m, t: ops.merge_scores(a, k, v, m, t, impl="pallas"),
        sd((C, S)), sd((C, S)), sd((C, S), jnp.bool_), sd((C,)),
        sd((G, G))),
    "multi_merge_scores": lambda sd: _lower(
        lambda a, k, v, m, t: ops.multi_merge_scores(a, k, v, m, t,
                                                     impl="pallas"),
        sd((S,)), sd((4, S)), sd((4, S), jnp.bool_), sd((4,)), _table(sd)),
    "gss_solve": lambda sd: _lower(
        lambda m, k: ops.gss_solve(m, k, n_iters=10, impl="pallas"),
        sd((4096,)), sd((4096,))),
    "merge_event-classes": lambda sd: _merge_event(sd, C, S, D),
    "merge_event-binary": lambda sd: _merge_event(sd, 1, S1, D1),
    "train_step-merge": lambda sd: _train_step(sd, C, S, D, "merge"),
    "train_step-multi-merge": lambda sd: _train_step(sd, 2, 264, D,
                                                     "multi-merge"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    sd = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    compiled = CASES[name](sd).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_kernel_over_vmem_names_the_limit(one_chip):
    """A class block too large for a core's VMEM is refused up front with
    the limit in the message, not by an obscure Mosaic failure."""
    sd = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    with pytest.raises(ValueError, match="MiB limit of a TPU core"):
        _merge_event(sd, 2, 4096, D)


def _chunk_program(sd, c, s, d):
    """The compiled text of the fused chunk program at ``c`` classes of
    ``s`` slots (budget + batch) and ``d`` features, and how many pads,
    slices and copies of a state-sized buffer it holds outside and inside
    its loop."""
    from repro.core import BSGDConfig, MulticlassSVMConfig
    from repro.core.bsgd import SVMState
    from repro.core.multiclass import train_chunk_multiclass
    cfg = MulticlassSVMConfig(n_classes=c, binary=BSGDConfig(
        budget=s - B, lambda_=1e-3, gamma=2.0**-10, batch_size=B,
        use_kernel_cache=True, step_engine="pallas"))
    i32 = lambda: sd((c,), jnp.int32)
    state = SVMState(sv_x=sd((c, s, d)), alpha=sd((c, s)), count=i32(),
                     step=i32(), n_inserts=i32(), n_merges=i32(),
                     kmat=sd((c, s, s)))
    text = train_chunk_multiclass.lower(
        cfg, _table(sd), state, sd((4, B, d)), sd((4, B), jnp.int32),
        impl="pallas").compile().as_text()
    sp, dp = -(-s // 128) * 128, -(-d // 128) * 128
    return text, ops_on_shapes(text, ("pad", "slice", "copy"),
                               {(c, s, s), (c, s, d), (c, sp, sp),
                                (c, sp, dp)})


def test_chunk_program_carries_state_without_copies(one_chip):
    """The compiled chunk program of the fused step keeps the lane-padded
    state in its loop: the kernel updates the carried bank and cache in
    place, with no pad, slice or copy of either per step."""
    sd = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    _, count = _chunk_program(sd, C, S, D)
    assert all(inside == 0 for _, inside in count.values()), count
    assert count["pad"][0] == 2 and count["slice"][0] == 2, count


def test_chunk_program_past_one_vmem_block_keeps_cache_in_hbm(one_chip):
    """At budget 4,096 and 784 features (the mnist8m cell) a class's cache
    is past one VMEM block: the chunk program compiles with the HBM kernel
    in place of the whole-block one, and its loop still holds no pad,
    slice or copy of the (C, S, S) cache or the (C, S, d) bank."""
    sd = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    text, count = _chunk_program(sd, 10, 4096 + B, 784)
    assert "train_step_hbm_pallas" in text
    assert "train_step_pallas" not in text
    assert all(inside == 0 for _, inside in count.values()), count
    assert count["pad"][0] == 2 and count["slice"][0] == 2, count
