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
