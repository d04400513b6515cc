"""Production meshes.  Functions, not module constants, so importing this
module never touches jax device state (smoke tests must keep seeing 1 CPU)."""
from __future__ import annotations

import jax


def make_mesh(shape, axes):
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; multi-pod adds a leading pure-DP pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist right now, as a 1-D data mesh (CPU tests)."""
    n = len(jax.devices())
    return make_mesh((n,), ("data",))
