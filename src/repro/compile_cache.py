"""Where JAX's persistent compilation cache lives.

Entry points (the training and serving CLIs, ``chip_smoke.py``; the
benchmark under ``bench/`` keeps its own copy) call ``enable_compile_cache`` once before they compile.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here.  Otherwise the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because the path is part of how a cached program is found
again, so a temporary or per-process directory would never hit.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE)
    return CHECKOUT_CACHE
