"""JAX's persistent compilation cache at a fixed place.

Launchers call :func:`use_compile_cache` at the start of ``main`` (never
at import), so a second run of the same program on the same device reads
its executables back instead of recompiling them.
"""
from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Returns the cache directory in effect.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own to handle; else
    the cache lives at ``<checkout>/.jax_cache``. The path is fixed because
    it is part of the cache key: a directory that moves never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
