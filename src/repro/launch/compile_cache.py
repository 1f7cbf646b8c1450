"""Where JAX keeps its persistent compilation cache.

Every entry point that compiles for a chip (``chip_smoke.py``, the
serve and train launchers, the benchmark harness) calls
:func:`enable_compile_cache` once, before its first compile.  Tests do
not: they compile small programs on the CPU and must not write a cache.
"""
from __future__ import annotations

import os

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing is changed.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, since the path is part of
    what a later run must find again."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
