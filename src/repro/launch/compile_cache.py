"""Where JAX keeps its persistent compilation cache.

The cache directory is part of each entry's key, so it must not move
between runs: a temp name, a pid or a time in the path would never hit.
"""

from __future__ import annotations

import os

import jax

# the checkout root: src/repro/launch/compile_cache.py -> four levels up
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
