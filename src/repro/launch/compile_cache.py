"""Where entry points keep JAX's persistent compilation cache.

The cache key includes the cache directory, so the directory must not move
between runs: a fixed path inside the checkout, unless the environment
places it with ``JAX_COMPILATION_CACHE_DIR`` (which JAX reads itself).
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    it: ``$JAX_COMPILATION_CACHE_DIR`` when set (nothing else is changed),
    ``<checkout>/.jax_cache`` otherwise. Call from an entry point's
    ``main()``, never at import."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
