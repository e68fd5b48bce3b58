"""Where the persistent compilation cache lives.

The path is part of what makes a cache entry findable again, so it is never
a temporary name, a pid or a time: either the one the environment gives
(``JAX_COMPILATION_CACHE_DIR``, which JAX reads itself — nothing is set in
code then) or ``<checkout>/.jax_cache``.  Every entry point that compiles
calls :func:`enable_compile_cache` before its first jit.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
