"""JAX's persistent compilation cache at a fixed place.

A process that compiles the serving programs of a multi-billion-parameter
model spends minutes in XLA; with the cache on, the next process in the
same checkout reads those programs back instead.  The cache key includes
the directory, so the directory must not move between runs.
"""
import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    left to stand.  Otherwise the cache is ``<checkout>/.jax_cache``.
    Call from a program's entry point, never on import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
