"""JAX persistent compilation cache location.

A full-width step takes tens of seconds to compile; the persistent cache lets
the next process on the same machine load it instead.  The cache key
includes the directory, so the path must not move between runs: it is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise one fixed
directory inside the checkout (``.jax_cache``, git-ignored).

Call :func:`enable_compilation_cache` from an entry point's ``main``, before
the first compile — never at import time.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Point JAX's persistent compilation cache at ``$ENV_VAR``, else at
    ``DEFAULT_DIR``, and return that path."""
    import jax

    path = os.environ.get(ENV_VAR) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
