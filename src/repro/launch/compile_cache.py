"""JAX's persistent compilation cache for the entry points.

A full-width train step takes minutes to compile; the cache makes the next
process on the same checkout skip that.  The cache key includes the
directory, so it lives at a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when
set (jax reads it itself, and nothing else is set here), otherwise
``<repo>/.jax_cache``.  Called from ``main()``s only, never at import.
"""
from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Point the persistent cache at its fixed directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
