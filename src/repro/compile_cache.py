"""JAX's persistent compilation cache at a fixed, placeable path.

Entry points that compile for a device call :func:`enable_compile_cache`
once, before their first compile. ``JAX_COMPILATION_CACHE_DIR``, when
set, is read by JAX itself and wins: nothing here overrides it. Without
it the cache lives in ``<root>/.jax_cache`` — a fixed directory inside
the checkout (git-ignored), so a second run of the same program on the
same machine reads back what the first one compiled. The path never
depends on a temporary name, a process id or the time: the directory is
part of the cache's key, and a moving one never hits.
"""
from __future__ import annotations

import os

import jax

CACHE_SUBDIR = ".jax_cache"


def enable_compile_cache(root: str) -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(root), CACHE_SUBDIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
