"""Where JAX's persistent compilation cache lives.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.  ``JAX_COMPILATION_CACHE_DIR``
wins when it is set (JAX reads it itself).  Otherwise the cache sits at a
fixed directory inside the checkout: the path is part of the cache key, so
a path built from a temp name, a pid or the time would never hit.
"""

from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
