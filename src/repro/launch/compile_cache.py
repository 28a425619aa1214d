"""JAX's persistent compilation cache at one fixed place.

A cold run at published widths spends minutes compiling. The cache keys
include its directory, so the directory must not move between runs: it is
never built from a temp name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the cache on before the first compile; returns its directory.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already reads it and this
    sets nothing else. Otherwise the cache lives at `<checkout>/.jax_cache`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
