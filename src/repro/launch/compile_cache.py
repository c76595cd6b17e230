"""JAX's persistent compilation cache for the entry points.

``JAX_COMPILATION_CACHE_DIR``, when set, is used as JAX itself reads it and
nothing is set in code. Otherwise the cache lives at a fixed ``.jax_cache/``
in the checkout (git-ignored). The path is part of what makes a cache entry
findable again, so it is never built from a temporary name, a pid or the
time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def compile_cache_dir() -> str:
    """The directory the persistent compilation cache uses."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at `compile_cache_dir` and
    return it. Call once at the start of a process, before compiling."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
