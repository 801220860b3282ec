"""Persistent XLA compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` once, before their first
compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here.  Otherwise the cache goes to a fixed directory
inside the checkout (``.jax_cache/``, listed in ``.gitignore``): the path is
part of the cache key, so it is never built from a temporary name, a
process id or the time.  Tests do not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
