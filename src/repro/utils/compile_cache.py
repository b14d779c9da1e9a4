"""Persistent JAX compilation cache at one fixed place.

A cold run on the chip compiles every program, and compilation is a large
part of a short run.  :func:`enable_compilation_cache` points JAX's
persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` when that is set (and at
no other directory), and otherwise at ``<repo>/.jax_cache``.  The path never
depends on a temporary name, a process id or the time: it is part of the
cache's key, so a directory that moved would never hit.
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Turn the persistent compilation cache on before the first compile;
    every program is cached, however quick it was to compile.  Returns the
    directory in use."""
    path = os.environ.get(ENV_VAR) or REPO_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
