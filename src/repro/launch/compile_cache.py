"""Where JAX keeps its persistent compilation cache.

The cache directory is part of every entry's key, so it must not move
between runs: either the deployment's ``JAX_COMPILATION_CACHE_DIR`` or a
fixed directory in the checkout (``.jax_cache``, git-ignored).
"""
from __future__ import annotations

import os
import pathlib

import jax

#: the checkout's own cache directory, used when the environment names none
REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache goes to :data:`REPO_CACHE_DIR`.
    Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
