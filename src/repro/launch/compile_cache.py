"""JAX's persistent compilation cache for the command-line entry points.

Called from each ``main()``, never at import: importing a library module
must not change JAX's configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the repository root
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other directory is set here.  Otherwise the cache is ``.jax_cache/`` at
    the repository root: a fixed path, since the path is part of what a
    later run must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
