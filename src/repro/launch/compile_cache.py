"""JAX's persistent compilation cache, kept at one fixed path.

A run finds what an earlier run cached only at the same directory, so the
path is fixed: never a temp dir, a pid or a timestamp.  Entry points call ``enable_compile_cache``
once, before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on -> the directory it uses.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    other path is set here; otherwise the cache lives in ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
