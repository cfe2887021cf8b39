"""JAX's persistent compilation cache, kept at one fixed place.

A run finds what an earlier run compiled only if both use the same
directory, so a directory built from a temporary name, a pid or the time
would never be read back.  :func:`enable_compile_cache` therefore uses
either the directory the environment names or ``.jax_cache/`` at the root
of the checkout, and nothing else.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Call it before the process compiles anything: JAX decides once, at
    the first compile, whether the cache is in use.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that variable itself
    and no other directory is set here.
    """
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    path = REPO_ROOT / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
