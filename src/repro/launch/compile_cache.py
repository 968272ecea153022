"""JAX's persistent compilation cache for the repo's entry points.

Each entry point's ``main()`` calls :func:`enable_compile_cache` once; no
module turns the cache on at import.  Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and nothing is set here.  Otherwise the cache
goes to ``.jax_cache/`` at the repo root: a fixed path, because the path
is part of the cache key and a moving directory never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[3]
REPO_CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(CACHE_ENV):
        return os.environ[CACHE_ENV]
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
