"""Where JAX's persistent compilation cache lives.

Entry points (``chip_smoke.py``, :mod:`repro.launch.serve`,
:mod:`repro.launch.train`) call :func:`enable_compile_cache` once at start;
importing a library module never touches the cache.  If
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing here
overrides it.  Otherwise the cache goes to one fixed directory inside the
checkout, ``<repo>/.jax_cache`` (git-ignored): the directory is part of the
cache key, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
