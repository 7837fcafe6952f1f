"""JAX's persistent compilation cache for the entry points.

Each entry point (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``)
calls :func:`setup_compile_cache` once, before its first compile, so that
the processes of one machine share compiled programs.  No module sets the
cache while it is imported.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed and inside the checkout (git-ignored): the path is part of the
# cache's key, so a directory that moved between runs would never hit
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point the cache at ``$JAX_COMPILATION_CACHE_DIR`` when it is set,
    otherwise at ``<checkout>/.jax_cache``; returns the directory."""
    path = os.environ.get(ENV_VAR) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
