"""JAX's persistent compilation cache for every entry point.

A cold run of the elastic round compiles the whole chunk program; with the
cache on, a later process on the same backend loads it instead. The cache
key includes the directory, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names (which JAX reads by itself) or else
``<checkout>/.jax_cache``, never a temporary or per-process name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
