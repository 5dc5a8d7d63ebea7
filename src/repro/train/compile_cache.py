"""JAX's persistent compilation cache, kept at one fixed place.

The cache key includes the cache directory, so a directory that moves
between runs never hits.  :func:`enable_compile_cache` leaves the directory
to ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself) and
otherwise points JAX at ``<repo>/.cache/jax`` — the same path on every run.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR_ENV_VAR", "DEFAULT_COMPILE_CACHE_DIR",
           "enable_compile_cache"]

CACHE_DIR_ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <repo>/src/repro/train/compile_cache.py -> <repo>/.cache/jax
DEFAULT_COMPILE_CACHE_DIR = (
    pathlib.Path(__file__).resolve().parents[3] / ".cache" / "jax")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; print and return its
    directory."""
    cache_dir = os.environ.get(CACHE_DIR_ENV_VAR)
    if not cache_dir:
        cache_dir = str(DEFAULT_COMPILE_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    print(f"jax compilation cache: {cache_dir}", flush=True)
    return cache_dir
