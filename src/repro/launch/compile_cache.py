"""JAX's persistent compilation cache for the entry points.

Every packet size of a range kernel is its own executable, so a cold run
recompiles the whole suite; the persistent cache lets the next process
load them instead.  The cache directory is part of JAX's key, so it never
moves: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads it itself), else ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.  Call before
    the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        # per-packet kernels compile in well under JAX's 1 s default
        # threshold; store them all
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
