"""Where the entry points keep JAX's persistent compile cache.

A cold run of the trainer compiles a whole model; a warm one should read
it back.  The cache key includes the cache's path, so the path is fixed:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself, and nothing here overrides it), otherwise ``.jax_cache``
at the root of the checkout -- never a temporary name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
