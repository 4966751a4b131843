"""Where jax keeps its persistent compilation cache.

The cache key includes the directory, so it lives at one fixed path: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set (jax reads the
variable itself), else ``.jax_cache/`` at the root of this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    placed = os.environ.get(ENV_CACHE_DIR)
    if placed:
        return placed
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
