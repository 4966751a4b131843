"""Where jax keeps its persistent compilation cache, and what its key holds.

The cache key includes the directory, so it lives at one fixed path: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set (jax reads the
variable itself), else ``.jax_cache/`` at the root of this checkout.

The key also holds the programs' metadata: the scope paths that the step
programs' named scopes give every operation (``repro.train.step``) and their
source lines.  Left out, as jax leaves it by default, an executable compiled
from the same computation under other scopes is loaded in its place, and a
profile of the step shows that program's scope paths, not this one's.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"
METADATA_IN_KEY = "jax_compilation_cache_include_metadata_in_key"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on, keyed with the programs'
    metadata; returns its directory."""
    import jax
    jax.config.update(METADATA_IN_KEY, True)
    placed = os.environ.get(ENV_CACHE_DIR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
