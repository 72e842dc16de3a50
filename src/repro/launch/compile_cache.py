"""Where JAX keeps its persistent compilation cache.

The cache key includes the directory, so the directory must not move
between runs: either the one the environment names or a fixed
``.jax_cache/`` at the root of this checkout, derived from this file's own
location.  Entry points call ``use_compile_cache()`` before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Return the cache directory in use.  ``JAX_COMPILATION_CACHE_DIR``,
    where set, is left to JAX (which reads it itself); otherwise the cache
    goes to ``CHECKOUT_CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
