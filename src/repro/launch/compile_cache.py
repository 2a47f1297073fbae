"""JAX's persistent compilation cache for the launchers.

A cold run compiles every program; the cache lets the next process on the
same machine load them instead.  The cache key includes the directory, so
it lives at a fixed path: ``$JAX_COMPILATION_CACHE_DIR`` when that is set
(JAX reads it itself), otherwise ``.jax_cache`` at the root of the
checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
