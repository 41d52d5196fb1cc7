"""JAX's persistent compilation cache for the launch entry points.

``JAX_COMPILATION_CACHE_DIR``, when it is set, places the cache (JAX reads
the variable itself).  Otherwise it is ``.jax_cache/`` at the root of the
checkout: a fixed path, since the path is part of the cache's key.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["CHECKOUT_CACHE", "enable_compile_cache"]
