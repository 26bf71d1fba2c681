"""JAX's persistent compilation cache, placed from outside.

Launchers and ``chip_smoke.py`` call :func:`enable_compile_cache` first
thing in ``main``; nothing calls it at import time, so tests never get it.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no path
is set in code. Otherwise the cache lives at ``<checkout>/.jax_cache`` — a
fixed path, because the path is part of what a cached entry is found by.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "CACHE_DIR"]

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
