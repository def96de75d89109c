"""Persistent XLA compilation cache for the program's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache`` once at start-up; library code and tests never do.
A deployment places the cache with ``JAX_COMPILATION_CACHE_DIR``, which JAX
reads by itself. Without it the cache lives at ``<checkout>/.jax_cache``: a
fixed path, because the path is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent cache; returns the directory in use."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
