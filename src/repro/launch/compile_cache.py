"""Persistent XLA compilation cache for the entry points.

Every launcher (``chip_smoke.py``, ``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``) calls :func:`enable_compile_cache` at start-up,
before anything compiles.  ``JAX_COMPILATION_CACHE_DIR``, when set, places
the cache: JAX reads the variable itself and nothing here overrides it.
Otherwise the cache lives in ``.jax_cache/`` at the checkout root.  The
path is fixed because it is part of what lets a later run find the
entries an earlier one wrote.  Tests do not call this;
``JAX_ENABLE_COMPILATION_CACHE=false`` keeps a launcher run by a test off
the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    cache every compilation, kernels included.  Returns the directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
