"""JAX's persistent compilation cache, placed from outside the library.

Scripts (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``) call
``enable_compile_cache()`` once at start-up; no library module calls it at
import.  The cache key includes the directory, so the path is fixed: the
one ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads it
itself), otherwise ``<checkout>/.jax_cache`` — never a temporary name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on for every compile, small kernels
    included; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
