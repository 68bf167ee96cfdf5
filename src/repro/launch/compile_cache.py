"""JAX's persistent compilation cache, set up one way for every entry
point (``chip_smoke.py``, ``benchmarks/run.py``).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache lives at the fixed, git-ignored
``<checkout>/.jax_cache``: a fixed path, because the directory is part of
what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn the persistent cache on before the first compile; returns
    the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    # cache every executable, however quick its compile or small its size
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(CHECKOUT_CACHE_DIR)
