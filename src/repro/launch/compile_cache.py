"""Persistent XLA compilation cache for the chip entry points.

`chip_smoke.py` and `benchmarks/run.py` call `enable_compile_cache()` once
at start-up, before their first compile.  Where `JAX_COMPILATION_CACHE_DIR`
is set, JAX reads it itself and nothing is set here.  Otherwise the cache
lives at one fixed path inside the checkout, `<repo>/.jax_cache` (listed
in `.gitignore`): the path is part of what a cache entry is found under,
so it is never built from a temp name, a pid or the time.  The tests do
not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
