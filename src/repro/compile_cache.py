"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, the ``benchmarks/`` scripts) calls
``enable_compile_cache()`` once, before its first compile:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads the cache
  there and this sets nothing;
* otherwise the cache goes to ``.jax_cache/`` at the root of the checkout,
  a fixed path (the path is part of the cache key, so a directory that
  moved between runs would never hit). Git ignores it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["DEFAULT_CACHE_DIR", "enable_compile_cache"]

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
