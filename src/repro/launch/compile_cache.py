"""The persistent compilation cache of the entry points.

``enable_compile_cache()`` is called by each entry point (``chip_smoke.py``,
``benchmarks.run``, ``repro.launch.train``, ``repro.launch.serve``) before
it compiles anything — never when a module is imported, and never by the
tests. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing else is configured. Otherwise the cache lives at one fixed path in
the checkout, ``.jax_cache/`` (listed in ``.gitignore``): the path is part
of the cache key, so a directory that moves never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    # the sweep programs are worth caching even when they compile quickly:
    # a chip call starts cold, and every second of it is charged
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CHECKOUT_CACHE_DIR)
