"""Persistent XLA compilation cache for the entry points.

Only entry points call :func:`use_compile_cache` (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``); importing a library module
never configures a cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout: the cache directory is part of JAX's
# cache key, so a name that changed per run would never hit.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and the
    directory is left alone; otherwise the cache goes to
    ``<repo>/.jax_cache``. Either way every compile is kept, not only those
    over JAX's default one second: a fresh process starts with nothing
    compiled, and the engine's decode step compiles in about a second.
    """
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
