"""Where JAX keeps its persistent compilation cache.

Entry points (``chip_smoke.py``, ``repro.launch.serve``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before
their first compile; library imports never touch the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# a fixed path inside the checkout: the directory is part of the cache
# key, so it must not move between runs
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is where JAX already keeps the
    cache, and no other directory is set.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``.
    """
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
