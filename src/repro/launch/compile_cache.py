"""JAX's persistent compilation cache for the entry points.

Each entry point (``chip_smoke.py``, ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` once at start-up; importing this module does
nothing. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and no directory is set in code. Otherwise the cache lives at one fixed
path inside the checkout: the path is part of the cache's key, so a
directory named after a process, a temporary or the time would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
DEFAULT_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
