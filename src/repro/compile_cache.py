"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call ``enable_compile_cache()`` once, before their first compile; the
library itself never touches the cache on import. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
is set here. Otherwise the cache lives at one fixed directory inside
the checkout: the directory is part of the cache key, so a path that
moved between runs (a temp, pid or time-stamped one) would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
