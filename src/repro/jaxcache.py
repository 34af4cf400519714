"""JAX's persistent compilation cache, for the program's entry points.

``chip_smoke.py``, the benchmarks and the examples call :func:`enable`
once, before their first compile; library modules never do (importing
``repro`` changes no JAX setting).  The cache directory is
``$JAX_COMPILATION_CACHE_DIR`` when that is set, and otherwise the fixed
``.jax_cache/`` at the root of the checkout (git-ignored): the path is
part of what a cached program is found by, so it never moves between runs.
"""
from __future__ import annotations

import os
import pathlib

ENV = "JAX_COMPILATION_CACHE_DIR"

#: where the cache lives when the environment names no directory
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Point JAX's persistent compilation cache at its directory and cache
    every compiled program, however quick its compile; returns the
    directory."""
    import jax

    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
