"""Where JAX keeps its persistent compilation cache for this repository.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here.  Otherwise the cache lives in ``.jax_cache/`` at
the root of the checkout: a fixed path, because the path is part of
what a later run must match to find the compiled programs again.
``chip_smoke.py`` and the benchmarks call ``enable_compile_cache()``
once, before their first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
