"""Where JAX keeps its persistent compilation cache.

Every program entry point (``chip_smoke.py``, ``python -m
repro.launch.train``, ``benchmarks/run.py``, the example trainers and the
test suite) calls :func:`enable_compile_cache` before its first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout root: ``src/repro/launch/cache.py`` -> three levels up.
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads the
    variable itself, and no other directory is set in code. Otherwise the
    cache is ``<checkout>/.jax_cache`` — a fixed path, because the path is
    part of what a later run must find again.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
