"""Persistent XLA compilation cache for the repository's entry points.

``chip_smoke.py``, ``examples/placement_search.py`` and the benchmark
scripts call :func:`use_compile_cache` before their first compile, so a
second run on the same machine reads its executables back instead of
compiling them again.  Importing the library never calls it: the test
suite compiles uncached.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing.  Otherwise the cache lives at ``<checkout>/.jax_cache``
— a fixed path, since the directory is part of what a later run must find.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["use_compile_cache", "DEFAULT_CACHE_DIR"]

#: ``src/repro/compile_cache.py`` → the checkout root's ``.jax_cache``.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def use_compile_cache() -> str:
    """Turn the persistent cache on; → the directory it writes to."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
