"""Persistent XLA compilation cache for the programs that run on a chip.

Called from entry points (``chip_smoke.py``, ``benchmarks/run.py``),
never when ``repro`` is imported: a library that moved the cache of
whoever imports it would surprise its callers, and the test suite keeps
no cache at all.
"""
from __future__ import annotations

import os

import jax

# A fixed directory inside the checkout: the path is part of the cache
# key, so a directory that moved between runs would never hit.  Listed
# in .gitignore.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, os.pardir,
    os.pardir, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its
    cache there and nothing is changed.  Otherwise the cache goes to
    ``CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.normpath(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
