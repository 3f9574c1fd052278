"""Where JAX keeps its persistent compilation cache for this repo's
entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``).

A call on a fresh machine compiles every fixpoint loop; a second process
with the same programs reads them back from the cache instead.  The
cache's key includes its path, so the path is fixed: never built from a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def checkout_cache_dir() -> str:
    """``<checkout>/.jax_cache`` (git-ignored)."""
    here = os.path.dirname(os.path.abspath(__file__))      # <root>/src/repro
    return os.path.join(os.path.dirname(os.path.dirname(here)),
                        ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable itself
    and this sets nothing; otherwise the cache goes to the checkout's
    ``.jax_cache``."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    path = checkout_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
