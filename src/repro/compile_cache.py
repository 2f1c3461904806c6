"""Persistent XLA compile cache for the entry-point scripts.

``chip_smoke.py`` and the ``benchmarks/*.py`` mains call
:func:`enable_compile_cache` before their first compile; importing
``repro`` alone never touches the cache, so the test suite runs without one.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
helper sets nothing.  Otherwise the cache goes to one fixed directory inside
the checkout (``<repo>/.jax_cache``, gitignored): the directory is part of
each entry's key, so a path built from a temp name, pid or time would never
hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
