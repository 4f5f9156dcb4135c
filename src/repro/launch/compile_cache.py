"""Persistent XLA compile cache for the command-line entry points.

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself; where it is set, nothing
here changes it.  Where it is not, :func:`enable_compile_cache` points
the cache at ONE fixed directory inside the checkout (``.jax_cache/``,
listed in ``.gitignore``).  The path is part of every cache key, so it
never depends on a temporary name, a pid or the clock: a second run of
the same checkout finds what the first one compiled.

Entry points (``chip_smoke.py``, ``python -m repro.launch.fl_sim``) call
it at the top of ``main()``.  Importing the package never does, so the
tests stay uncached.
"""
from __future__ import annotations

import os
from pathlib import Path

#: src/repro/launch/compile_cache.py -> the checkout root
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
