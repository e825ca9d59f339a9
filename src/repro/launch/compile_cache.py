"""JAX's persistent compilation cache at a fixed place.

Entry points that compile on a chip (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) call :func:`use_compile_cache`
before their first compile, so their processes share compiled programs.
"""
from __future__ import annotations

import os
import pathlib

# the checkout root (src/repro/launch/ -> three levels up); gitignored
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> None:
    """Enable the persistent compilation cache.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it itself and
    nothing is set here. Otherwise the cache lives in ``.jax_cache/`` at
    the checkout root. The path is fixed, never derived from a temporary
    name, a pid or the time, so every process run from this checkout
    finds the entries the others wrote."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
