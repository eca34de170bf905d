"""JAX's persistent compilation cache for the entry points.

A cold 32-layer serve step compiles for minutes on a TPU; the persistent
cache lets a later process (or a later run on the same machine) load the
executable instead.  The cache key includes its directory, so the
directory must not move between runs: it is either what
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself) or
the fixed ``.jax_cache/`` at the checkout root — never a temp-, pid- or
time-derived path.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    Leaves a set ``JAX_COMPILATION_CACHE_DIR`` alone; otherwise points
    ``jax_compilation_cache_dir`` at ``CACHE_DIR``.  Call it before the
    first compile of the process."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
