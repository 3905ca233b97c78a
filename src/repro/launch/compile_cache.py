"""Persistent XLA compile cache, placed from outside the program.

Called by entry points (``chip_smoke.py``, ``launch/serve.py``,
``benchmarks/run.py``), never at import: importing a module must not
change process-wide JAX configuration.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["configure_compile_cache", "DEFAULT_CACHE_DIR"]

# fixed and inside the checkout: the cache key includes nothing about the
# directory, but a directory that moves (a temp name, a pid, a date)
# would never be found again by the next run
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory and return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set.  Otherwise the cache goes to the fixed
    in-checkout ``DEFAULT_CACHE_DIR``.
    """
    # the perception programs compile in well under JAX's default 1 s
    # minimum, below which nothing is written; a second run would then
    # recompile every rung from scratch
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
