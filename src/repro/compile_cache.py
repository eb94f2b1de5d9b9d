"""Persistent XLA compilation cache for the command-line entry points.

``chip_smoke.py``, ``examples/*.py`` and the ``benchmarks/*`` mains call
``enable_compile_cache()`` once, first thing in ``main``; library code,
imports and tests never do.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing is changed here.  Otherwise the cache goes
to one fixed directory inside the checkout (git-ignored): the cache key
includes nothing about where the process runs, but a directory that moved
between runs would never be found again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the checkout's own cache directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
