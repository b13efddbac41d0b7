"""JAX's persistent compilation cache, for the command-line entry points.

``enable_compile_cache()`` is called by ``chip_smoke.py``,
``python -m repro.serve``, ``python -m repro.obs`` and
``benchmarks/run.py`` before they compile anything, and never on import:
a library user decides about caching in their own process.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets no other directory.  Otherwise the cache lives at one fixed directory
inside the checkout (``.jax_cache/``, listed in ``.gitignore``): the path is
part of the cache key, so a directory that moved would never hit.
"""

from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Give the persistent compilation cache its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
