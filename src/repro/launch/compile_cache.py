"""Persistent XLA compile cache for the command-line entry points.

Called once at start-up by ``chip_smoke.py``, ``benchmarks/run.py`` and
``python -m repro.launch.serve`` — never at library import, so importing
``repro`` leaves JAX's configuration alone.
"""
from __future__ import annotations

import os
import pathlib

#: Fixed cache location inside the checkout (listed in ``.gitignore``). The
#: path is part of every cache key, so it never depends on a temp name, a
#: process id or the time.
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing; otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
