"""JAX's persistent compilation cache, placed for an entry point.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile; library
code never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing else is set here.  Otherwise the cache lives at
the fixed ``.jax_cache/`` of the checkout (listed in ``.gitignore``):
the directory is part of the cache's key, so it must not move between
runs.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the cache on; returns the directory it uses."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
