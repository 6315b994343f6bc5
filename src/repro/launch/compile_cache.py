"""Where JAX keeps its persistent compilation cache.

A program run as a driver (``chip_smoke.py``, the ``launch/`` entry
points) calls ``enable_compile_cache()`` first. The cache's path is
part of what makes an entry found again, so it is fixed: the
directory ``JAX_COMPILATION_CACHE_DIR`` names, when it is set (JAX
reads that variable itself), and otherwise ``.jax_cache/`` at the root
of this checkout (gitignored). Tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
