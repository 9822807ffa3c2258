"""Where JAX keeps its persistent compilation cache for the launchers.

A cache directory is part of the cache key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` wins when it is set (JAX reads it
itself), otherwise the cache lives at a fixed ``.jax_cache`` in the checkout.
Called by the launchers' ``main`` and by ``chip_smoke.py``, never on import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
