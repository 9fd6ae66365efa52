"""Where JAX keeps its persistent compilation cache.

A cold run compiles every kernel of every planned network; the cache lets
the next process on the same machine load them instead. The cache key
includes the directory, so it lives at a fixed path: the one
``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads the variable
itself, and nothing here overrides it), otherwise ``.jax_cache`` at the
root of the checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call ``enable``;
nothing calls it at import time, so a process that only compiles for a
described chip (the compile tests) never reads entries it cannot load.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_enable_compilation_cache", True)
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
