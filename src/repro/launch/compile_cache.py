"""Where JAX's persistent compilation cache lives for the entry points.

Scripts (``chip_smoke.py``, the benchmarks, the examples) call
:func:`setup_compile_cache` once before their first compile; importing
``repro`` never does, so tests keep the cache off unless they turn it on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "setup_compile_cache"]

# fixed, inside the checkout and ignored by git: the path is part of the
# cache key, so a directory that moved between runs would never hit
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Use ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it
    itself; nothing is changed), else :data:`CHECKOUT_CACHE_DIR`.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
