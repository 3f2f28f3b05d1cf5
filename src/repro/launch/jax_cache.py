"""JAX's persistent compilation cache, kept at one fixed place.

A directory made per run (temporary, pid- or time-based) is never found
again by the next run, so the place is fixed. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
this module sets nothing; otherwise the cache lives in ``.jax_cache/`` at
the root of the checkout. Entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.*``) call :func:`use_persistent_cache`
before their first compile; library code and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
