"""JAX's persistent compilation cache, at one fixed place.

Entry points (``repro.launch.serve``, ``chip_smoke.py``, ``benchmarks.run``)
call ``enable_compile_cache()`` from their ``main``; importing this module
changes nothing. A later process on the same machine then loads what an
earlier one compiled instead of compiling it again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

# Fixed, in the checkout: the directory is part of what a later process
# looks up, so a per-run temp name would never hit.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    when that is set, else at ``DEFAULT_CACHE_DIR``; returns the directory."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir
