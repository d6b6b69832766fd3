"""Where JAX keeps its persistent compilation cache for this repo's entry
points (``chip_smoke.py``, ``launch/serve_events.py``, ``benchmarks/run.py``).

The cache directory is part of what a later process must find again, so it
is a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself), else ``<repo root>/.jax_cache``.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"
_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get(_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
