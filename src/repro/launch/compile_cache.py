"""Persistent XLA compile cache for the entry points (train, serve and
``chip_smoke.py``).

JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set this
sets nothing.  Otherwise the cache lives at one fixed path inside the
checkout, ``<checkout>/.jax_cache`` (git-ignored): the same directory
from every entry point and every working directory, so a later process
finds what an earlier one compiled.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at :data:`CACHE_DIR` unless
    ``JAX_COMPILATION_CACHE_DIR`` chooses one; returns the directory in
    use.  Call it before the first compile of the process."""
    chosen = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if chosen:
        return chosen
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
