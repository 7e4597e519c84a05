"""Persistent compilation cache for the entry points.

JAX keys its persistent cache on the directory path, so the directory must
not move between runs.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this module sets nothing; otherwise the cache lives in
``<checkout>/.jax_cache`` (git-ignored).  Entry points call
:func:`enable_compile_cache` before their first compile; the library never
calls it, and importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
