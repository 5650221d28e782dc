"""JAX's persistent compilation cache at a fixed place.

A cache is keyed partly by its directory, so the directory never moves: it
is ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself), and
``<repo>/.jax_cache`` otherwise — never a temporary name, a pid or a time.
Entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
