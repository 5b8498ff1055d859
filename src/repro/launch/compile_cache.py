"""Where the entry points keep JAX's persistent compilation cache.

A process that compiles a 32-layer model spends minutes in the compiler on
a cold start; the persistent cache lets the next process in the same
checkout read those programs back.  The cache is keyed by its directory,
so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
deployment sets it (JAX reads that variable itself, and no other
directory is set in code), else ``.jax_cache/`` at the root of the
checkout — never a temporary, per-process or timestamped name.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory
    (see the module docstring) and return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
