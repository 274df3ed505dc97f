"""Where JAX's persistent compilation cache lives for this checkout.

Entry points call :func:`use_compile_cache` once, at the start of ``main``
(never at import).  A fixed path matters: the cache directory is part of
what a later run must find again, so a temporary name, a process id or a
timestamp would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <checkout>/src/repro/launch/compile_cache.py -> <checkout>/.jax_cache
CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
    sets nothing else; otherwise the cache goes to ``.jax_cache/`` at the
    checkout root."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
