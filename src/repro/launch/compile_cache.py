"""JAX's persistent compilation cache, placed from outside the program.

Entry points call :func:`enable_compile_cache` from their ``main()``, never
on import.  If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
nothing is set here; otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` (a fixed path, because the directory is part of what
a later run must find again).
"""
from __future__ import annotations

import os
import pathlib

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache; returns the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
