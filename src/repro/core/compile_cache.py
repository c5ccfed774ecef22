"""JAX's persistent compilation cache, at a path that does not move.

The cache directory is part of what a later process looks up, so it must be
the same from run to run: ``JAX_COMPILATION_CACHE_DIR`` where it is set, and
otherwise ``.jax_cache/`` at the root of this checkout — never a temporary
name, a pid or a time.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: ``.jax_cache/`` beside ``src/`` (this file is ``src/repro/core/…``).
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; call before the first
    compile. Returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
