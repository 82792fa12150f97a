"""Where JAX keeps its persistent compilation cache.

Every launcher calls ``enable_compile_cache()`` before its first compile.
When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets nothing; otherwise the cache goes to ``<checkout>/.jax_cache``, a
fixed path (the directory is part of the cache key, so a path that moves
between runs never hits).
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
