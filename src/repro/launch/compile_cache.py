"""JAX's persistent compilation cache at a fixed path.

Entry points (chip_smoke.py, launch/serve.py, the benchmarks) call
`enable_compile_cache()` once at start-up; importing this module changes
nothing. The cache key includes the directory, so the path is fixed.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Where
    JAX_COMPILATION_CACHE_DIR is set, jax already reads it and nothing is
    set here; otherwise the cache lives at `<checkout>/.jax_cache`."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
