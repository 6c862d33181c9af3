"""JAX's persistent compilation cache, placed from outside or at one fixed path.

Entry points that compile full-size programs (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``) call :func:`enable_compile_cache`
once, before their first compile.  A directory named in
``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads that variable itself, so no
other directory is set in code.  Otherwise the cache lives at
``<checkout>/.jax_cache``: a fixed path, because a run that caches under a
new directory each time never hits.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
