"""Persistent XLA compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``examples/*``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` once, before their first compile; the
library never does, and the tests run without it.  A later process
that compiles the same program (same HLO, same device, same cache
path) loads it from disk instead of recompiling -- at published widths
that is the difference between minutes and seconds per cold start.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(checkout) -> str:
    """Turn on the persistent compilation cache and return its path.

    If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here.  Otherwise the cache lives in the fixed
    directory ``<checkout>/.jax_cache`` (listed in ``.gitignore``): a
    fixed path, because the path is part of what a later run must find.
    Every compile is cached, however short.
    """
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(Path(checkout).resolve() / CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
