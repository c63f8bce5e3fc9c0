"""JAX's persistent compilation cache for this repo's entry points.

Compiling a full-width serving step takes seconds to minutes on a TPU; the
persistent cache lets a second process (or a second run) reuse it. Only
entry points call :func:`enable_compile_cache` — ``launch/serve.py``,
``benchmarks/run.py`` and ``chip_smoke.py`` — so importing :mod:`repro`
(tests included) never turns the cache on.

The directory is ``$JAX_COMPILATION_CACHE_DIR`` when that is set, and
otherwise the fixed ``<checkout>/.jax_cache`` (listed in ``.gitignore``):
the cache is keyed partly by its path, so the default is resolved from
this file's location, never from a temp name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir() -> str:
    """The cache directory entry points use (module docstring)."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    return str(Path(__file__).resolve().parents[2] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at
    :func:`compile_cache_dir` and return that directory."""
    path = compile_cache_dir()
    jax.config.update("jax_compilation_cache_dir", path)
    return path
