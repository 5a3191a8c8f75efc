"""Process-level JAX settings the repo shares: the float64 scope of the
device engine and the persistent compilation cache of the entry points.

Nothing here runs at import.  The engine calls :func:`x64` around each
kernel launch; the entry points (``chip_smoke.py``, ``benchmarks/run.py``,
the examples) call :func:`init_compile_cache` once, before their first
compile.
"""

from __future__ import annotations

import os
import pathlib
from typing import ContextManager

import jax

#: fixed in-checkout cache path: the directory is part of the cache key, so
#: a path that moved (tmp dirs, pids, timestamps) would never hit
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def x64() -> ContextManager:
    """Scope in which the co-simulation kernels trace and run in float64.

    The scan and vmapped search kernels are pinned to the numpy float64
    reference to <= 1e-10, so every launch and every ``jnp.asarray`` that
    feeds one happens inside this scope."""
    return jax.enable_x64(True)


def init_compile_cache() -> pathlib.Path:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing; otherwise the cache goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return pathlib.Path(env)
    DEFAULT_CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR

