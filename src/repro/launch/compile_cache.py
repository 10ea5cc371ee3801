"""Persistent XLA compilation cache for the entry points.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/faces_worker.py``) calls
:func:`enable_compile_cache` before its first compile. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and the cache
stays there. Otherwise the cache lives at a fixed path inside the
checkout (``.jax_compile_cache/``, ignored by git): the directory is
part of what makes a later process find an entry, so it is never built
from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(ROOT, ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Every
    program is cached, however quickly it compiled, so a second run of
    the same entry point compiles nothing new."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
