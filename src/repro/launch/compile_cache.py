"""Persistent XLA compilation cache at a fixed place.

Entry points (``chip_smoke.py``, ``launch/serve.py``, the ``examples/``
CLIs and the ``benchmarks/`` mains) call :func:`enable_compile_cache`
before their first compile; importing the library never does, so the
tests compile without a cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it itself and the directory is left alone.  Otherwise the
cache lives in ``.jax_cache/`` at the root of the checkout: a fixed path,
since the directory is part of every entry's key, so a second process in
the same checkout finds the first one's programs.

Either way every program is cached, not only those that took JAX's
default one second to compile: a registry sweep compiles some 330
sub-second programs (summaries, bucket glue), which together cost a
cold process as long as its few large tick programs.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its place; return it."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
