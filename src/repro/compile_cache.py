"""Persistent XLA compilation cache placement for the entry scripts.

A cold solve at real size spends minutes compiling, so every entry script
(``chip_smoke.py``, ``benchmarks/run.py``, the examples) calls
:func:`enable_compile_cache` before its first compile. Importing ``repro``
never touches the cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
module sets nothing. Otherwise the cache lives at one fixed path inside the
checkout, ``<repo>/.jax_cache`` (git-ignored): the directory is part of
what makes a later run find its entries, so it never depends on a
temporary name, a pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
