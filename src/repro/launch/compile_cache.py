"""The persistent XLA compilation cache shared by every entry point.

A cold run on the chip spends minutes compiling its job programs, and
JAX keys each cache entry by the cache directory too, so the directory
must not move between runs.  ``JAX_COMPILATION_CACHE_DIR`` wins when it
is set (JAX reads it itself, and nothing else is changed); otherwise
the cache lives at ``<checkout>/.jax_cache``, which git ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
