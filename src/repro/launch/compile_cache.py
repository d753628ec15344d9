"""Persistent XLA compilation cache for the entry points.

A compiled program is looked up by a key that includes the cache
directory, so the directory is fixed: ``<repo>/.jax_cache``, which git
ignores.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here.  Tests never call this."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs across processes.  Call before the first
    compile."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
