"""Persistent XLA compilation cache for the program's entry points.

Entry points (``chip_smoke.py``, ``examples/serve_gcn.py``, the
``benchmarks/*.py`` mains) call ``enable_compile_cache`` once, before
they compile anything; importing the library never touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache: fixed, because the directory is part of what a
# later run must find again (git-ignored).
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets nothing. Otherwise the cache goes to ``DEFAULT_DIR``.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
