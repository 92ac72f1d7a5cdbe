"""JAX's persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``examples/serve_multiround.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once before they
compile anything; no library module turns the cache on at import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this helper
sets nothing.  Otherwise the cache lives at ``<repo>/.jax_cache/``: a fixed
path, never built from a tmpdir, a pid or the time, because the path is
part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the cache directory inside the checkout (git-ignored)
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
