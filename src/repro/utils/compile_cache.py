"""JAX's persistent compilation cache, at a place a later run finds again.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train``
and ``benchmarks.run``) call :func:`enable_compile_cache` once, before their
first compile; importing this module changes nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax

# <repo>/.jax_cache: fixed, never built from a temp dir, a pid or the time —
# a directory that moves between runs never hits.
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it and
    that setting stands: nothing is set here. Otherwise the cache goes to
    :data:`DEFAULT_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
