"""Persistent XLA compilation cache for the repo's command-line entry points.

Called by ``chip_smoke.py``, ``benchmarks/run.py`` and ``repro.launch.train``
when they start — never on import of ``repro``, so tests and library users
keep JAX's defaults.
"""

from __future__ import annotations

import os

import jax

# <checkout>/.jax_cache: this file is <checkout>/src/repro/launch/.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and no other directory is set here.  Otherwise the cache goes to
    the fixed ``<checkout>/.jax_cache``: the path is part of the cache key,
    so it must not move between runs.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path
