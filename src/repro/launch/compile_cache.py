"""JAX persistent compilation cache, set up once by each entry point.

Called from ``main`` of the command-line entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks.run``), never on import.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.  Otherwise the cache goes to ``.jax_cache/`` at the
checkout root: a fixed path, because the path is part of the cache key
and a directory that moves never hits.
"""

from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; return that path."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
