"""Persistent XLA compile cache placement.

One rule, applied from `hvd.init()` and `ServingEngine.__init__` (and
by `chip_smoke.py`): where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this code sets nothing; otherwise the cache lives
at ``<checkout>/.jax_cache`` — a FIXED path next to the package. The
directory is part of every cache key, so a temp name, pid or
timestamp would never hit.
"""

from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Place the compile cache (see module doc); returns its path."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    import jax
    if jax.config.jax_compilation_cache_dir != DEFAULT_CACHE_DIR:
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
