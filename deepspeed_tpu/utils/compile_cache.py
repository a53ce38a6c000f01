"""Where JAX's persistent compilation cache lives — the one rule.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here (or
anywhere else in the repo) sets another directory. Unset: the cache is
``<checkout>/.xla_cache`` (git-ignored). The path is part of the cache key,
so it is never derived from a pid, a timestamp or a temporary directory.
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Apply the rule above; returns the directory in effect."""
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".xla_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
