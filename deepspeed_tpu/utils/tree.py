"""Pytree helpers shared across subsystems."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def cast_floating(tree, dtype, keep=()):
    """astype(dtype) on floating leaves; everything else untouched, and so
    is a leaf whose own key (the last of its path) is in ``keep``."""
    def cast(path, x):
        if keep and getattr(path[-1], "key", None) in keep:
            return x
        return x.astype(dtype) \
            if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x

    return jax.tree_util.tree_map_with_path(cast, tree)


def path_to_str(path, sep: str = ".") -> str:
    """jax KeyPath → joined string ('layers.wq', 'opt.0.mu.embed', ...)."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return sep.join(parts)
