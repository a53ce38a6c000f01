"""Six deliberately WRONG variants of the Granite-4.0-H reference, to show
what a comparison against the right one can see
(``benchmark/tools/wrong_reference_check.py`` on the chip,
``tests/test_granite_hybrid.py`` on the CPU). Each changes one thing a port
of this model is likely to get wrong; none is ever what a cell is held to.

``logits(name, cfg, weights, tokens)`` takes the same arguments as
``granite_hybrid.logits`` after the variant's name.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import blocks
from . import granite_hybrid as right


def _head_scale(cfg):
    """``1 / sqrt(head size)`` (1/8), every other decoder's scale, where the
    model has ``attention_multiplier`` (1/64)."""
    return (cfg["hidden_size"] // cfg["num_attention_heads"]) ** -0.5


def _rotary_attention(x, w, cfg):
    """Rotary embedding applied, as in every other decoder here; the model
    has no positional embedding."""
    return right.attention(x, w, cfg, positions=jnp.arange(x.shape[0]))


def _norm_then_gate(y, z, weight, eps):
    """Mamba-2's other order (``norm_before_gate``): the norm first."""
    return blocks.rms_norm(y, weight, eps) * jax.nn.silu(z)


# one function object a variant: ``_layer`` is jitted on it
_LAYERS = {
    "head_scale": functools.partial(right.layer, attention_fn=functools.partial(
        right.attention, scale=_head_scale)),
    "rotary": functools.partial(right.layer, attention_fn=_rotary_attention),
    "norm_before_gate": functools.partial(
        right.layer, mamba_fn=functools.partial(right.mamba,
                                                gate=_norm_then_gate)),
    "no_dt_bias": functools.partial(
        right.layer, mamba_fn=functools.partial(right.mamba, dt_bias=False)),
    "bf16_state": functools.partial(
        right.layer, mamba_fn=functools.partial(right.mamba,
                                                state_dtype=jnp.bfloat16)),
}


def logits(name: str, cfg: dict, weights, tokens):
    if name == "residual_one":       # ``residual_multiplier`` left out
        return right.logits({**cfg, "residual_multiplier": 1.0}, weights,
                            tokens)
    if name in _LAYERS:
        return right.logits(cfg, weights, tokens, layer_fn=_LAYERS[name])
    raise ValueError(f"no variant named {name!r}")


NAMES = ("head_scale", "rotary", "norm_before_gate", "no_dt_bias",
         "residual_one", "bf16_state")
