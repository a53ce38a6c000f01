"""Plain reference of the Mixtral-8x7B block (arXiv:2401.04088;
``mistralai/Mixtral-8x7B-v0.1`` ``config.json``): the Mistral block with the
feed-forward replaced by 8 SwiGLU experts, of which a linear router picks
the top 2 per token; their outputs are weighted by the softmax over the two
chosen router logits (equivalently, the full softmax renormalised over the
chosen two). No capacity limit: no token is ever dropped.

One expert is widened to float32 at a time (one layer's experts are 5.6 GB
in float32): every expert runs over every token and the router's weight,
zero for the six experts a token did not choose, scales its output. That is
the same sum as the sparse form, term for term.

The router's choice is discrete: where a token's second and third router
logits lie closer together than bfloat16 arithmetic can tell apart, a served
model that computes in bfloat16 may rightly choose the other expert, and its
logits at that position then differ from this reference's by order 1.
``logits_and_margin`` therefore also gives, for every position, the least
distance over the layers between the last chosen and the first unchosen
router logit, so that a comparison knows where the reference's own answer
is one of two.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import blocks, mistral


@functools.partial(jax.jit, static_argnames=("cfg",))
def _attention_and_route(x, w, cfg):
    cfg = dict(cfg)
    x = x + blocks.attention(blocks.rms_norm(x, w["attn_norm"],
                                             cfg["rms_norm_eps"]), w, cfg)
    y = blocks.rms_norm(x, w["ffn_norm"], cfg["rms_norm_eps"])
    router_logits = y @ w["router"].astype(blocks.F32)
    k = cfg["num_experts_per_tok"]
    top, idx = jax.lax.top_k(router_logits, k + 1)
    margin = top[:, k - 1] - top[:, k]       # last chosen - first unchosen
    gate = jax.nn.softmax(top[:, :k], axis=-1)               # [seq, k]
    dense = jnp.sum(jax.nn.one_hot(idx[:, :k], cfg["num_local_experts"],
                                   dtype=blocks.F32) * gate[..., None], 1)
    return x, y, dense, margin                         # dense is [seq, E]


@jax.jit
def _expert(y, weight, gate, up, down):
    return weight[:, None] * blocks.swiglu(y, gate, up, down)


def _layer(x, w, cfg, margins=None):
    x, y, dense, margin = _attention_and_route(
        x, {k: v for k, v in w.items() if k != "experts"}, cfg)
    if margins is not None:
        margins.append(margin)
    for e, (gate, up, down) in enumerate(w["experts"]):
        x = x + _expert(y, dense[:, e], gate, up, down)
    return x


def logits(cfg: dict, weights, tokens):
    return mistral.logits(cfg, weights, tokens, layer_fn=_layer)


def logits_and_margin(cfg: dict, weights, tokens):
    """Logits, and for each position the least routing margin over the
    layers (in router logits, which have about unit spread)."""
    margins = []
    out = mistral.logits(cfg, weights, tokens, layer_fn=functools.partial(
        _layer, margins=margins))
    return out, functools.reduce(jnp.minimum, margins)


def loss(cfg: dict, weights, rows):
    return mistral.loss(cfg, weights, rows, layer_fn=_layer)
