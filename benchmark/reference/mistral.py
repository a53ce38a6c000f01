"""Plain reference of the Mistral-7B decoder block and model
(arXiv:2310.06825; ``mistralai/Mistral-7B-v0.1`` ``config.json``):
pre-norm residual block, RMSNorm, rotary grouped-query attention, SwiGLU
feed-forward, untied output head. Departure, stated in the configuration's
``assumed``: the 4096-token sliding window is not applied.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import blocks


@functools.partial(jax.jit, static_argnames=("cfg",))
def _layer(x, w, cfg):
    cfg = dict(cfg)
    x = x + blocks.attention(blocks.rms_norm(x, w["attn_norm"],
                                             cfg["rms_norm_eps"]), w, cfg)
    y = blocks.rms_norm(x, w["ffn_norm"], cfg["rms_norm_eps"])
    return x + blocks.swiglu(y, w["gate"], w["up"], w["down"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, eps):
    return blocks.rms_norm(x, norm, eps) @ head.astype(blocks.F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _row_loss(x, norm, head, tokens, eps):
    return blocks.next_token_loss(
        blocks.rms_norm(x, norm, eps) @ head.astype(blocks.F32), tokens)


def _hidden(cfg: dict, weights, rows, layer_fn):
    """Final hidden states of each row of tokens, layer by layer: only one
    layer (for Mixtral one expert) is widened to float32 at a time."""
    frozen = tuple(sorted((k, v) for k, v in cfg.items()
                          if isinstance(v, (int, float, bool, str))))
    xs = [weights.embed[jnp.asarray(row)].astype(blocks.F32) for row in rows]
    for i in range(cfg["num_hidden_layers"]):
        w = weights.layer(i)
        xs = [layer_fn(x, w, frozen) for x in xs]
    return xs


def logits(cfg: dict, weights, tokens, layer_fn=_layer):
    """Logits ``[seq, vocab]`` of one sequence. ``weights`` gives ``embed``,
    ``final_norm``, ``head`` and ``layer(i)``, a dict of one layer's
    matrices."""
    with jax.default_matmul_precision("highest"):
        (x,) = _hidden(cfg, weights, [tokens], layer_fn)
        return _head(x, weights.final_norm, weights.head,
                     cfg["rms_norm_eps"])


def logits_and_margin(cfg: dict, weights, tokens):
    """``logits`` and, for each position, how far the model was from another
    discrete choice: a dense model makes none, so infinitely far."""
    out = logits(cfg, weights, tokens)
    return out, jnp.full(out.shape[0], jnp.inf)


def loss(cfg: dict, weights, rows, layer_fn=_layer):
    """Mean next-token loss over ``rows`` of ``seq + 1`` tokens each: row
    ``r`` predicts ``rows[r][1:]`` from ``rows[r][:-1]``."""
    rows = [jnp.asarray(row) for row in rows]
    with jax.default_matmul_precision("highest"):
        xs = _hidden(cfg, weights, [row[:-1] for row in rows], layer_fn)
        each = [_row_loss(x, weights.final_norm, weights.head, row,
                          cfg["rms_norm_eps"]) for x, row in zip(xs, rows)]
    return sum(each) / len(each)
