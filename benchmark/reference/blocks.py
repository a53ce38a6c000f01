"""Plain float32 ``jax.numpy`` building blocks of the two reference models,
written from the published descriptions (the Mistral 7B and Mixtral of
Experts papers and the ``config.json`` of each release), not imported from
``deepspeed_tpu/models``. No kernels, no cache, no batching: one sequence,
one layer at a time, every matmul under
``jax.default_matmul_precision("highest")`` (a float32 matmul on a TPU runs
in lower precision without it).

Weights arrive in the layout ``y = x @ W`` (``[in, out]``) in whatever type
the program serves them in, and are widened to float32 here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def rms_norm(x, weight, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * weight.astype(F32)


def rope(x, positions, theta):
    """Rotary embedding in the half-split ("rotate_half") convention of the
    published checkpoints: dimension ``i`` pairs with ``i + d/2``.
    ``x`` is ``[seq, heads, d]``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(x, w, cfg):
    """Causal grouped-query self-attention over one whole sequence
    ``x [seq, hidden]``; no sliding window (see the configuration's
    ``assumed``)."""
    s = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // nh
    pos = jnp.arange(s)
    q = rope((x @ w["q"].astype(F32)).reshape(s, nh, hd), pos,
             cfg["rope_theta"])
    k = rope((x @ w["k"].astype(F32)).reshape(s, nkv, hd), pos,
             cfg["rope_theta"])
    v = (x @ w["v"].astype(F32)).reshape(s, nkv, hd)
    k = jnp.repeat(k, nh // nkv, axis=1)   # each KV head serves a group
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    mix = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return mix.reshape(s, nh * hd) @ w["o"].astype(F32)


def swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) \
        @ down.astype(F32)


def next_token_loss(logits, tokens):
    """Mean cross-entropy of ``logits[i]``, computed from ``tokens[:i + 1]``,
    against ``tokens[i + 1]``; ``tokens`` is one longer than ``logits``."""
    logp = jax.nn.log_softmax(logits.astype(F32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))
