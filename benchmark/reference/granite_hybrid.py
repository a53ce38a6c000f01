"""Plain reference of the Granite-4.0-H decoder (``ibm-granite/
granite-4.0-h-micro`` ``config.json``, ``model_type`` ``granitemoehybrid``),
written from the published ``modeling_granitemoehybrid`` (and the Mamba-2
paper, arXiv:2405.21060, for the recurrence): float32, every matmul under
``jax.default_matmul_precision("highest")``, one sequence, one layer at a
time with that layer's weights widened as it is used.

    x = embedding_multiplier * E[token]
    x = x + residual_multiplier * mixer_l(RMSNorm(x))      l = 0 .. L - 1
    x = x + residual_multiplier * mlp_l(RMSNorm(x))
    logits = RMSNorm(x) E^T / logits_scaling               (tied table)

``mlp(y) = W_out (silu(g) * u)``, ``[g | u] = W_in y`` (``shared_mlp``; the
sparse branch is absent: ``num_local_experts`` 0 is required).

Attention layers (``layer_types[l] == "attention"``): q, k, v, o without
bias, grouped-query, NO positional embedding (``position_embedding_type``
"nope"), causal softmax of ``attention_multiplier * q k^T``.

Mamba-2 layers: ``in_proj`` (no bias) ``y -> [z | xBC | dt]``; ``xBC <-
silu(conv1d(xBC) + b)``, depthwise and causal over the previous ``K - 1``
rows; ``xBC -> x | B | C`` (one group); ``dt <- softplus(dt + dt_bias)``,
``A = -exp(A_log)``, one scalar each a head; a head with input ``x_t`` in
R^P and state ``H`` in R^(P x N):

    H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T         y_t = H_t C_t + D x_t

then ``y <- RMSNorm(y * silu(z))`` over the whole inner width (gate first,
then norm) and ``out_proj``.

Departures: none in the mathematics. The recurrence is a plain ``lax.scan``
over TOKENS - the published code blocks it by ``mamba_chunk_size``, which
is how it is computed and no part of the result, and the program's chunked
form is what this is independent of. A sequence is computed with zeros
after it up to a multiple of 1024 (``_padded``: causal, so no logit of a real
position moves). ``time_step_limit`` is (0, inf) in the
release and so not applied; ``mamba_n_groups`` other than 1,
``mamba_proj_bias``, ``attention_bias`` and a sparse branch are refused.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import blocks, mistral

F32 = blocks.F32


def _published(cfg: dict) -> dict:
    if cfg["mamba_n_groups"] != 1 or cfg["num_local_experts"] != 0:
        raise ValueError("the Granite reference has one group of B and C "
                         "and no sparse branch")
    for key in ("attention_bias", "mamba_proj_bias", "rope_scaling"):
        if cfg.get(key):
            raise ValueError(f"the Granite reference has no {key}")
    if not (cfg["mamba_conv_bias"] and cfg["tie_word_embeddings"]):
        raise ValueError("the Granite reference has a convolution bias and "
                         "a tied table")
    return cfg


def scores_scale(cfg):
    return cfg["attention_multiplier"]


def attention(x, w, cfg, scale=scores_scale, positions=None):
    """Causal grouped-query self-attention over one sequence ``x [seq,
    hidden]`` with no positional embedding. ``scale`` and ``positions`` are
    hooks for the deliberately wrong variants (``positions`` given: rotary
    applied, which the model does not have)."""
    s = x.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    q = (x @ w["q"].astype(F32)).reshape(s, nh, hd)
    k = (x @ w["k"].astype(F32)).reshape(s, nkv, hd)
    v = (x @ w["v"].astype(F32)).reshape(s, nkv, hd)
    if positions is not None:
        q = blocks.rope(q, positions, cfg["rope_theta"])
        k = blocks.rope(k, positions, cfg["rope_theta"])
    k = jnp.repeat(k, nh // nkv, axis=1)   # each KV head serves a group
    v = jnp.repeat(v, nh // nkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * scale(cfg)
    pos = jnp.arange(s)
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    mix = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return mix.reshape(s, nh * hd) @ w["o"].astype(F32)


def gated_norm(y, z, weight, eps):
    """Gate first, then the norm over the whole inner width."""
    return blocks.rms_norm(y * jax.nn.silu(z), weight, eps)


def mamba(x, w, cfg, gate=gated_norm, dt_bias=True, state_dtype=F32):
    """The Mamba-2 mixer over one sequence ``x [seq, hidden]`` from a zero
    state. ``gate``, ``dt_bias`` and ``state_dtype`` are hooks for the
    deliberately wrong variants."""
    s = x.shape[0]
    H, P, N = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    K, d_in = cfg["mamba_d_conv"], cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    z, xbc, dt = jnp.split(x @ w["in_proj"].astype(F32),
                           [d_in, d_in + d_in + 2 * N], axis=-1)
    # depthwise causal convolution: tap k meets the row K - 1 - k back
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    taps = w["conv_w"].astype(F32)                              # [K, C]
    xbc = jax.nn.silu(sum(padded[k:k + s] * taps[k] for k in range(K))
                      + w["conv_b"].astype(F32))
    xs, B, C = jnp.split(xbc, [d_in, d_in + N], axis=-1)
    xs = xs.reshape(s, H, P)
    dt = jax.nn.softplus(dt + (w["dt_bias"].astype(F32) if dt_bias else 0.0))
    A = -jnp.exp(w["A_log"].astype(F32))

    def token(h, t):
        x_t, dt_t, b_t, c_t = t
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        if state_dtype != F32:   # a wrong variant: the state kept rounded
            # (reduce_precision, which the compiler must honour: it may
            # drop a convert to a narrower type and back as excess precision)
            kept = jnp.finfo(state_dtype)
            h = jax.lax.reduce_precision(h, kept.nexp, kept.nmant)
        return h, jnp.einsum("hpn,n->hp", h, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), F32), (xs, dt, B, C))
    y = y + w["D"].astype(F32)[:, None] * xs
    return gate(y.reshape(s, d_in), z, w["gate_norm"], cfg["rms_norm_eps"]) \
        @ w["out_proj"].astype(F32)


def mlp(y, w):
    g, u = jnp.split(y @ w["w_in"].astype(F32), 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w["w_out"].astype(F32)


@functools.partial(jax.jit, static_argnames=("cfg", "mixer"))
def _layer(x, w, cfg, mixer):
    cfg = dict(cfg)
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    x = x + res * mixer(blocks.rms_norm(x, w["norm"], eps), w, cfg)
    return x + res * mlp(blocks.rms_norm(x, w["mlp_norm"], eps), w)


def layer(x, w, cfg, attention_fn=attention, mamba_fn=mamba):
    """One layer over one sequence, of the kind its weights say
    (``_Multiplied.layer`` puts ``kind`` beside them); ``attention_fn`` and
    ``mamba_fn`` are hooks for the wrong variants."""
    w = dict(w)
    mixer = attention_fn if w.pop("kind") == "attention" else mamba_fn
    return _layer(x, w, cfg, mixer)


class _Multiplied:
    """The weights in stack order, with the embedding multiplier on the table's rows as they
    are looked up, and the tied table (transposed, over ``logits_scaling``)
    as the head: what ``mistral.logits``' embed-layers-norm-head walk needs
    to be this model's."""

    def __init__(self, weights, cfg):
        self._weights, self._cfg = weights, cfg
        self.final_norm = weights.final_norm

    def layer(self, i: int) -> dict:
        """Layer ``i`` of the stack: the ``j``-th of its kind, as
        ``layer_types`` counts them."""
        kinds = self._cfg["layer_types"]
        j = sum(kind == kinds[i] for kind in kinds[:i])
        return {"kind": kinds[i], **self._weights.layer(kinds[i], j)}

    @property
    def embed(self):
        return _Rows(self._weights.embed, self._cfg["embedding_multiplier"])

    @property
    def head(self):
        return self._weights.embed.astype(F32).T / self._cfg["logits_scaling"]


class _Rows:
    def __init__(self, table, multiplier):
        self._table, self._multiplier = table, multiplier

    def __getitem__(self, tokens):
        return self._table[tokens].astype(F32) * self._multiplier


BUCKET = 1024


def _padded(tokens):
    """``tokens`` with zeros after them up to a whole number of buckets.
    The model is causal - nothing that follows a position can move it - so
    the real positions' logits are those of the unpadded sequence, and the
    layers (and the 40 compilations a new length costs them on the chip)
    are shared by every sequence of a bucket."""
    tokens = jnp.asarray(tokens)
    return jnp.pad(tokens, (0, -len(tokens) % BUCKET))


def logits(cfg: dict, weights, tokens, layer_fn=layer):
    """Logits ``[seq, vocab]`` of one sequence. ``weights`` gives ``embed``
    (the tied table), ``final_norm`` and ``layer(kind, j)``: the matrices of
    the ``j``-th layer of a kind."""
    cfg = _published(cfg)
    return mistral.logits(cfg, _Multiplied(weights, cfg), _padded(tokens),
                          layer_fn=layer_fn)[:len(tokens)]


def logits_and_margin(cfg: dict, weights, tokens):
    """``logits`` and no margin: the model makes no discrete choice, so
    every served token is held to the flat ``SERVED_TOKEN_GAP_TOL``."""
    out = logits(cfg, weights, tokens)
    return out, jnp.full(out.shape[0], jnp.inf)


def loss(cfg: dict, weights, rows, layer_fn=layer):
    cfg = _published(cfg)
    return mistral.loss(cfg, _Multiplied(weights, cfg), rows,
                        layer_fn=layer_fn)
