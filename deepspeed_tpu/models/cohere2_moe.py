"""Cohere2-MoE family (``model_type`` ``cohere2_moe``: Command A+), written
TPU-first. What sets it apart from the sibling sparse decoder
(``models/mixtral.py``), and why it is a module of its own rather than four
more switches there - every one of them changes the block's SHAPE, not a
projection inside it:

- **A parallel block on ONE LayerNorm**: ``h = LayerNorm(x)`` (mean and
  variance, a learned weight, no bias); attention and the expert layer both
  read ``h``; ``x' = x + attn(h) + moe(h)``.
- **Window and full attention layers in one stack** (``layer_types``: three
  ``sliding_attention`` of every four, local first): a window layer ropes q
  and k (adjacent pairs rotated, ``rope_gptj``) and attends the last
  ``sliding_window`` positions; a full layer has NO positional embedding
  and attends the whole context. The stack is a scan NEST over whole periods
  of the pattern (``_paged.scan_nest`` is the pattern), so each
  kind's window is a static number and each kind's KV pool rides its own
  carry.
- **Two kinds of KV state** (``window_kinds``; ``inference/ragged.py``
  ``WindowKind``): the full layers' pool holds every block of a context,
  the window layers' pool what lies inside the window - the blocks behind
  it are given back - and a call's block table is one segment a kind
  (``_paged.kind_tables``).
- **A sigmoid router and averaged shared experts**: scores are
  ``sigmoid(h Wr)`` over all the experts, the gates the top-k scores over
  their sum (``moe/sharded_moe.py`` ``score="sigmoid"``);
  ``num_shared_experts`` always-on experts of the routed experts' width are
  AVERAGED and added - one FFN ``n`` times as wide, times ``1 / n``
  (``moe/layer.py`` ``shared_scale``).
- **Tied embeddings** with ``logit_scale``.

Same TPU shape as the sibling models: stacked layers, logical axis names
per param for the sharding-rule engine. ``experts_held``: one chip's share
of an expert-parallel deployment, as ``models/mixtral.py`` has it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..moe.layer import MoELayer, init_moe_ffn, moe_ffn_logical_axes
from ..ops.attention import attention
from ..ops.embedding import embedding_lookup
from ..ops.norms import layer_norm
from ..ops.rotary import apply_rotary_interleaved, rope_frequencies
from ._paged import (KINDS, dense_kind_attention, gather_rows,
                     init_stack_pools, paged_kind_attention, row_positions,
                     scan_stack, stack_layer_types, stack_window_kinds)
from .mixtral import _bank_apart
from .mixtral import moe_rows  # noqa: F401  (the same shape facts: the
#                                engine reads them off the family's module)

Params = Dict[str, Any]


@dataclass(frozen=True)
class Cohere2MoeConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    intermediate_size: int = 4096     # ONE expert's width (routed or shared)
    num_layers: int = 32
    num_heads: int = 128
    num_kv_heads: int = 8
    head_dim: Optional[int] = 128
    num_experts: int = 128
    top_k: int = 8
    num_shared_experts: int = 4
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 \
        + ("full_attention",)           # ONE period; repeated to num_layers
    sliding_window: int = 4096
    max_seq_len: int = 200000
    rope_theta: float = 50000.0
    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    norm_topk_prob: bool = True
    capacity_factor: float = 1.25
    min_capacity: int = 4
    drop_tokens: bool = True          # training; serving never drops
    aux_loss_coef: float = 0.01
    moe_dispatch: str = "einsum"
    # one chip's share of an expert-parallel deployment: ``(first, count)``
    # of the ``num_experts`` the router chooses among (moe/layer.py)
    experts_held: Optional[Tuple[int, int]] = None

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def resolved_layer_types(self) -> Tuple[str, ...]:
        """``layer_types`` for every layer (``_paged.stack_layer_types``)."""
        return stack_layer_types(self.layer_types, self.num_layers)

    def count(self, layer_type: str) -> int:
        return self.resolved_layer_types().count(layer_type)

    @classmethod
    def tiny(cls, **kw) -> "Cohere2MoeConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                    num_layers=4, num_heads=8, num_kv_heads=2, head_dim=16,
                    num_experts=8, top_k=2, num_shared_experts=2,
                    sliding_window=16, max_seq_len=128, rope_theta=10000.0)
        base.update(kw)
        return cls(**base)


def window_kinds(cfg: Cohere2MoeConfig) -> Dict[str, int]:
    """``ModelFamily.window_kinds``: ``{"window": sliding_window}``."""
    return stack_window_kinds(cfg.resolved_layer_types(), cfg.sliding_window)


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def init(cfg: Cohere2MoeConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    cfg.resolved_layer_types()      # refuses a pattern the family has not
    h, hd = cfg.hidden_size, cfg.head_size
    L, nh, nkv = cfg.num_layers, cfg.num_heads, cfg.num_kv_heads
    si = cfg.num_shared_experts * cfg.intermediate_size
    keys = jax.random.split(rng, 7)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def one_moe(k):
        if cfg.experts_held is None:
            p = init_moe_ffn(k, cfg.num_experts, h, cfg.intermediate_size,
                             dtype)
        else:
            p = init_moe_ffn(k, cfg.experts_held[1], h,
                             cfg.intermediate_size, dtype,
                             routed=cfg.num_experts)
        if si:
            # the shared experts side by side: expert j is columns
            # ``j * intermediate .. (j + 1) * intermediate`` of gate and up
            # and the same rows of down (each expert's own fan-in)
            ks = jax.random.split(jax.random.fold_in(k, 7), 3)
            p["shared_w_gate"] = normal(ks[0], (h, si), h)
            p["shared_w_up"] = normal(ks[1], (h, si), h)
            p["shared_w_down"] = normal(ks[2], (si, h),
                                        cfg.intermediate_size)
        return p

    return {
        "embed": normal(keys[0], (cfg.vocab_size, h), h),   # and the head
        "layers": {
            "norm": jnp.ones((L, h), dtype),
            "wq": normal(keys[1], (L, h, nh * hd), h),
            "wk": normal(keys[2], (L, h, nkv * hd), h),
            "wv": normal(keys[3], (L, h, nkv * hd), h),
            "wo": normal(keys[4], (L, nh * hd, h), nh * hd),
            "moe": jax.vmap(one_moe)(jax.random.split(keys[5], L)),
        },
        "final_norm": jnp.ones((h,), dtype),
    }


def param_logical_axes(cfg: Cohere2MoeConfig) -> Params:
    moe_axes = {k: ("layers",) + tuple(v)
                for k, v in moe_ffn_logical_axes().items()}
    if cfg.num_shared_experts:
        moe_axes.update({"shared_w_gate": ("layers", "embed", "mlp"),
                         "shared_w_up": ("layers", "embed", "mlp"),
                         "shared_w_down": ("layers", "mlp", "embed")})
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "moe": moe_axes,
        },
        "final_norm": ("embed",),
    }


# --------------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------------- #
def _moe(cfg: Cohere2MoeConfig, drop_tokens: bool) -> MoELayer:
    return MoELayer(cfg.num_experts, cfg.top_k, cfg.capacity_factor,
                    cfg.min_capacity, drop_tokens,
                    norm_topk=cfg.norm_topk_prob, dispatch=cfg.moe_dispatch,
                    held=cfg.experts_held, score="sigmoid",
                    shared_scale=1.0 / max(cfg.num_shared_experts, 1))


def _qkv(cfg, w, h, layer_type, cos, sin, positions):
    """q, k and v of one layer from its normed input; rope on a window
    layer's q and k (adjacent pairs), none on a full layer's."""
    b, t, _ = h.shape
    q = (h @ w["wq"]).reshape(b, t, cfg.num_heads, cfg.head_size)
    k = (h @ w["wk"]).reshape(b, t, cfg.num_kv_heads, cfg.head_size)
    v = (h @ w["wv"]).reshape(b, t, cfg.num_kv_heads, cfg.head_size)
    if layer_type == "sliding_attention":
        q = apply_rotary_interleaved(q, cos, sin, positions)
        k = apply_rotary_interleaved(k, cos, sin, positions)
    return q, k, v


def _block(cfg, x, w, bank, index, moe_layer, attend):
    """The parallel block: ``x + attn(h) + moe(h)``, ``h`` the ONE norm.
    ``attend(h) -> (attention's mix [b, t, nh, hd], pools)``; ``bank``: the
    stacked expert banks of a grouped call ({}: the layer's own are in
    ``w``). Returns ``(x, pools, aux)``."""
    b, t, _ = x.shape
    with jax.named_scope("norm"):
        h = layer_norm(x, w["norm"], None, cfg.layer_norm_eps)
    with jax.named_scope("attn"):   # the pool update inside is "kv_write"
        mix, pools = attend(h)
        a = mix.reshape(b, t, -1) @ w["wo"]
    m, aux = moe_layer({**w["moe"], **bank}, h,
                       layer=index if bank else None)
    return x + a + m, pools, aux


def _compute_layers(params, compute_dtype, moe_layer=None):
    """``(layers, bank)``: the stacked layers in the compute type and, where
    ``moe_layer``'s calls take the grouped form, the expert banks apart and
    whole (``mixtral._bank_apart``)."""
    layers = jax.tree.map(lambda p: p.astype(compute_dtype)
                          if jnp.issubdtype(p.dtype, jnp.floating) else p,
                          params["layers"])
    return (layers, {}) if moe_layer is None \
        else _bank_apart(layers, moe_layer)


def _embed(params, tokens, compute_dtype):
    with jax.named_scope("embed"):
        return embedding_lookup(params["embed"], tokens, compute_dtype)


def _head_split(cfg, params, x, compute_dtype):
    """Final norm and the tied table as the unembed matrix, ``logit_scale``
    folded into the hidden - what ``tiled_loss_fn`` consumes."""
    with jax.named_scope("norm"):
        x = layer_norm(x, params["final_norm"].astype(compute_dtype), None,
                       cfg.layer_norm_eps)
    return x * jnp.asarray(cfg.logit_scale, x.dtype), \
        params["embed"].astype(compute_dtype).T


def _head(cfg, params, x, compute_dtype):
    x, head = _head_split(cfg, params, x, compute_dtype)
    with jax.named_scope("logits"):      # the tied table
        return (x @ head).astype(jnp.float32)


def _rope(cfg):
    return rope_frequencies(cfg.head_size, cfg.max_seq_len, cfg.rope_theta)


# --------------------------------------------------------------------------- #
# entry points
# --------------------------------------------------------------------------- #
def apply(cfg: Cohere2MoeConfig, params: Params, tokens: jnp.ndarray, *,
          compute_dtype=jnp.bfloat16, return_hidden: bool = False):
    """Whole sequences with no cache → (logits [b, s, vocab] fp32, total
    aux loss); with ``return_hidden`` → (scaled normed hidden, unembed
    matrix, total aux loss)."""
    cos, sin = _rope(cfg)
    moe_layer = _moe(cfg, cfg.drop_tokens)
    layers, _ = _compute_layers(params, compute_dtype)

    def block(layer_type, window):
        def attend(h, w):
            q, k, v = _qkv(cfg, w, h, layer_type, cos, sin, None)
            with jax.named_scope("attn_" + KINDS[layer_type]):
                return attention(q, k, v, causal=True, window=window), None

        return lambda x, w, _pools, index, _i: _block(
            cfg, x, w, {}, index, moe_layer, lambda h: attend(h, w))

    x, _, aux = scan_stack(
        cfg.resolved_layer_types(), _embed(params, tokens, compute_dtype),
        layers, None,
        {"sliding_attention": block("sliding_attention", cfg.sliding_window),
         "full_attention": block("full_attention", None)})
    if return_hidden:
        return _head_split(cfg, params, x, compute_dtype) + (aux,)
    return _head(cfg, params, x, compute_dtype), aux


def loss_fn(cfg: Cohere2MoeConfig, params: Params,
            batch: Dict[str, jnp.ndarray], *, compute_dtype=jnp.bfloat16):
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    logits, aux = apply(cfg, params, inputs, compute_dtype=compute_dtype)
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        lm_loss = -jnp.mean(
            jnp.take_along_axis(logp, labels[..., None], axis=-1))
        loss = lm_loss + cfg.aux_loss_coef * aux
    return loss, {"loss": loss, "lm_loss": lm_loss, "aux_loss": aux}


def model_spec(cfg: Cohere2MoeConfig, compute_dtype=jnp.bfloat16):
    from ..runtime.engine import ModelSpec

    return ModelSpec(
        name="cohere2_moe",
        init_fn=lambda rng: init(cfg, rng),
        loss_fn=lambda params, batch: loss_fn(cfg, params, batch,
                                              compute_dtype=compute_dtype),
        apply_fn=lambda params, tokens, **kw: apply(
            cfg, params, tokens, compute_dtype=compute_dtype)[0],
        logical_axes=param_logical_axes(cfg),
        pipeline_capable=False,   # a scan nest, no pipeline path
    )


# ---- KV-cached decode (v1-engine path): every layer keeps the whole
# context, a window layer masks what lies behind its window ---- #
def init_cache(cfg: Cohere2MoeConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    shape = (cfg.num_layers, batch_size, max_len, cfg.num_kv_heads,
             cfg.head_size)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg: Cohere2MoeConfig) -> Params:
    spec = ("layers", None, None, "kv_heads", None)
    return {"k": spec, "v": spec}


def apply_cached(cfg: Cohere2MoeConfig, params: Params, tokens: jnp.ndarray,
                 cache: Params, cache_len: jnp.ndarray, *,
                 compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (tokens.shape[0],))
    b, t = tokens.shape
    cos, sin = _rope(cfg)
    positions = cache_len[:, None] + jnp.arange(t)[None, :]
    moe_layer = _moe(cfg, False)
    layers, bank = _compute_layers(params, compute_dtype, moe_layer)
    dense = dense_kind_attention(cache, cache_len, positions)

    def block(layer_type, window):
        attend = dense(KINDS[layer_type], window)
        return lambda x, w, pools, index, _i: _block(
            cfg, x, w, bank, index, moe_layer, lambda h: attend(
                *_qkv(cfg, w, h, layer_type, cos, sin, positions), pools,
                index))

    x, cache, _ = scan_stack(
        cfg.resolved_layer_types(), _embed(params, tokens, compute_dtype),
        layers, dict(cache),
        {"sliding_attention": block("sliding_attention", cfg.sliding_window),
         "full_attention": block("full_attention", None)})
    return _head(cfg, params, x, compute_dtype), cache


# --------------------------------------------------------------------------- #
# Paged (blocked) KV-cache path — the v2 continuous-batching protocol, over
# two kinds of KV state
# --------------------------------------------------------------------------- #
def init_paged_cache(cfg: Cohere2MoeConfig, num_blocks: int,
                     block_size: int, dtype=jnp.bfloat16,
                     window_blocks: Optional[Dict[str, int]] = None
                     ) -> Params:
    """The full layers' pools, ``k`` / ``v`` ``[L_full, num_blocks, ...]``,
    and the window layers', ``k_window`` / ``v_window`` ``[L_window,
    window_blocks["window"], ...]`` (the engine sizes them:
    ``inference.ragged.WindowKind.sized``; as many as the full kind's where
    no one says). No quantized-KV mode."""
    return init_stack_pools(cfg.resolved_layer_types(), cfg.sliding_window,
                            num_blocks, window_blocks, cfg.num_kv_heads,
                            block_size, cfg.head_size, dtype)


def apply_paged(cfg: Cohere2MoeConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the two-kind cache (prefill rows, chunks, decode
    steps or a mixed call): ``llama.apply_paged``'s contract (``rows``: the
    head scores those rows alone), with
    ``block_tables`` one segment a kind of KV state side by side, the full
    kind's first (``_paged.kind_tables``; ``StateManager.block_table``
    builds it; a table of the full kind's width alone serves both kinds
    from it - a cache that gives nothing back). A layer writes and reads its
    own kind's pool through its own kind's table, a window layer at context
    lengths counted from its sequence's first live block."""
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    cos, sin = _rope(cfg)
    positions = row_positions(block_tables, context_lens, t)
    moe_layer = _moe(cfg, False)
    layers, bank = _compute_layers(params, compute_dtype, moe_layer)
    attend = paged_kind_attention(cache, block_tables, context_lens, valid,
                                  cfg.max_seq_len, window_kinds(cfg))

    def block(layer_type):
        return lambda x, w, pools, index, i: _block(
            cfg, x, w, bank, index, moe_layer, lambda h: attend(
                KINDS[layer_type], *_qkv(cfg, w, h, layer_type, cos, sin,
                                         positions), pools, i))

    x, cache, _ = scan_stack(
        cfg.resolved_layer_types(), _embed(params, tokens, compute_dtype),
        layers, dict(cache),
        {layer_type: block(layer_type) for layer_type in KINDS})
    return _head(cfg, params, gather_rows(x, rows), compute_dtype), cache
