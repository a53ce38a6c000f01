"""Mellum family (``model_type`` ``mellum``: Mellum2-12B-A2.5B), written
TPU-first and built from what the sibling families share. A SEQUENTIAL
pre-norm block (the Llama / Mixtral shape: ``x += attn(RMSNorm(x))``, then
``x += moe(RMSNorm(x))``) over the two-kind stack ``models/cohere2_moe.py``
has (``_paged.scan_stack``, ``paged_kind_attention``: window layers and full
layers, a KV pool of their own kind each, the window kind's blocks given
back), and what neither has:

- **A rope table a KIND of layer**: a ``sliding_attention`` layer ropes q
  and k by the plain table at ``rope_theta``; a ``full_attention`` layer by
  a YaRN table (``ops/rotary.py yarn_frequencies``: ``factor``,
  ``original_max_position_embeddings``, ``beta_fast`` / ``beta_slow``) whose
  cos AND sin carry the published ``attention_factor`` - so a full layer's
  scores are ``attention_factor ** 2`` times a plain rope's. Both tables are
  made once a program (:func:`_rope`) and a layer takes its kind's
  statically: the scan nest runs one kind a run. Half-split rotation
  (``rotate_half``) over the whole head.
- **Every layer sparse** (``mlp_layer_types``): 64 experts of
  ``moe_intermediate_size``, 8 a token, a float32 softmax router
  (``FLOAT32_PARAMS``) whose chosen scores are normalised
  (``norm_topk_prob``); no shared expert, no dense layer - the published
  ``intermediate_size`` is the width of a feed-forward no layer has.
  ``MellumConfig.intermediate_size`` is ONE expert's width, as
  ``models/mixtral.py`` has it (``moe_rows`` is that module's).
- An untied head; no bias anywhere; no per-head q/k norm.

SERVING ONLY: there is no ``loss_fn`` and no ``model_spec`` - training
through two kinds of layer with a rope each (``ops/pallas/
flash_attention.py`` takes one static window) and through a 64-expert bank
without the ``[T, E, C]`` slabs is ROADMAP.md B-I's, by mechanism. The
multi-token-prediction head the release describes is in no key of its
configuration and is left out.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..moe.layer import MoELayer, init_moe_ffn, moe_ffn_logical_axes
from ..ops.attention import attention
from ..ops.embedding import embedding_lookup
from ..ops.norms import rms_norm
from ..ops.rotary import apply_rotary, rope_frequencies, yarn_frequencies
from ..utils.tree import cast_floating
from ._paged import (KINDS, dense_kind_attention, gather_rows,
                     init_stack_pools, paged_kind_attention, row_positions,
                     scan_stack, stack_layer_types, stack_window_kinds)
from .mixtral import _bank_apart
from .mixtral import moe_rows  # noqa: F401  (the same shape facts: the
#                                engine reads them off the family's module)

Params = Dict[str, Any]

# leaves the serving engine keeps in the type they come in (``inference/
# engine.py``): the router scores float32 rows
FLOAT32_PARAMS = ("router",)


@dataclass(frozen=True)
class MellumConfig:
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 896      # ONE expert's width
    num_layers: int = 28
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: Optional[int] = 128
    num_experts: int = 64
    top_k: int = 8
    layer_types: Tuple[str, ...] = ("sliding_attention",) * 3 \
        + ("full_attention",)           # ONE period; repeated to num_layers
    sliding_window: int = 1024
    max_seq_len: int = 131072
    rope_theta: float = 500000.0
    # the full layers' YaRN table (``rope_parameters.full_attention``)
    rope_factor: float = 16.0
    rope_original_max_len: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782    # on cos AND sin
    rms_norm_eps: float = 1e-6
    norm_topk_prob: bool = True
    # (``mixtral.moe_rows`` reads these four; serving never drops a token,
    # so the capacity only sizes the slabs of a program over several devices)
    capacity_factor: float = 1.25
    min_capacity: int = 4
    moe_dispatch: str = "einsum"
    # (``mixtral.moe_rows`` reads it too: every expert is held here)
    experts_held = None

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def resolved_layer_types(self) -> Tuple[str, ...]:
        """``layer_types`` for every layer (``_paged.stack_layer_types``)."""
        return stack_layer_types(self.layer_types, self.num_layers)

    @classmethod
    def tiny(cls, **kw) -> "MellumConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=32,
                    num_layers=4, num_heads=8, num_kv_heads=2, head_dim=16,
                    num_experts=8, top_k=2, sliding_window=16,
                    max_seq_len=128, rope_theta=10000.0,
                    rope_original_max_len=32, rope_factor=4.0)
        base.update(kw)
        return cls(**base)


def window_kinds(cfg: MellumConfig) -> Dict[str, int]:
    """``ModelFamily.window_kinds``: ``{"window": sliding_window}``."""
    return stack_window_kinds(cfg.resolved_layer_types(), cfg.sliding_window)


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def init(cfg: MellumConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Fan-in scaled normals, norm weights of one; the router a float32
    matrix whatever ``dtype``."""
    cfg.resolved_layer_types()      # refuses a pattern the family has not
    h, hd = cfg.hidden_size, cfg.head_size
    L, nh, nkv = cfg.num_layers, cfg.num_heads, cfg.num_kv_heads
    keys = jax.random.split(rng, 7)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def one_moe(k):
        p = init_moe_ffn(k, cfg.num_experts, h, cfg.intermediate_size, dtype)
        # (a draw of its own: the bank's router was rounded to ``dtype``)
        p["router"] = jax.random.normal(
            jax.random.fold_in(k, 1), (h, cfg.num_experts),
            jnp.float32) * h ** -0.5
        return p

    return {
        "embed": normal(keys[0], (cfg.vocab_size, h), h),
        "layers": {
            "attn_norm": jnp.ones((L, h), dtype),
            "wq": normal(keys[1], (L, h, nh * hd), h),
            "wk": normal(keys[2], (L, h, nkv * hd), h),
            "wv": normal(keys[3], (L, h, nkv * hd), h),
            "wo": normal(keys[4], (L, nh * hd, h), nh * hd),
            "mlp_norm": jnp.ones((L, h), dtype),
            "moe": jax.vmap(one_moe)(jax.random.split(keys[5], L)),
        },
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": normal(keys[6], (h, cfg.vocab_size), h),
    }


def param_logical_axes(cfg: MellumConfig) -> Params:
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", "embed"),
            "moe": {k: ("layers",) + tuple(v)
                    for k, v in moe_ffn_logical_axes().items()},
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# --------------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------------- #
def _moe(cfg: MellumConfig) -> MoELayer:
    """The expert layer of every forward here: it never drops a token (the
    published model has no capacity limit)."""
    return MoELayer(cfg.num_experts, cfg.top_k, cfg.capacity_factor,
                    cfg.min_capacity, drop_tokens=False,
                    norm_topk=cfg.norm_topk_prob, dispatch=cfg.moe_dispatch)


def _rope(cfg: MellumConfig) -> Dict[str, Tuple[jnp.ndarray, jnp.ndarray]]:
    """The cos / sin tables of each layer type, made once a program: the
    plain table for the window layers, YaRN's with ``attention_factor`` on
    both for the full layers."""
    hd, n, theta = cfg.head_size, cfg.max_seq_len, cfg.rope_theta
    return {
        "sliding_attention": rope_frequencies(hd, n, theta),
        "full_attention": yarn_frequencies(
            hd, n, theta, cfg.rope_factor, cfg.rope_original_max_len,
            cfg.rope_beta_fast, cfg.rope_beta_slow,
            table_scale=cfg.attention_factor)}


def _qkv(cfg, w, u, table, positions):
    """q, k and v of one layer from its normed input, q and k roped by the
    layer's own kind's ``table``."""
    b, t, _ = u.shape
    q = (u @ w["wq"]).reshape(b, t, cfg.num_heads, cfg.head_size)
    k = (u @ w["wk"]).reshape(b, t, cfg.num_kv_heads, cfg.head_size)
    v = (u @ w["wv"]).reshape(b, t, cfg.num_kv_heads, cfg.head_size)
    return (apply_rotary(q, *table, positions),
            apply_rotary(k, *table, positions), v)


def _block(cfg, x, w, bank, index, moe_layer, attend):
    """The sequential block. ``attend(u) -> (attention's mix [b, t, nh, hd],
    pools)``; ``bank``: the stacked expert banks of a grouped call ({}: the
    layer's own are in ``w``). Returns ``(x, pools, aux)``."""
    b, t, _ = x.shape
    with jax.named_scope("norm"):
        u = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn"):   # the pool update inside is "kv_write"
        mix, pools = attend(u)
        x = x + mix.reshape(b, t, -1) @ w["wo"]
    with jax.named_scope("norm"):
        n = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
    m, aux = moe_layer({**w["moe"], **bank}, n,
                       layer=index if bank else None)
    return x + m, pools, aux


def _head(cfg, params, x, compute_dtype):
    with jax.named_scope("norm"):
        x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                     cfg.rms_norm_eps)
    with jax.named_scope("logits"):
        return (x @ params["lm_head"].astype(compute_dtype)).astype(
            jnp.float32)


def _window(cfg, layer_type) -> Optional[int]:
    return cfg.sliding_window if layer_type == "sliding_attention" else None


def _forward(cfg, params, tokens, compute_dtype, pools, positions,
             attend_of):
    """The embedding and every layer, for all three entry points:
    ``attend_of(layer_type)`` gives that type's ``attend(q, k, v, pools,
    layer index, index among its type) -> (mix, pools)``. Every forward is a
    serving forward: the experts take the grouped form over the stacked
    banks wherever ``MoELayer.grouped`` allows it. Returns the last layer's
    ``x`` and the pools."""
    tables = _rope(cfg)
    moe_layer = _moe(cfg)
    layers, bank = _bank_apart(
        cast_floating(params["layers"], compute_dtype, keep=FLOAT32_PARAMS),
        moe_layer)
    with jax.named_scope("embed"):
        x = embedding_lookup(params["embed"], tokens, compute_dtype)

    def block(layer_type):
        attend = attend_of(layer_type)

        def run(x, w, pools, index, i):
            return _block(
                cfg, x, w, bank, index, moe_layer, lambda u: attend(
                    *_qkv(cfg, w, u, tables[layer_type], positions), pools,
                    index, i))

        return run

    x, pools, _ = scan_stack(cfg.resolved_layer_types(), x, layers, pools,
                             {layer_type: block(layer_type)
                              for layer_type in KINDS})
    return x, pools


# --------------------------------------------------------------------------- #
# entry points
# --------------------------------------------------------------------------- #
def apply(cfg: MellumConfig, params: Params, tokens: jnp.ndarray, *,
          compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Whole sequences with no cache -> logits ``[b, s, vocab]`` float32."""
    def attend_of(layer_type):
        def attend(q, k, v, _pools, _index, _i):
            with jax.named_scope("attn_" + KINDS[layer_type]):
                return attention(q, k, v, causal=True,
                                 window=_window(cfg, layer_type)), None

        return attend

    x, _ = _forward(cfg, params, tokens, compute_dtype, None, None,
                    attend_of)
    return _head(cfg, params, x, compute_dtype)


# ---- KV-cached decode (v1-engine path): every layer keeps the whole
# context, a window layer masks what lies behind its window ---- #
def init_cache(cfg: MellumConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    shape = (cfg.num_layers, batch_size, max_len, cfg.num_kv_heads,
             cfg.head_size)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg: MellumConfig) -> Params:
    spec = ("layers", None, None, "kv_heads", None)
    return {"k": spec, "v": spec}


def apply_cached(cfg: MellumConfig, params: Params, tokens: jnp.ndarray,
                 cache: Params, cache_len: jnp.ndarray, *,
                 compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (tokens.shape[0],))
    positions = cache_len[:, None] + jnp.arange(tokens.shape[1])[None, :]
    dense = dense_kind_attention(cache, cache_len, positions)

    def attend_of(layer_type):
        attend = dense(KINDS[layer_type], _window(cfg, layer_type))
        return lambda q, k, v, pools, index, _i: attend(q, k, v, pools,
                                                        index)

    x, cache = _forward(cfg, params, tokens, compute_dtype, dict(cache),
                        positions, attend_of)
    return _head(cfg, params, x, compute_dtype), cache


# --------------------------------------------------------------------------- #
# Paged (blocked) KV-cache path - the v2 continuous-batching protocol, over
# two kinds of KV state
# --------------------------------------------------------------------------- #
def init_paged_cache(cfg: MellumConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16,
                     window_blocks: Optional[Dict[str, int]] = None,
                     slots: Optional[int] = None) -> Params:
    """``_paged.init_stack_pools``: ``k`` / ``v`` the full layers' pools,
    ``k_window`` / ``v_window`` the window layers'. ``slots``: taken and not
    used (no per-slot state), as a family without recurrent state does."""
    del slots
    return init_stack_pools(cfg.resolved_layer_types(), cfg.sliding_window,
                            num_blocks, window_blocks, cfg.num_kv_heads,
                            block_size, cfg.head_size, dtype)


def apply_paged(cfg: MellumConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the two-kind cache (prefill rows, chunks, decode
    steps or a mixed call): ``cohere2_moe.apply_paged``'s contract - a
    ``MixedCall`` or ``block_tables`` one segment a kind of KV state side by
    side (``_paged.kind_tables``; one table of the full kind's width serves
    both kinds from it), ``rows`` the rows the head scores. Rope takes every
    row's TRUE position whatever its kind's table is counted from."""
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    positions = row_positions(block_tables, context_lens, t)
    paged = paged_kind_attention(cache, block_tables, context_lens, valid,
                                 cfg.max_seq_len, window_kinds(cfg))

    def attend_of(layer_type):
        return lambda q, k, v, pools, _index, i: paged(
            KINDS[layer_type], q, k, v, pools, i)

    x, cache = _forward(cfg, params, tokens, compute_dtype, dict(cache),
                        positions, attend_of)
    return _head(cfg, params, gather_rows(x, rows), compute_dtype), cache
