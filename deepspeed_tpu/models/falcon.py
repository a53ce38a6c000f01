"""Falcon family (RW architecture), written TPU-first.

Reference parity: the reference serves Falcon via
``inference/v2/model_implementations/falcon`` and a v1 injection policy.
Falcon differs from the Llama family in three ways, all handled here:
parallel attention+MLP blocks (``x + attn(ln(x)) + mlp(ln(x))``), LayerNorm
(with bias) instead of RMSNorm, and MQA (classic 7B: one shared KV head) or
grouped KV (new decoder architecture, 40B/180B: separate ln_attn/ln_mlp).

Same TPU shape as ``models/llama``: stacked layers under ``lax.scan``,
logical axis names per param, attention through the op registry.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import attention
from ._paged import (gather_rows, paged_attention_step, row_positions,
                     scan_layers)
from ._paged import init_paged_pools as _init_paged_pools
from ..ops.embedding import embedding_lookup
from ..ops.norms import layer_norm
from ..ops.rotary import apply_rotary, rope_frequencies

Params = Dict[str, Any]


@dataclass(frozen=True)
class FalconConfig:
    vocab_size: int = 65024
    hidden_size: int = 4544
    num_layers: int = 32
    num_heads: int = 71
    num_kv_heads: int = 1          # classic 7B MQA
    max_seq_len: int = 2048
    parallel_attn: bool = True
    new_decoder_architecture: bool = False
    layer_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    attention_bias: bool = False
    tie_embeddings: bool = True    # falcon ties lm_head to word embeddings

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size

    @classmethod
    def tiny(cls, **kw) -> "FalconConfig":
        base = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
                    num_kv_heads=1, max_seq_len=128)
        base.update(kw)
        return cls(**base)


def init(cfg: FalconConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    h, hd = cfg.hidden_size, cfg.head_size
    L, nh, nkv, v = cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.vocab_size
    i = cfg.intermediate_size
    keys = jax.random.split(rng, 8)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)

    params: Params = {
        "embed": normal(keys[0], (v, h), h),
        "layers": {
            "ln_attn_scale": jnp.ones((L, h), dtype),
            "ln_attn_bias": jnp.zeros((L, h), dtype),
            "wq": normal(keys[1], (L, h, nh * hd), h),
            "wk": normal(keys[2], (L, h, nkv * hd), h),
            "wv": normal(keys[3], (L, h, nkv * hd), h),
            "wo": normal(keys[4], (L, nh * hd, h), nh * hd),
            "w_up": normal(keys[5], (L, h, i), h),
            "w_down": normal(keys[6], (L, i, h), i),
        },
        "final_ln_scale": jnp.ones((h,), dtype),
        "final_ln_bias": jnp.zeros((h,), dtype),
    }
    if cfg.new_decoder_architecture or not cfg.parallel_attn:
        # 40B+: parallel block with separate MLP norm; sequential classic
        # (rw-1b): distinct post-attention norm
        params["layers"]["ln_mlp_scale"] = jnp.ones((L, h), dtype)
        params["layers"]["ln_mlp_bias"] = jnp.zeros((L, h), dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(keys[7], (h, v), h)
    return params


def param_logical_axes(cfg: FalconConfig) -> Params:
    axes = {
        "embed": ("vocab", "embed"),
        "layers": {
            "ln_attn_scale": ("layers", "embed"),
            "ln_attn_bias": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_ln_scale": ("embed",),
        "final_ln_bias": ("embed",),
    }
    if cfg.new_decoder_architecture or not cfg.parallel_attn:
        axes["layers"]["ln_mlp_scale"] = ("layers", "embed")
        axes["layers"]["ln_mlp_bias"] = ("layers", "embed")
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _attn_part(cfg: FalconConfig, y: jnp.ndarray, layer: Params,
               cos, sin, positions, mask_args=None) -> jnp.ndarray:
    b, s, _ = y.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    q = (y @ layer["wq"]).reshape(b, s, nh, hd)
    k = (y @ layer["wk"]).reshape(b, s, nkv, hd)
    v = (y @ layer["wv"]).reshape(b, s, nkv, hd)
    q = apply_rotary(q, cos, sin, positions)
    k = apply_rotary(k, cos, sin, positions)
    # K/V pass NARROW (classic Falcon MQA: ONE kv head) into the attention
    # op — under attention.gqa_native the flash kernels keep them narrow
    # end to end (nq× less KV HBM traffic; the gqa-native lint traces this)
    out = attention(q, k, v, causal=True)
    return out.reshape(b, s, nh * hd) @ layer["wo"]


def _block(cfg: FalconConfig, x: jnp.ndarray, layer: Params,
           cos, sin, positions) -> jnp.ndarray:
    """Parallel Falcon block: x + attn(ln_attn(x)) + mlp(ln_mlp_or_attn(x))."""
    y_attn = layer_norm(x, layer["ln_attn_scale"], layer["ln_attn_bias"],
                        cfg.layer_norm_eps)
    if cfg.new_decoder_architecture:
        y_mlp = layer_norm(x, layer["ln_mlp_scale"], layer["ln_mlp_bias"],
                           cfg.layer_norm_eps)
    else:
        y_mlp = y_attn
    attn_out = _attn_part(cfg, y_attn, layer, cos, sin, positions)
    mlp_out = jax.nn.gelu(y_mlp @ layer["w_up"], approximate=False) @ layer["w_down"]
    if cfg.parallel_attn:
        return x + attn_out + mlp_out
    # sequential variant (parallel_attn=False checkpoints): the second norm
    # is the checkpoint's post_attention_layernorm (imported as ln_mlp_*)
    x = x + attn_out
    y2 = layer_norm(x, layer["ln_mlp_scale"], layer["ln_mlp_bias"],
                    cfg.layer_norm_eps)
    return x + jax.nn.gelu(y2 @ layer["w_up"], approximate=False) @ layer["w_down"]


def _head_split(cfg: FalconConfig, params: Params, x: jnp.ndarray,
                compute_dtype):
    """Final norm + unembed matrix minus the logits matmul — consumed by
    the tiled fused logits+loss head (``tiled_loss_fn``)."""
    x = layer_norm(x, params["final_ln_scale"].astype(compute_dtype),
                   params["final_ln_bias"].astype(compute_dtype),
                   cfg.layer_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return x, head.astype(compute_dtype)


def _head(cfg: FalconConfig, params: Params, x: jnp.ndarray,
          compute_dtype) -> jnp.ndarray:
    x, head = _head_split(cfg, params, x, compute_dtype)
    return (x @ head).astype(jnp.float32)


def _cast_layers(params: Params, compute_dtype):
    return jax.tree.map(lambda p: p.astype(compute_dtype)
                        if jnp.issubdtype(p.dtype, jnp.floating) else p,
                        params["layers"])


def apply(cfg: FalconConfig, params: Params, tokens: jnp.ndarray, *,
          positions: Optional[jnp.ndarray] = None,
          compute_dtype=jnp.bfloat16, return_hidden: bool = False):
    x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len, cfg.rope_theta)
    layers = _cast_layers(params, compute_dtype)
    block = partial(_block, cfg)

    from ..comm import overlap as ov

    def scan_body(x, layer):
        return block(x, ov.constrain_scan_slice(layer),
                     cos, sin, positions), None

    x, _ = lax.scan(scan_body, x, layers)
    if return_hidden:
        return _head_split(cfg, params, x, compute_dtype)
    return _head(cfg, params, x, compute_dtype)


# ---- KV-cached decode (v1-engine path) ---- #
def init_cache(cfg: FalconConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    L, nkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_size
    shape = (L, batch_size, max_len, nkv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg: FalconConfig) -> Params:
    spec = ("layers", None, None, "kv_heads", None)
    return {"k": spec, "v": spec}


def _write_cache(cache, new, starts):
    def one(c, n, s):
        return lax.dynamic_update_slice(c, n.astype(c.dtype), (s, 0, 0))

    return jax.vmap(one)(cache, new, starts)


def apply_cached(cfg: FalconConfig, params: Params, tokens: jnp.ndarray,
                 cache: Params, cache_len: jnp.ndarray, *,
                 compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (tokens.shape[0],))
    b, t = tokens.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len, cfg.rope_theta)
    positions = cache_len[:, None] + jnp.arange(t)[None, :]
    layers = _cast_layers(params, compute_dtype)

    def scan_body(x, scanned):
        layer, k_c, v_c = scanned
        S = k_c.shape[1]
        y_attn = layer_norm(x, layer["ln_attn_scale"], layer["ln_attn_bias"],
                            cfg.layer_norm_eps)
        y_mlp = layer_norm(x, layer["ln_mlp_scale"], layer["ln_mlp_bias"],
                           cfg.layer_norm_eps) \
            if cfg.new_decoder_architecture else y_attn
        q = (y_attn @ layer["wq"]).reshape(b, t, nh, hd)
        k = (y_attn @ layer["wk"]).reshape(b, t, nkv, hd)
        v = (y_attn @ layer["wv"]).reshape(b, t, nkv, hd)
        q = apply_rotary(q, cos, sin, positions)
        k = apply_rotary(k, cos, sin, positions)
        k_c = _write_cache(k_c, k, cache_len)
        v_c = _write_cache(v_c, v, cache_len)
        kv_pos = jnp.arange(S)[None, None, None, :]
        q_abs = cache_len[:, None, None, None] + jnp.arange(t)[None, None, :, None]
        mask = kv_pos <= q_abs
        attn_out = attention(q, k_c, v_c, causal=False, mask=mask)
        attn_out = attn_out.reshape(b, t, nh * hd) @ layer["wo"]
        if cfg.parallel_attn:
            mlp_out = jax.nn.gelu(y_mlp @ layer["w_up"], approximate=False) \
                @ layer["w_down"]
            x = x + attn_out + mlp_out
        else:
            x = x + attn_out
            y2 = layer_norm(x, layer["ln_mlp_scale"], layer["ln_mlp_bias"],
                            cfg.layer_norm_eps)
            x = x + jax.nn.gelu(y2 @ layer["w_up"], approximate=False) \
                @ layer["w_down"]
        return x, (k_c, v_c)

    x, (new_k, new_v) = lax.scan(scan_body, x, (layers, cache["k"], cache["v"]))
    return _head(cfg, params, x, compute_dtype), {"k": new_k, "v": new_v}


def loss_fn(cfg: FalconConfig, params: Params, batch: Dict[str, jnp.ndarray], *,
            compute_dtype=jnp.bfloat16):
    tokens = batch["tokens"]
    if "labels" in batch:
        inputs, labels = tokens, batch["labels"]
    else:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
    logits = apply(cfg, params, inputs, compute_dtype=compute_dtype)
    valid = labels != -100
    safe = jnp.where(valid, labels, 0)
    logp = jax.nn.log_softmax(logits, axis=-1)
    tl = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    loss = jnp.where(valid, tl, 0.0).sum() / jnp.maximum(valid.sum(), 1)
    return loss, {"loss": loss, "ntokens": valid.sum()}


def tiled_loss_fn(cfg: FalconConfig, params: Params,
                  batch: Dict[str, jnp.ndarray], *,
                  compute_dtype=jnp.bfloat16, shards: int = 8):
    """``loss_fn`` with the unembed matmul + CE fused per sequence tile —
    [B, S, V] logits are never materialized (``sequence.tiled_loss``)."""
    from ..sequence.tiled import tiled_fused_logits_loss

    tokens = batch["tokens"]
    if "labels" in batch:
        inputs, labels = tokens, batch["labels"]
    else:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
    hidden, head = apply(cfg, params, inputs, compute_dtype=compute_dtype,
                         return_hidden=True)
    loss = tiled_fused_logits_loss(hidden, head, labels, shards=shards)
    return loss, {"loss": loss, "ntokens": (labels != -100).sum()}


def model_spec(cfg: FalconConfig, compute_dtype=jnp.bfloat16):
    from ..runtime.engine import ModelSpec

    return ModelSpec(
        name="falcon",
        init_fn=lambda rng: init(cfg, rng),
        loss_fn=lambda params, batch: loss_fn(cfg, params, batch,
                                              compute_dtype=compute_dtype),
        tiled_loss_fn=lambda params, batch, shards=8: tiled_loss_fn(
            cfg, params, batch, compute_dtype=compute_dtype, shards=shards),
        apply_fn=lambda params, tokens, **kw: apply(
            cfg, params, tokens, compute_dtype=compute_dtype, **kw),
        logical_axes=param_logical_axes(cfg),
        pipeline_capable=False,
    )


# --------------------------------------------------------------------------- #
# Paged (blocked) KV-cache path — the v2 continuous-batching protocol
# (reference serves Falcon through inference/v2; block-table layout as in
# models/llama.py: fixed-width tables, block 0 is the trash block)
# --------------------------------------------------------------------------- #
def init_paged_cache(cfg: FalconConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16,
                     kv_quant_group: Optional[int] = None) -> Params:
    return _init_paged_pools(cfg.num_layers, num_blocks, cfg.num_kv_heads,
                             block_size, cfg.head_size, dtype,
                             kv_quant_group)


def apply_paged(cfg: FalconConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the paged cache (see llama.apply_paged for the
    contract); handles the parallel / sequential / new-decoder variants."""
    b, t = tokens.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    if valid is None:
        valid = jnp.ones((b, t), bool)
    x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len, cfg.rope_theta)
    positions = row_positions(block_tables, context_lens, t)
    layers = _cast_layers(params, compute_dtype)

    def scan_body(x, scanned):
        layer, k_c, v_c = scanned
        y_attn = layer_norm(x, layer["ln_attn_scale"], layer["ln_attn_bias"],
                            cfg.layer_norm_eps)
        y_mlp = layer_norm(x, layer["ln_mlp_scale"], layer["ln_mlp_bias"],
                           cfg.layer_norm_eps) \
            if cfg.new_decoder_architecture else y_attn
        q = (y_attn @ layer["wq"]).reshape(b, t, nh, hd)
        k = (y_attn @ layer["wk"]).reshape(b, t, nkv, hd)
        v = (y_attn @ layer["wv"]).reshape(b, t, nkv, hd)
        q = apply_rotary(q, cos, sin, positions)
        k = apply_rotary(k, cos, sin, positions)
        attn_out, k_c, v_c = paged_attention_step(
            q, k, v, k_c, v_c, block_tables, context_lens, positions, valid)
        attn_out = attn_out.reshape(b, t, nh * hd) @ layer["wo"]
        if cfg.parallel_attn:
            mlp_out = jax.nn.gelu(y_mlp @ layer["w_up"], approximate=False) \
                @ layer["w_down"]
            x = x + attn_out + mlp_out
        else:
            x = x + attn_out
            y2 = layer_norm(x, layer["ln_mlp_scale"], layer["ln_mlp_bias"],
                            cfg.layer_norm_eps)
            x = x + jax.nn.gelu(y2 @ layer["w_up"], approximate=False) \
                @ layer["w_down"]
        return x, (k_c, v_c)

    x, cache = scan_layers(scan_body, x, layers, cache)
    return _head(cfg, params, gather_rows(x, rows), compute_dtype), cache
