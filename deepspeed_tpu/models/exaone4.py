"""EXAONE-4 family, written TPU-first.

Reference parity: ``inference/v2/model_implementations`` lists exaone4 as a
served family. Architecture deltas vs llama, all handled here:

- **Post-norm placement**: ``x = x + rms(attn(x)); x = x + rms(mlp(x))`` —
  the RMSNorm wraps the sublayer OUTPUT (no input norms).
- **QK-Norm**: per-head RMSNorm on q/k (as Qwen3).
- **Hybrid attention**: a layer-type pattern mixes sliding-window layers
  (RoPE + windowed causal mask) with global layers (full causal, NoPE — no
  rotary at all). Under ``lax.scan`` the per-layer variation rides two
  scanned scalars: the window size (∞ ≈ max_seq for global) and a
  rope-on/off flag resolved with ``jnp.where`` — compiler-friendly, no
  per-layer Python branching.

Same TPU shape as the sibling models: stacked layers, logical axis names
per param for the sharding-rule engine.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.attention import attention
from ._paged import (gather_rows, paged_attention_step, row_positions,
                     scan_layers)
from ._paged import init_paged_pools as _init_paged_pools
from ..ops.embedding import embedding_lookup
from ..ops.norms import rms_norm
from ..ops.rotary import apply_rotary, rope_frequencies

Params = Dict[str, Any]


@dataclass(frozen=True)
class Exaone4Config:
    vocab_size: int = 102400
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    max_seq_len: int = 4096
    sliding_window: Optional[int] = 4096
    sliding_window_pattern: int = 4   # every Nth layer is global
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    layer_types: Optional[Tuple[str, ...]] = None  # override the pattern

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    def resolved_layer_types(self) -> Tuple[str, ...]:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        if self.sliding_window is None:
            return ("full_attention",) * self.num_layers
        # HF pattern: every `pattern`-th layer (1-indexed) is global
        return tuple(
            "full_attention" if (i + 1) % self.sliding_window_pattern == 0
            else "sliding_attention" for i in range(self.num_layers))

    @classmethod
    def tiny(cls, **kw) -> "Exaone4Config":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_layers=4, num_heads=4, num_kv_heads=2,
                    max_seq_len=64, sliding_window=16,
                    sliding_window_pattern=2, rope_theta=10000.0)
        base.update(kw)
        return cls(**base)


def init(cfg: Exaone4Config, rng: jax.Array, dtype=jnp.float32) -> Params:
    h, hd = cfg.hidden_size, cfg.head_size
    L, nh, nkv = cfg.num_layers, cfg.num_heads, cfg.num_kv_heads
    i, v = cfg.intermediate_size, cfg.vocab_size
    keys = jax.random.split(rng, 8)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    params: Params = {
        "embed": normal(keys[0], (v, h), h),
        "layers": {
            "wq": normal(keys[1], (L, h, nh * hd), h),
            "wk": normal(keys[2], (L, h, nkv * hd), h),
            "wv": normal(keys[3], (L, h, nkv * hd), h),
            "wo": normal(keys[4], (L, nh * hd, h), nh * hd),
            "q_norm": jnp.ones((L, hd), dtype),
            "k_norm": jnp.ones((L, hd), dtype),
            "post_attn_norm": jnp.ones((L, h), dtype),
            "w_gate": normal(keys[5], (L, h, i), h),
            "w_up": normal(keys[6], (L, h, i), h),
            "w_down": normal(keys[7], (L, i, h), i),
            "post_mlp_norm": jnp.ones((L, h), dtype),
        },
        "final_norm": jnp.ones((h,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(jax.random.fold_in(rng, 99), (h, v), h)
    return params


def param_logical_axes(cfg: Exaone4Config) -> Params:
    axes = {
        "embed": ("vocab", "embed"),
        "layers": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "q_norm": ("layers", None),
            "k_norm": ("layers", None),
            "post_attn_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
            "post_mlp_norm": ("layers", "embed"),
        },
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _layer_scalars(cfg: Exaone4Config):
    """(windows [L], use_rope [L]) scanned alongside the stacked weights."""
    types = cfg.resolved_layer_types()
    big = 1 << 30  # effectively unwindowed
    windows = jnp.asarray(
        [cfg.sliding_window if t == "sliding_attention" else big
         for t in types], jnp.int32)
    # global NoPE: rotary only on sliding layers (when hybrid at all)
    use_rope = jnp.asarray(
        [1 if (cfg.sliding_window is None or t == "sliding_attention")
         else 0 for t in types], jnp.int32)
    return windows, use_rope


def _qkv(cfg: Exaone4Config, x, layer, cos, sin, positions, use_rope):
    b, s, _ = x.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    q = (x @ layer["wq"]).reshape(b, s, nh, hd)
    k = (x @ layer["wk"]).reshape(b, s, nkv, hd)
    v = (x @ layer["wv"]).reshape(b, s, nkv, hd)
    q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
    k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    q = jnp.where(use_rope > 0, apply_rotary(q, cos, sin, positions), q)
    k = jnp.where(use_rope > 0, apply_rotary(k, cos, sin, positions), k)
    return q, k, v


def _block(cfg: Exaone4Config, x, layer, cos, sin, positions,
           window, use_rope):
    b, s, h = x.shape
    nh, hd = cfg.num_heads, cfg.head_size
    q, k, v = _qkv(cfg, x, layer, cos, sin, positions, use_rope)
    if cfg.sliding_window is None:
        # pure-global config: plain causal keeps the Pallas flash path (a
        # dense mask would force the XLA fallback on every layer)
        attn_out = attention(q, k, v, causal=True)
    else:
        # per-layer windows are SCANNED traced scalars, so the static
        # flash `window=` fast path can't apply — the dense mask routes to
        # the XLA reference, which under attention.gqa_native computes
        # grouped einsums on the NARROW K/V (no q-width repeat; the
        # gqa-native lint traces this apply)
        q_pos = jnp.arange(s)[:, None]
        kv_pos = jnp.arange(s)[None, :]
        mask = (q_pos >= kv_pos) & (q_pos - kv_pos < window)
        attn_out = attention(q, k, v, causal=False, mask=mask[None, None])
    attn_out = attn_out.reshape(b, s, nh * hd) @ layer["wo"]
    x = x + rms_norm(attn_out, layer["post_attn_norm"], cfg.rms_norm_eps)
    mlp = (jax.nn.silu(x @ layer["w_gate"]) * (x @ layer["w_up"])) \
        @ layer["w_down"]
    return x + rms_norm(mlp, layer["post_mlp_norm"], cfg.rms_norm_eps)


def _cast_layers(params, compute_dtype):
    return jax.tree.map(lambda p: p.astype(compute_dtype)
                        if jnp.issubdtype(p.dtype, jnp.floating) else p,
                        params["layers"])


def _head_split(cfg, params, x, compute_dtype):
    """Final norm + unembed matrix minus the logits matmul — consumed by
    the tiled fused logits+loss head (``tiled_loss_fn``)."""
    x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                 cfg.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    return x, head.astype(compute_dtype)


def _head(cfg, params, x, compute_dtype):
    x, head = _head_split(cfg, params, x, compute_dtype)
    return (x @ head).astype(jnp.float32)


def apply(cfg: Exaone4Config, params: Params, tokens: jnp.ndarray, *,
          positions: Optional[jnp.ndarray] = None,
          compute_dtype=jnp.bfloat16, return_hidden: bool = False):
    x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len,
                                cfg.rope_theta)
    layers = _cast_layers(params, compute_dtype)
    windows, use_rope = _layer_scalars(cfg)

    def body(x, scanned):
        layer, window, rope = scanned
        return _block(cfg, x, layer, cos, sin, positions, window, rope), None

    x, _ = lax.scan(body, x, (layers, windows, use_rope))
    if return_hidden:
        return _head_split(cfg, params, x, compute_dtype)
    return _head(cfg, params, x, compute_dtype)


# ---- KV-cached decode (v1-engine path) ---- #
def init_cache(cfg: Exaone4Config, batch_size: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    shape = (cfg.num_layers, batch_size, max_len, cfg.num_kv_heads,
             cfg.head_size)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg: Exaone4Config) -> Params:
    spec = ("layers", None, None, "kv_heads", None)
    return {"k": spec, "v": spec}


def _write_cache(cache, new, starts):
    def one(c, n, s):
        return lax.dynamic_update_slice(c, n.astype(c.dtype), (s, 0, 0))

    return jax.vmap(one)(cache, new, starts)


def apply_cached(cfg: Exaone4Config, params: Params, tokens: jnp.ndarray,
                 cache: Params, cache_len: jnp.ndarray, *,
                 compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (tokens.shape[0],))
    b, t = tokens.shape
    nh, hd = cfg.num_heads, cfg.head_size
    x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len,
                                cfg.rope_theta)
    positions = cache_len[:, None] + jnp.arange(t)[None, :]
    layers = _cast_layers(params, compute_dtype)
    windows, use_rope = _layer_scalars(cfg)

    def body(x, scanned):
        layer, k_c, v_c, window, rope = scanned
        S = k_c.shape[1]
        q, k, v = _qkv(cfg, x, layer, cos, sin, positions, rope)
        k_c = _write_cache(k_c, k, cache_len)
        v_c = _write_cache(v_c, v, cache_len)
        kv_pos = jnp.arange(S)[None, None, None, :]
        q_abs = cache_len[:, None, None, None] + \
            jnp.arange(t)[None, None, :, None]
        mask = (kv_pos <= q_abs) & (q_abs - kv_pos < window)
        attn_out = attention(q, k_c, v_c, causal=False, mask=mask)
        attn_out = attn_out.reshape(b, t, nh * hd) @ layer["wo"]
        x = x + rms_norm(attn_out, layer["post_attn_norm"], cfg.rms_norm_eps)
        mlp = (jax.nn.silu(x @ layer["w_gate"]) * (x @ layer["w_up"])) \
            @ layer["w_down"]
        x = x + rms_norm(mlp, layer["post_mlp_norm"], cfg.rms_norm_eps)
        return x, (k_c, v_c)

    x, (new_k, new_v) = lax.scan(
        body, x, (layers, cache["k"], cache["v"], windows, use_rope))
    return _head(cfg, params, x, compute_dtype), {"k": new_k, "v": new_v}


def loss_fn(cfg: Exaone4Config, params: Params,
            batch: Dict[str, jnp.ndarray], *, compute_dtype=jnp.bfloat16):
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


def tiled_loss_fn(cfg: Exaone4Config, params: Params,
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


def model_spec(cfg: Exaone4Config, compute_dtype=jnp.bfloat16):
    from ..runtime.engine import ModelSpec

    return ModelSpec(
        name="exaone4",
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
# (reference lists exaone4 among the v2 model implementations). The hybrid
# sliding/global layers pass their scanned per-layer window to the paged
# kernels (decode and prefill both take it by scalar prefetch); block-table
# layout as in models/llama.py (block 0 = trash).
# --------------------------------------------------------------------------- #
def init_paged_cache(cfg: Exaone4Config, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16,
                     kv_quant_group: Optional[int] = None) -> Params:
    return _init_paged_pools(cfg.num_layers, num_blocks, cfg.num_kv_heads,
                             block_size, cfg.head_size, dtype,
                             kv_quant_group)


def apply_paged(cfg: Exaone4Config, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    b, t = tokens.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    if valid is None:
        valid = jnp.ones((b, t), bool)
    x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len,
                                cfg.rope_theta)
    positions = row_positions(block_tables, context_lens, t)
    layers = _cast_layers(params, compute_dtype)
    windows, use_rope = _layer_scalars(cfg)

    def scan_body(x, scanned):
        layer, k_c, v_c, window, rope = scanned
        q, k, v = _qkv(cfg, x, layer, cos, sin, positions, rope)
        # pure-global configs (static) take window=None (plain-causal
        # decode kernel); hybrid configs pass the traced per-layer window —
        # single-token decode runs the WINDOWED Pallas kernel (the window
        # rides scalar prefetch), prefill takes the gathered mask path
        attn_out, k_c, v_c = paged_attention_step(
            q, k, v, k_c, v_c, block_tables, context_lens, positions, valid,
            window=None if cfg.sliding_window is None else window)
        attn_out = attn_out.reshape(b, t, nh * hd) @ layer["wo"]
        x = x + rms_norm(attn_out, layer["post_attn_norm"], cfg.rms_norm_eps)
        mlp = (jax.nn.silu(x @ layer["w_gate"]) * (x @ layer["w_up"])) \
            @ layer["w_down"]
        x = x + rms_norm(mlp, layer["post_mlp_norm"], cfg.rms_norm_eps)
        return x, (k_c, v_c)

    x, cache = scan_layers(scan_body, x, layers, cache, windows, use_rope)
    return _head(cfg, params, gather_rows(x, rows), compute_dtype), cache
