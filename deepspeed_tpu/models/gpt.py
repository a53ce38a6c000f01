"""GPT-2/OPT-family model (learned positions, LayerNorm, GELU MLP, MHA).

Reference parity: the reference injects kernels into these HF families via
``module_inject/containers/{gpt2,gptneo,opt,bloom}.py`` and serves OPT in
inference v2 (``inference/v2/model_implementations/opt``). Same TPU-first
shape as ``models/llama.py``: stacked layers under ``lax.scan``, logical axis
names for the shared partitioner, op-registry norms/attention, KV-cached
decode path for the inference engines.

Covers GPT-2, OPT (pre-LN), and with ``post_ln=True`` the original
post-LN ordering (BLOOM-style alibi is not modeled)."""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops.attention import attention
from ._paged import (gather_rows, paged_attention_step, row_positions,
                     scan_layers)
from ._paged import init_paged_pools as _init_paged_pools
from ..ops.embedding import embedding_lookup
from ..ops.norms import layer_norm

Params = Dict[str, Any]

# checkpoint names this family's TRAINING block attaches (the selective-
# remat saveables; no "mlp_gate" — the GPT FFN has no gate projection)
CHECKPOINT_NAMES_EMITTED = ("qkv_proj", "attn_mix", "attn_out",
                            "mlp_up", "mlp_out")


@dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    intermediate_size: int = 3072
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    layer_norm_eps: float = 1e-5
    tie_embeddings: bool = True
    post_ln: bool = False     # True = original transformer/BLOOM ordering
    activation: str = "gelu"  # "gelu" (GPT-2) | "relu" (OPT)
    remat: bool = False
    remat_policy: str = "none"  # none | full | dots | any registry policy

    def __post_init__(self):
        if self.activation not in ("gelu", "relu"):
            raise ValueError(f"unsupported activation {self.activation!r} "
                             "(gelu | relu)")

    @property
    def head_size(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def num_params(self) -> int:
        h, i, v, L, s = (self.hidden_size, self.intermediate_size,
                         self.vocab_size, self.num_layers, self.max_seq_len)
        # weights 4h²+2hi; biases bqkv 3h + bo h + b_up i + b_down h; LN 4h
        block = 4 * h * h + 2 * h * i + 9 * h + i
        embed = v * h * (1 if self.tie_embeddings else 2) + s * h
        return L * block + embed + 2 * h

    @classmethod
    def tiny(cls, **kw) -> "GPTConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_layers=2, num_heads=4, max_seq_len=128)
        base.update(kw)
        return cls(**base)

    @classmethod
    def gpt2_small(cls) -> "GPTConfig":
        return cls()

    @classmethod
    def opt_1_3b(cls) -> "GPTConfig":
        return cls(vocab_size=50272, hidden_size=2048, intermediate_size=8192,
                   num_layers=24, num_heads=32, max_seq_len=2048)


def init(cfg: GPTConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    h, i, v, L = (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size,
                  cfg.num_layers)
    keys = jax.random.split(rng, 8)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    params: Params = {
        "embed": normal(keys[0], (v, h), h),
        "pos_embed": normal(keys[1], (cfg.max_seq_len, h), h),
        "layers": {
            "ln1_scale": jnp.ones((L, h), dtype),
            "ln1_bias": jnp.zeros((L, h), dtype),
            "wqkv": normal(keys[2], (L, h, 3 * h), h),
            "bqkv": jnp.zeros((L, 3 * h), dtype),
            "wo": normal(keys[3], (L, h, h), h),
            "bo": jnp.zeros((L, h), dtype),
            "ln2_scale": jnp.ones((L, h), dtype),
            "ln2_bias": jnp.zeros((L, h), dtype),
            "w_up": normal(keys[4], (L, h, i), h),
            "b_up": jnp.zeros((L, i), dtype),
            "w_down": normal(keys[5], (L, i, h), i),
            "b_down": jnp.zeros((L, h), dtype),
        },
        "final_ln_scale": jnp.ones((h,), dtype),
        "final_ln_bias": jnp.zeros((h,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(keys[6], (h, v), h)
    return params


def param_logical_axes(cfg: GPTConfig) -> Params:
    axes = {
        "embed": ("vocab", "embed"),
        "pos_embed": (None, "embed"),
        "layers": {
            "ln1_scale": ("layers", "embed"), "ln1_bias": ("layers", "embed"),
            "wqkv": ("layers", "embed", "heads"), "bqkv": ("layers", "heads"),
            "wo": ("layers", "heads", "embed"), "bo": ("layers", "embed"),
            "ln2_scale": ("layers", "embed"), "ln2_bias": ("layers", "embed"),
            "w_up": ("layers", "embed", "mlp"), "b_up": ("layers", "mlp"),
            "w_down": ("layers", "mlp", "embed"), "b_down": ("layers", "embed"),
        },
        "final_ln_scale": ("embed",),
        "final_ln_bias": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _attn(cfg: GPTConfig, x: jnp.ndarray, layer: Params,
          kv: Optional[Tuple] = None, cache_len: Optional[jnp.ndarray] = None):
    """QKV projection + (cached) attention. Returns (out, (k, v))."""
    b, t, h = x.shape
    nh, hd = cfg.num_heads, cfg.head_size
    qkv = checkpoint_name(x @ layer["wqkv"] + layer["bqkv"], "qkv_proj")
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = q.reshape(b, t, nh, hd)
    # GPT-2/OPT are MHA (kv heads == query heads): K/V enter the attention
    # op already at query width, so attention.gqa_native is a no-op here —
    # the gqa-native lint still traces this apply to pin that no widening
    # ever appears
    k = k.reshape(b, t, nh, hd)
    v = v.reshape(b, t, nh, hd)
    if kv is None:
        out = attention(q, k, v, causal=True)
    else:
        k_cache, v_cache = kv
        S = k_cache.shape[1]

        def write(c, n, s):
            return lax.dynamic_update_slice(c, n.astype(c.dtype), (s, 0, 0))

        k_cache = jax.vmap(write)(k_cache, k, cache_len)
        v_cache = jax.vmap(write)(v_cache, v, cache_len)
        kv_pos = jnp.arange(S)[None, None, None, :]
        q_abs = (cache_len[:, None] + jnp.arange(t)[None, :])[:, None, :, None]
        out = attention(q, k_cache, v_cache, causal=False,
                        mask=kv_pos <= q_abs)
        k, v = k_cache, v_cache
    out = checkpoint_name(out, "attn_mix")
    return out.reshape(b, t, nh * hd) @ layer["wo"] + layer["bo"], (k, v)


def _block(cfg: GPTConfig, x, layer, kv=None, cache_len=None,
           attn_call=None):
    """One block; ``attn_call(y) -> (attn_out, kv_state)`` overrides the
    default dense/cached attention (the paged path supplies its own)."""
    if attn_call is None:
        attn_call = lambda y: _attn(cfg, y, layer, kv, cache_len)  # noqa: E731
    eps = cfg.layer_norm_eps
    act = jax.nn.relu if cfg.activation == "relu" else jax.nn.gelu
    # "attn_out"/"mlp_out" mark the selective-remat saveables (identity
    # outside a targeting jax.checkpoint policy) — see the registry in
    # runtime/activation_checkpointing/checkpointing.py
    if cfg.post_ln:
        a, kv = attn_call(x)
        a = checkpoint_name(a, "attn_out")
        x = layer_norm(x + a, layer["ln1_scale"], layer["ln1_bias"], eps)
        up = checkpoint_name(x @ layer["w_up"] + layer["b_up"], "mlp_up")
        m = checkpoint_name(act(up) @ layer["w_down"], "mlp_out") \
            + layer["b_down"]
        x = layer_norm(x + m, layer["ln2_scale"], layer["ln2_bias"], eps)
    else:  # pre-LN (GPT-2/OPT)
        y = layer_norm(x, layer["ln1_scale"], layer["ln1_bias"], eps)
        a, kv = attn_call(y)
        x = x + checkpoint_name(a, "attn_out")
        y = layer_norm(x, layer["ln2_scale"], layer["ln2_bias"], eps)
        up = checkpoint_name(y @ layer["w_up"] + layer["b_up"], "mlp_up")
        x = x + checkpoint_name(act(up) @ layer["w_down"], "mlp_out") \
            + layer["b_down"]
    return x, kv


def _cast_layers(params: Params, dtype) -> Params:
    return jax.tree.map(lambda p: p.astype(dtype)
                        if jnp.issubdtype(p.dtype, jnp.floating) else p,
                        params["layers"])


def _head_split(cfg: GPTConfig, params: Params, x: jnp.ndarray,
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


def _head(cfg: GPTConfig, params: Params, x: jnp.ndarray,
          compute_dtype) -> jnp.ndarray:
    x, head = _head_split(cfg, params, x, compute_dtype)
    return (x @ head).astype(jnp.float32)


def apply(cfg: GPTConfig, params: Params, tokens: jnp.ndarray, *,
          positions: Optional[jnp.ndarray] = None,
          compute_dtype=jnp.bfloat16, return_hidden: bool = False):
    b, t = tokens.shape
    if positions is None:
        positions = jnp.arange(t)[None, :]
    x = (embedding_lookup(params["embed"], tokens, compute_dtype) + params["pos_embed"][positions].astype(compute_dtype)) \
        .astype(compute_dtype)
    layers = _cast_layers(params, compute_dtype)
    block = partial(_block, cfg)
    if cfg.remat:
        # the shared remat-policy registry: the policy the config names,
        # else the one the engine was named or chose (ac.remat_block)
        from ..runtime.activation_checkpointing import checkpointing as ac

        block = ac.remat_block(block, cfg.remat_policy)

    from ..comm import overlap as ov

    def scan_body(x, layer):
        x, _ = block(x, ov.constrain_scan_slice(layer))
        return x, None

    if ov.layer_prefetch_active():
        x, _ = ov.prefetch_scan(scan_body, x, layers)
    else:
        x, _ = lax.scan(scan_body, x, layers)
    if return_hidden:
        return _head_split(cfg, params, x, compute_dtype)
    return _head(cfg, params, x, compute_dtype)


# --- KV-cached inference path (engine ModelFamily protocol) ---------------- #
def init_cache(cfg: GPTConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    shape = (cfg.num_layers, batch_size, max_len, cfg.num_heads, cfg.head_size)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def cache_logical_axes(cfg: GPTConfig) -> Params:
    spec = ("layers", None, None, "heads", None)
    return {"k": spec, "v": spec}


def apply_cached(cfg: GPTConfig, params: Params, tokens: jnp.ndarray,
                 cache: Params, cache_len: jnp.ndarray, *,
                 compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (tokens.shape[0],))
    positions = jnp.minimum(cache_len[:, None] + jnp.arange(tokens.shape[1]),
                            cfg.max_seq_len - 1)
    x = (embedding_lookup(params["embed"], tokens, compute_dtype) + params["pos_embed"][positions].astype(compute_dtype)) \
        .astype(compute_dtype)
    layers = _cast_layers(params, compute_dtype)

    def scan_body(x, scanned):
        layer, k_c, v_c = scanned
        x, (k_c, v_c) = _block(cfg, x, layer, (k_c, v_c), cache_len)
        return x, (k_c, v_c)

    x, (nk, nv) = lax.scan(scan_body, x, (layers, cache["k"], cache["v"]))
    return _head(cfg, params, x, compute_dtype), {"k": nk, "v": nv}


# --------------------------------------------------------------------------- #
# Paged (blocked) KV-cache path — the v2 continuous-batching protocol
# (reference serves OPT through inference/v2; see models/llama.py for the
# block-table layout: fixed-width tables, block 0 is the trash block)
# --------------------------------------------------------------------------- #
def init_paged_cache(cfg: GPTConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16,
                     kv_quant_group: Optional[int] = None) -> Params:
    return _init_paged_pools(cfg.num_layers, num_blocks, cfg.num_heads,
                             block_size, cfg.head_size, dtype,
                             kv_quant_group)



def _attn_paged(cfg: GPTConfig, y: jnp.ndarray, layer: Params,
                k_cache, v_cache, block_tables, context_lens, valid,
                positions):
    b, t, _ = y.shape
    nh, hd = cfg.num_heads, cfg.head_size
    qkv = y @ layer["wqkv"] + layer["bqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    out, k_cache, v_cache = paged_attention_step(
        q.reshape(b, t, nh, hd), k.reshape(b, t, nh, hd),
        v.reshape(b, t, nh, hd), k_cache, v_cache, block_tables,
        context_lens, positions, valid)
    out = out.reshape(b, t, nh * hd) @ layer["wo"] + layer["bo"]
    return out, k_cache, v_cache


def apply_paged(cfg: GPTConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the paged cache (see llama.apply_paged for the
    contract); handles both LN orderings and the relu/gelu variants."""
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    positions = row_positions(block_tables, context_lens, t)
    # clamp ONLY the learned-position lookup; the cache scatter/mask must see
    # the true absolute positions or slots past max_seq_len silently collide
    pos_idx = jnp.minimum(positions, cfg.max_seq_len - 1)
    x = (embedding_lookup(params["embed"], tokens, compute_dtype)
         + params["pos_embed"][pos_idx].astype(compute_dtype))
    layers = _cast_layers(params, compute_dtype)

    def scan_body(x, scanned):
        layer, k_c, v_c = scanned

        def attn_call(y):
            out, nk, nv = _attn_paged(cfg, y, layer, k_c, v_c, block_tables,
                                      context_lens, valid, positions)
            return out, (nk, nv)

        x, kv = _block(cfg, x, layer, attn_call=attn_call)
        return x, kv

    x, cache = scan_layers(scan_body, x, layers, cache)
    return _head(cfg, params, gather_rows(x, rows), compute_dtype), cache


def loss_fn(cfg: GPTConfig, params: Params, batch: Dict[str, jnp.ndarray], *,
            compute_dtype=jnp.bfloat16):
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    logits = apply(cfg, params, inputs, compute_dtype=compute_dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss = -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))
    return loss, {"loss": loss}


def tiled_loss_fn(cfg: GPTConfig, params: Params,
                  batch: Dict[str, jnp.ndarray], *,
                  compute_dtype=jnp.bfloat16, shards: int = 8):
    """``loss_fn`` with the unembed matmul + CE fused per sequence tile —
    [B, S, V] logits are never materialized (``sequence.tiled_loss``)."""
    from ..sequence.tiled import tiled_fused_logits_loss

    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    hidden, head = apply(cfg, params, inputs, compute_dtype=compute_dtype,
                         return_hidden=True)
    loss = tiled_fused_logits_loss(hidden, head, labels, shards=shards)
    return loss, {"loss": loss}


def model_spec(cfg: GPTConfig, compute_dtype=jnp.bfloat16):
    from ..runtime.engine import ModelSpec

    return ModelSpec(
        name="gpt",
        init_fn=lambda rng: init(cfg, rng),
        loss_fn=lambda params, batch: loss_fn(cfg, params, batch,
                                              compute_dtype=compute_dtype),
        tiled_loss_fn=lambda params, batch, shards=8: tiled_loss_fn(
            cfg, params, batch, compute_dtype=compute_dtype, shards=shards),
        apply_fn=lambda params, tokens, **kw: apply(cfg, params, tokens,
                                                    compute_dtype=compute_dtype,
                                                    **kw),
        logical_axes=param_logical_axes(cfg),
        pipeline_capable=False,
    )
