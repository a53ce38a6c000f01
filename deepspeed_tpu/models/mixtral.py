"""Mixtral-family model: Llama backbone with MoE FFN (expert parallel).

Reference parity: the reference serves mixtral via
``inference/v2/model_implementations/mixtral`` and trains MoE via
``deepspeed/moe`` — this is the training+inference model family for MoE here.
Stacked-layer ``lax.scan`` like ``models/llama.py``; each block's FFN is the
expert bank with top-k routing; the load-balancing aux loss accumulates
through the scan and is added to the LM loss.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..moe.layer import BANK, MoELayer, init_moe_ffn, moe_ffn_logical_axes
from ..moe.sharded_moe import compute_capacity, row_tile
from ..ops.attention import attention
from ._paged import (gather_rows, init_index_pool, paged_attention_step,
                     row_positions, scan_layers, sparse_attention_step)
from ._paged import init_paged_pools as _init_paged_pools
from ..ops.embedding import embedding_lookup
from ..ops.norms import rms_norm
from ..ops.pallas.grouped_matmul import ROW_SUBTILE
from ..ops.rotary import apply_rotary, rope_frequencies
from . import llama as llama_mod

Params = Dict[str, Any]

# checkpoint names this family's TRAINING block attaches (the selective-
# remat saveables; the MoE expert matmuls stay unnamed — their dispatch
# layout is the compact/einsum implementation's concern)
CHECKPOINT_NAMES_EMITTED = ("qkv_proj", "attn_mix", "attn_out", "mlp_out")


@dataclass(frozen=True)
class SparseAttention:
    """A learned token selection inside attention (DeepSeek-Sparse-Attention's
    indexer on grouped-query attention): ``index_heads`` index queries of
    ``index_head_dim`` a token score every cached token's ONE index key, and
    attention reads the ``topk`` best-scoring tokens alone
    (``ops/pallas/paged_sparse_attention.py`` has the equations)."""
    index_heads: int
    index_head_dim: int
    topk: int


@dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    min_capacity: int = 4
    drop_tokens: bool = True
    aux_loss_coef: float = 0.01
    max_seq_len: int = 4096
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-5
    remat: bool = False
    remat_policy: str = "none"  # none | full | dots | any registry policy
    # Qwen2-MoE extensions (reference .../qwen_v2_moe): QKV biases, raw
    # (unnormalized) top-k gates, and a sigmoid-gated shared dense expert
    attention_bias: bool = False
    norm_topk_prob: bool = True
    shared_expert_intermediate_size: int = 0
    # OLMoE: an RMSNorm with a learned weight over the WHOLE q and k
    # projections (nh*hd and nkv*hd wide), before the split into heads and
    # before rope (models/llama.py's ``qk_norm`` norms each head by itself)
    qk_proj_norm: bool = False
    # MoE dispatch implementation: 'einsum' (dense one-hot, MXU) or
    # 'compact' (index-table gather/scatter) — see moe/layer.py
    moe_dispatch: str = "einsum"
    # a head's width where it is not ``hidden_size / num_heads`` (None: it is)
    head_dim: Optional[int] = None
    # an RMSNorm over each HEAD of q and k, before rope (models/llama.py's
    # ``qk_norm``; ``qk_proj_norm`` above is the other kind)
    qk_norm: bool = False
    # a learned token selection inside attention (None: attention reads the
    # whole context)
    sparse_attention: Optional[SparseAttention] = None
    # one chip's share of an expert-parallel deployment: ``(first, count)``,
    # the experts this program HOLDS of the ``num_experts`` the router
    # chooses among (None: all of them). What the absent experts would add
    # is left out (moe/layer.py)
    experts_held: Optional[Tuple[int, int]] = None

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @classmethod
    def tiny(cls, **kw) -> "MixtralConfig":
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_layers=2, num_heads=4, num_kv_heads=2, num_experts=4,
                    top_k=2, max_seq_len=128, rope_theta=10000.0)
        base.update(kw)
        return cls(**base)


def init(cfg: MixtralConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    h, hd = cfg.hidden_size, cfg.head_size
    L, nh, nkv, v = cfg.num_layers, cfg.num_heads, cfg.num_kv_heads, cfg.vocab_size
    keys = jax.random.split(rng, 8)

    def normal(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5).astype(dtype)

    def one_moe(k):
        if cfg.experts_held is None:
            p = init_moe_ffn(k, cfg.num_experts, h, cfg.intermediate_size,
                             dtype)
        else:
            p = init_moe_ffn(k, cfg.experts_held[1], h, cfg.intermediate_size,
                             dtype, routed=cfg.num_experts)
        si = cfg.shared_expert_intermediate_size
        if si:
            ks = jax.random.split(jax.random.fold_in(k, 7), 4)
            scale_h = jnp.float32(h) ** -0.5
            p["shared_w_gate"] = (jax.random.normal(ks[0], (h, si)) * scale_h).astype(dtype)
            p["shared_w_up"] = (jax.random.normal(ks[1], (h, si)) * scale_h).astype(dtype)
            p["shared_w_down"] = (jax.random.normal(ks[2], (si, h)) *
                                  jnp.float32(si) ** -0.5).astype(dtype)
            p["shared_gate"] = (jax.random.normal(ks[3], (h, 1)) * scale_h).astype(dtype)
        return p

    moe = jax.vmap(one_moe)(jax.random.split(keys[5], L))
    out = {
        "embed": normal(keys[0], (v, h), h),
        "layers": {
            "attn_norm": jnp.ones((L, h), dtype),
            "wq": normal(keys[1], (L, h, nh * hd), h),
            "wk": normal(keys[2], (L, h, nkv * hd), h),
            "wv": normal(keys[3], (L, h, nkv * hd), h),
            "wo": normal(keys[4], (L, nh * hd, h), nh * hd),
            "mlp_norm": jnp.ones((L, h), dtype),
            "moe": moe,   # leaves: [L, E, ...] / router [L, H, E]
        },
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": normal(keys[6], (h, v), h),
    }
    if cfg.attention_bias:
        out["layers"]["bq"] = jnp.zeros((L, nh * hd), dtype)
        out["layers"]["bk"] = jnp.zeros((L, nkv * hd), dtype)
        out["layers"]["bv"] = jnp.zeros((L, nkv * hd), dtype)
    if cfg.qk_proj_norm:
        out["layers"]["q_norm"] = jnp.ones((L, nh * hd), dtype)
        out["layers"]["k_norm"] = jnp.ones((L, nkv * hd), dtype)
    if cfg.qk_norm:
        if cfg.qk_proj_norm:
            raise ValueError("qk_norm and qk_proj_norm are two norms of the "
                             "same projections: a model has one")
        out["layers"]["q_norm"] = jnp.ones((L, hd), dtype)
        out["layers"]["k_norm"] = jnp.ones((L, hd), dtype)
    if cfg.sparse_attention is not None:
        sa = cfg.sparse_attention
        ki = jax.random.split(keys[7], 3)
        out["layers"]["wq_idx"] = normal(
            ki[0], (L, h, sa.index_heads * sa.index_head_dim), h)
        out["layers"]["wk_idx"] = normal(ki[1], (L, h, sa.index_head_dim), h)
        out["layers"]["ww_idx"] = normal(ki[2], (L, h, sa.index_heads), h)
    return out


def param_logical_axes(cfg: MixtralConfig) -> Params:
    moe_axes = {k: ("layers",) + tuple(v) for k, v in moe_ffn_logical_axes().items()}
    if cfg.shared_expert_intermediate_size:
        moe_axes.update({"shared_w_gate": ("layers", "embed", "mlp"),
                         "shared_w_up": ("layers", "embed", "mlp"),
                         "shared_w_down": ("layers", "mlp", "embed"),
                         "shared_gate": ("layers", "embed", None)})
    axes = {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", "embed"),
            "moe": moe_axes,
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }
    if cfg.attention_bias:
        axes["layers"]["bq"] = ("layers", "heads")
        axes["layers"]["bk"] = ("layers", "kv_heads")
        axes["layers"]["bv"] = ("layers", "kv_heads")
    if cfg.qk_proj_norm:
        axes["layers"]["q_norm"] = ("layers", "heads")
        axes["layers"]["k_norm"] = ("layers", "kv_heads")
    if cfg.qk_norm:
        axes["layers"]["q_norm"] = ("layers", None)
        axes["layers"]["k_norm"] = ("layers", None)
    if cfg.sparse_attention is not None:
        # the indexer is whole on every chip, as its one key head must be
        for name in ("wq_idx", "wk_idx", "ww_idx"):
            axes["layers"][name] = ("layers", "embed", None)
    return axes


def _qkv(cfg, layer, y, cos, sin, positions=None, save=False):
    """q ``[b, t, nh, hd]``, k and v ``[b, t, nkv, hd]`` of one block from
    its normed input ``y [b, t, h]``: projection, bias (Qwen2-MoE), the norm
    over the whole projection (OLMoE), the split into heads, rope at
    ``positions`` (the first ``t`` if None). ``save`` tags the projections as
    the training block's ``qkv_proj`` saveables (identity outside a
    selective-remat policy; POLICY_SAVED_NAMES in
    activation_checkpointing/checkpointing)."""
    b, t, _ = y.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    q, k, v = y @ layer["wq"], y @ layer["wk"], y @ layer["wv"]
    if "bq" in layer:
        q, k, v = q + layer["bq"], k + layer["bk"], v + layer["bv"]
    if "q_norm" in layer and not cfg.qk_norm:
        q = rms_norm(q, layer["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, layer["k_norm"], cfg.rms_norm_eps)
    if save:
        q, k, v = (checkpoint_name(a, "qkv_proj") for a in (q, k, v))
    if cfg.qk_norm:     # each head by itself
        q = rms_norm(q.reshape(b, t, nh, hd), layer["q_norm"],
                     cfg.rms_norm_eps)
        k = rms_norm(k.reshape(b, t, nkv, hd), layer["k_norm"],
                     cfg.rms_norm_eps)
    q = apply_rotary(q.reshape(b, t, nh, hd), cos, sin, positions)
    k = apply_rotary(k.reshape(b, t, nkv, hd), cos, sin, positions)
    return q, k, v.reshape(b, t, nkv, hd)


def index_vectors(cfg, layer, y, cos_i, sin_i, positions=None):
    """The indexer's view of one block's normed input ``y [b, t, h]``: index
    queries ``[b, t, H, d]`` and the ONE index key ``[b, t, d]``, both roped
    over all ``d`` dims at the model's theta, and the index heads' weights
    ``[b, t, H]``."""
    sa = cfg.sparse_attention
    b, t, _ = y.shape
    q_idx = apply_rotary(
        (y @ layer["wq_idx"]).reshape(b, t, sa.index_heads,
                                      sa.index_head_dim),
        cos_i, sin_i, positions)
    k_idx = apply_rotary((y @ layer["wk_idx"])[:, :, None], cos_i, sin_i,
                         positions)[:, :, 0]
    return q_idx, k_idx, y @ layer["ww_idx"]


def _selected_mask(cfg, q_idx, k_idx, w_idx, k_live):
    """``[b, t, S]``: which of the ``S`` keys each query row may read -
    the ``topk`` of largest index score among those ``k_live [b, t, S]``
    marks as the row's own (equal scores: the lower position), all of them
    while they are fewer. The dense form of what the paged path computes a
    block table at a time, for ``apply`` and ``apply_cached``."""
    from ..ops.pallas.paged_sparse_attention import (
        index_scores_dense, paged_sparse_select_xla, selected)

    s = index_scores_dense(q_idx, k_idx, w_idx)
    b, t, S = s.shape
    # the row's own keys are a prefix of the positions: its last one
    last = jnp.sum(k_live, axis=-1, dtype=jnp.int32) - 1
    tau, cut = paged_sparse_select_xla(
        s.reshape(b * t, S), last.reshape(-1),
        topk=cfg.sparse_attention.topk)
    keep = selected(s, jnp.arange(S)[None, None, :],
                    tau.reshape(b, t, 1), cut.reshape(b, t, 1))
    return jnp.logical_and(keep, k_live)


def index_rope(cfg):
    """The indexer's rope tables (its head is narrower than attention's),
    None without an indexer."""
    if cfg.sparse_attention is None:
        return None
    return rope_frequencies(cfg.sparse_attention.index_head_dim,
                            cfg.max_seq_len, cfg.rope_theta)


def _bank_apart(layers, moe_layer):
    """``(layers, bank)`` of a serving forward. Where its MoE calls take the
    grouped form: the stacked layers WITHOUT the expert banks, for a layer
    scan to slice, and the ``[L, E, ...]`` banks whole - the grouped matmul is a Mosaic call, which reads a block
    of the stack where it lies but would have a scanned slice of it copied
    out first, a layer's whole bank, every layer (PERF.md Findings, PR 41).
    Where they build slabs (a program over several devices, ``"compact"``):
    the layers as they are and no bank apart - an XLA fusion reads its
    scanned slice in place, and the program is what it was."""
    if not moe_layer.grouped():
        return layers, {}
    moe = layers["moe"]
    bank = {n: moe[n] for n in BANK if n in moe}
    rest = {n: w for n, w in moe.items() if n not in BANK}
    return {**layers, "moe": rest}, bank


def _head_split(cfg, params, x, compute_dtype):
    """Final norm + unembed matrix minus the logits matmul — consumed by
    the tiled fused logits+loss head (``tiled_loss_fn``)."""
    with jax.named_scope("norm"):
        x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                     cfg.rms_norm_eps)
    return x, params["lm_head"].astype(compute_dtype)


def _head(cfg, params, x, compute_dtype):
    x, head = _head_split(cfg, params, x, compute_dtype)
    with jax.named_scope("logits"):
        return (x @ head).astype(jnp.float32)


def apply(cfg: MixtralConfig, params: Params, tokens: jnp.ndarray, *,
          compute_dtype=jnp.bfloat16, return_hidden: bool = False):
    """Forward → (logits [b, s, vocab] fp32, total_aux_loss); with
    ``return_hidden`` → (normed hidden, unembed matrix, total_aux_loss)."""
    with jax.named_scope("embed"):
        x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len, cfg.rope_theta)
    rope_idx = index_rope(cfg)
    moe_layer = MoELayer(cfg.num_experts, cfg.top_k, cfg.capacity_factor,
                         cfg.min_capacity, cfg.drop_tokens,
                         norm_topk=cfg.norm_topk_prob,
                         dispatch=cfg.moe_dispatch, held=cfg.experts_held)

    layers = jax.tree.map(lambda p: p.astype(compute_dtype)
                          if jnp.issubdtype(p.dtype, jnp.floating) else p,
                          params["layers"])

    def block(x, layer):
        b, s, h = x.shape
        nh, hd = cfg.num_heads, cfg.head_size
        # named scopes (norm / attn, and moe_router / moe_experts inside
        # the MoE layer): metadata a trace reduction sums device time by
        with jax.named_scope("norm"):
            y = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        with jax.named_scope("attn"):
            q, k, v = _qkv(cfg, layer, y, cos, sin, save=True)
            # K/V pass NARROW (nkv heads) into the attention op: widening —
            # when the gqa_native kernels are off — happens inside the op,
            # never here (the gqa-native lint traces this apply)
            if cfg.sparse_attention is None:
                mix = attention(q, k, v, causal=True)
            else:
                causal = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)),
                                          (b, s, s))
                mix = attention(q, k, v, causal=False, mask=_selected_mask(
                    cfg, *index_vectors(cfg, layer, y, *rope_idx),
                    causal)[:, None])
            x = x + checkpoint_name(
                checkpoint_name(mix, "attn_mix")
                .reshape(b, s, nh * hd) @ layer["wo"], "attn_out")
        with jax.named_scope("norm"):
            y = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
        ffn_out, aux = moe_layer(layer["moe"], y)
        return x + checkpoint_name(ffn_out, "mlp_out"), aux

    if cfg.remat:
        # the shared remat-policy registry: the policy the config names,
        # else the one the engine was named or chose (ac.remat_block)
        from ..runtime.activation_checkpointing import checkpointing as ac

        block = ac.remat_block(block, cfg.remat_policy)

    from ..comm import overlap as ov

    def scan_body(x, layer):
        x, aux = block(x, ov.constrain_scan_slice(layer))
        return x, aux

    if ov.layer_prefetch_active():
        x, aux_losses = ov.prefetch_scan(scan_body, x, layers)
    else:
        x, aux_losses = lax.scan(scan_body, x, layers)
    if return_hidden:
        hidden, head = _head_split(cfg, params, x, compute_dtype)
        return hidden, head, jnp.sum(aux_losses)
    return _head(cfg, params, x, compute_dtype), jnp.sum(aux_losses)


# --- KV-cached inference path (MoE decode; reference
# ``inference/v2/model_implementations/mixtral``) ------------------------- #
def init_cache(cfg: MixtralConfig, batch_size: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    shape = (cfg.num_layers, batch_size, max_len, cfg.num_kv_heads,
             cfg.head_size)
    cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if cfg.sparse_attention is not None:
        cache["kI"] = jnp.zeros(
            shape[:3] + (1, cfg.sparse_attention.index_head_dim), dtype)
    return cache


def cache_logical_axes(cfg: MixtralConfig) -> Params:
    spec = ("layers", None, None, "kv_heads", None)
    axes = {"k": spec, "v": spec}
    if cfg.sparse_attention is not None:
        axes["kI"] = ("layers", None, None, None, None)
    return axes


def apply_cached(cfg: MixtralConfig, params: Params, tokens: jnp.ndarray,
                 cache: Params, cache_len: jnp.ndarray, *,
                 compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Prefill/decode with KV cache; MoE routing runs per new token (aux loss
    is discarded at inference)."""
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (tokens.shape[0],))
    b, t = tokens.shape
    nh, hd = cfg.num_heads, cfg.head_size
    x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len, cfg.rope_theta)
    rope_idx = index_rope(cfg)
    sparse = cfg.sparse_attention is not None
    positions = cache_len[:, None] + jnp.arange(t)[None, :]
    moe_layer = _serving_moe(cfg)
    layers, bank = _bank_apart(jax.tree.map(
        lambda p: p.astype(compute_dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params["layers"]),
        moe_layer)
    if bank:    # a grouped call reads the stack at the layer's index
        layers["index"] = jnp.arange(cfg.num_layers, dtype=jnp.int32)

    def scan_body(x, scanned):
        layer, k_c, v_c, *i_c = scanned
        y = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(cfg, layer, y, cos, sin, positions)
        k_c = llama_mod._write_cache(k_c, k, cache_len)
        v_c = llama_mod._write_cache(v_c, v, cache_len)
        S = k_c.shape[1]
        kv_pos = jnp.arange(S)[None, None, None, :]
        q_abs = positions[:, None, :, None]
        mask = kv_pos <= q_abs
        if sparse:      # the index keys are cached like K: one head of them
            q_idx, k_idx, w_idx = index_vectors(cfg, layer, y, *rope_idx,
                                                 positions)
            i_c = [llama_mod._write_cache(i_c[0], k_idx[:, :, None],
                                          cache_len)]
            mask = _selected_mask(cfg, q_idx, i_c[0][:, :, 0], w_idx,
                                  mask[:, 0])[:, None]
        attn = attention(q, k_c, v_c, causal=False, mask=mask)
        x = x + attn.reshape(b, t, nh * hd) @ layer["wo"]
        y = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
        ffn_out, _aux = moe_layer({**layer["moe"], **bank}, y,
                                  layer=layer.get("index"))
        return x + ffn_out, (k_c, v_c, *i_c)

    names = ("k", "v") + (("kI",) if sparse else ())
    x, new = lax.scan(scan_body, x, (layers, *(cache[n] for n in names)))
    x = rms_norm(x, params["final_norm"].astype(compute_dtype), cfg.rms_norm_eps)
    logits = x @ params["lm_head"].astype(compute_dtype)
    return logits.astype(jnp.float32), dict(zip(names, new))


def loss_fn(cfg: MixtralConfig, params: Params, batch: Dict[str, jnp.ndarray], *,
            compute_dtype=jnp.bfloat16):
    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    logits, aux = apply(cfg, params, inputs, compute_dtype=compute_dtype)
    with jax.named_scope("loss"):
        logp = jax.nn.log_softmax(logits, axis=-1)
        lm_loss = -jnp.mean(
            jnp.take_along_axis(logp, labels[..., None], axis=-1))
        loss = lm_loss + cfg.aux_loss_coef * aux
    return loss, {"loss": loss, "lm_loss": lm_loss, "aux_loss": aux}


def tiled_loss_fn(cfg: MixtralConfig, params: Params,
                  batch: Dict[str, jnp.ndarray], *,
                  compute_dtype=jnp.bfloat16, shards: int = 8):
    """``loss_fn`` with the unembed matmul + CE fused per sequence tile —
    [B, S, V] logits are never materialized (``sequence.tiled_loss``).
    The MoE aux loss is added exactly as in ``loss_fn``."""
    from ..sequence.tiled import tiled_fused_logits_loss

    tokens = batch["tokens"]
    inputs, labels = tokens[:, :-1], tokens[:, 1:]
    hidden, head, aux = apply(cfg, params, inputs,
                              compute_dtype=compute_dtype,
                              return_hidden=True)
    with jax.named_scope("logits"):    # unembed matmul and CE, fused per tile
        lm_loss = tiled_fused_logits_loss(hidden, head, labels, shards=shards)
    loss = lm_loss + cfg.aux_loss_coef * aux
    return loss, {"loss": loss, "lm_loss": lm_loss, "aux_loss": aux}


def model_spec(cfg: MixtralConfig, compute_dtype=jnp.bfloat16):
    from ..runtime.engine import ModelSpec

    return ModelSpec(
        name="mixtral",
        init_fn=lambda rng: init(cfg, rng),
        loss_fn=lambda params, batch: loss_fn(cfg, params, batch,
                                              compute_dtype=compute_dtype),
        tiled_loss_fn=lambda params, batch, shards=8: tiled_loss_fn(
            cfg, params, batch, compute_dtype=compute_dtype, shards=shards),
        apply_fn=lambda params, tokens, **kw: apply(cfg, params, tokens,
                                                    compute_dtype=compute_dtype)[0],
        logical_axes=param_logical_axes(cfg),
        pipeline_capable=False,   # MoE model runs plain scan (no pipeline path yet)
    )


# --------------------------------------------------------------------------- #
# Paged (blocked) KV-cache path — the v2 continuous-batching protocol
# (reference serves Mixtral through inference/v2; block-table layout as in
# models/llama.py: fixed-width tables, block 0 is the trash block)
# --------------------------------------------------------------------------- #
def init_paged_cache(cfg: MixtralConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16,
                     kv_quant_group: Optional[int] = None) -> Params:
    cache = _init_paged_pools(cfg.num_layers, num_blocks, cfg.num_kv_heads,
                              block_size, cfg.head_size, dtype,
                              kv_quant_group)
    if cfg.sparse_attention is not None:
        if kv_quant_group is not None:
            from ..inference.ragged import IndexPoolError

            raise IndexPoolError(
                "inference.kv_quant", "the index keys' pool has no quantized "
                "mode, and the selection reads scores of bf16 keys")
        cache["kI"] = init_index_pool(
            cfg.num_layers, num_blocks, block_size,
            cfg.sparse_attention.index_head_dim, dtype)
    return cache


def sparse_rows(cfg: MixtralConfig, contexts) -> Dict[str, int]:
    """What ONE layer's selection of a serving call does, from lengths alone
    (the engine puts it on the call's span): ``contexts``, each row's own
    position + 1 - the cached tokens its indexer scores; of them attention
    reads ``min(context, topk)``. Empty without an indexer."""
    if cfg.sparse_attention is None:
        return {}
    contexts = np.asarray(contexts, np.int64)
    return {"sparse_rows": int(contexts.size),
            "sparse_ctx_scored": int(contexts.sum()),
            "sparse_kv_selected": int(np.minimum(
                contexts, cfg.sparse_attention.topk).sum())}


def _serving_moe(cfg: MixtralConfig) -> MoELayer:
    """The MoE layer of an inference forward. It never drops a token: a
    dropped decode token would silently corrupt the completion (reference
    v2 mixtral routes without capacity)."""
    return MoELayer(cfg.num_experts, cfg.top_k, cfg.capacity_factor,
                    cfg.min_capacity, drop_tokens=False,
                    norm_topk=cfg.norm_topk_prob, dispatch=cfg.moe_dispatch,
                    held=cfg.experts_held)


@functools.lru_cache(maxsize=None)
def _expected_tiles(rows: int, share: float, tile: int) -> float:
    """Row tiles ONE expert's rows take, ``ceil(count / tile)``, in
    expectation over ``count ~ Binomial(rows, share)``: a uniform router
    sends each of ``rows`` tokens to the expert with probability ``share``
    (1 where every expert is every token's: the count is ``rows``)."""
    if share >= 1.0:
        return float(-(-rows // tile))
    pmf, total = (1.0 - share) ** rows, 0.0
    for count in range(1, rows + 1):
        pmf *= (rows - count + 1) / count * share / (1.0 - share)
        total += pmf * -(-count // tile)
    return total


def moe_rows(cfg: MixtralConfig, rows: int) -> Dict[str, int]:
    """What ONE MoE layer of a serving call over ``rows`` token rows does,
    from shapes alone (the engine puts it on the call's span): the rows its
    router sends to experts, ``rows * top_k``; the rows its expert bank
    computes - the grouped form's row tiles in use, counted in the sub-tiles
    the kernel works them in (``ROW_SUBTILE`` rows, a whole tile where it is
    smaller): the routed rows and what pads each expert's rows to whole
    sub-tiles, in expectation under uniform routing; and ``moe_row_tile``,
    the tile itself, 0 where the capacity slabs ran (``MoELayer.grouped``):
    serving never drops a token, so a slab is at least ``rows`` long
    (``sharded_moe.top_k_gating_compact``) and every expert runs over one.
    With a held range: the rows a uniform router sends to the HELD experts,
    and the tiles (slabs) of the experts this bank holds."""
    held = cfg.num_experts if cfg.experts_held is None else cfg.experts_held[1]
    routed = rows * cfg.top_k * held // cfg.num_experts
    if not _serving_moe(cfg).grouped():
        capacity = max(compute_capacity(rows, cfg.num_experts, cfg.top_k,
                                        cfg.capacity_factor, cfg.min_capacity),
                       rows)
        return {"moe_rows_routed": routed,
                "moe_rows_computed": held * capacity, "moe_row_tile": 0}
    tile = row_tile(rows, cfg.num_experts, cfg.top_k, held,
                    cfg.intermediate_size)
    sub = min(tile, ROW_SUBTILE)
    passes = held * _expected_tiles(rows, cfg.top_k / cfg.num_experts, sub)
    return {"moe_rows_routed": routed,
            "moe_rows_computed": round(passes * sub), "moe_row_tile": tile}


def apply_paged(cfg: MixtralConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the paged cache (see llama.apply_paged for the
    contract, a mixed call and ``rows`` included: its ``slots + t`` rows go
    through the expert bank as one call's rows, the head scores ``rows``);
    the FFN is the no-drop MoE routing of apply_cached."""
    b, t = tokens.shape
    nh, hd = cfg.num_heads, cfg.head_size
    if valid is None:
        valid = jnp.ones((b, t), bool)
    with jax.named_scope("embed"):
        x = embedding_lookup(params["embed"], tokens, compute_dtype)
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len, cfg.rope_theta)
    rope_idx = index_rope(cfg)
    positions = row_positions(block_tables, context_lens, t)
    moe_layer = _serving_moe(cfg)
    layers, bank = _bank_apart(jax.tree.map(
        lambda p: p.astype(compute_dtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params["layers"]),
        moe_layer)

    def scan_body(x, scanned):
        layer, k_c, v_c, *i_c = scanned
        with jax.named_scope("norm"):
            y = rms_norm(x, layer["attn_norm"], cfg.rms_norm_eps)
        with jax.named_scope("attn"):   # the pool update inside is "kv_write"
            q, k, v = _qkv(cfg, layer, y, cos, sin, positions)
            if i_c:     # a learned selection: the index keys' pool is there
                with jax.named_scope("attn_index"):
                    index = index_vectors(cfg, layer, y, *rope_idx,
                                           positions)
                attn, k_c, v_c, *i_c = sparse_attention_step(
                    q, k, v, *index, k_c, v_c, i_c[0], block_tables,
                    context_lens, valid, topk=cfg.sparse_attention.topk)
            else:
                attn, k_c, v_c = paged_attention_step(
                    q, k, v, k_c, v_c, block_tables, context_lens, positions,
                    valid)
            x = x + attn.reshape(b, t, nh * hd) @ layer["wo"]
        with jax.named_scope("norm"):
            y = rms_norm(x, layer["mlp_norm"], cfg.rms_norm_eps)
        ffn_out, _aux = moe_layer({**layer["moe"], **bank}, y,
                                  layer=k_c.layer if bank else None)
        return x + ffn_out, (k_c, v_c, *i_c)

    x, cache = scan_layers(scan_body, x, layers, cache)
    x = gather_rows(x, rows)
    with jax.named_scope("norm"):
        x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                     cfg.rms_norm_eps)
    with jax.named_scope("logits"):
        logits = (x @ params["lm_head"].astype(compute_dtype)).astype(
            jnp.float32)
    return logits, cache
