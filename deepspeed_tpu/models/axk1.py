"""A.X-K1 family (``model_type`` ``axk1``; DeepSeek-V3's block,
arXiv:2412.19437 section 2.1, latent attention from arXiv:2405.04434 section
2.1), written TPU-first. What sets it apart from the sibling sparse decoders
and why it is a module of its own:

- **Latent attention (MLA)**: queries through a normed 1536-wide bottleneck;
  keys and values of all heads from ONE normed 512-wide latent a token, and
  ONE roped 64-wide key shared by every head. What a serving cache keeps is
  that latent (after its norm) and that key (after its rope): 576 numbers a
  token a layer, one row of ONE pool (``_paged.init_latent_pool``) where 64
  heads' keys and values would be 20 480. Every cached forward attends in
  the ABSORBED form - ``q~_i = q_n,i W_uk,i^T``, scores against the cached
  row, the weighted latents times ``W_uv,i`` afterwards: multi-query
  attention at one KV head, keys the whole row and values its first 512
  numbers (``_paged.latent_attention_step``). :func:`apply` (no cache) runs
  the EXPANDED form, keys and values rebuilt a head - the same numbers in
  another order.
- **YaRN rope** over the 64 rope dims, adjacent pairs rotated, and the
  softmax scale ``(128 + 64) ** -0.5 * m ** 2`` (``ops/rotary.py``).
- **A leading dense layer** (``first_k_dense``): a SwiGLU of the dense
  width ahead of the scanned sparse stack - its own parameters, its own
  (unrolled) layers, the same latent pool.
- **A group-limited, scaled sigmoid router**: scores ``sigmoid(h W_r)``, the
  top-k among the experts of the ``topk_group`` best of ``n_group`` groups,
  gates the chosen scores over their sum, the routed sum times
  ``route_scale``, and one ungated shared expert beside it
  (``moe/sharded_moe.py`` ``groups``; ``moe/layer.py`` ``route_scale``).

Same TPU shape as the sibling models: stacked layers, logical axis names per
param for the sharding-rule engine. ``experts_held``: one chip's share of an
expert-parallel deployment, as ``models/mixtral.py`` has it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.layer import MoELayer, init_moe_ffn, moe_ffn_logical_axes
from ..ops.attention import attention_xla
from ..ops.embedding import embedding_lookup
from ..ops.norms import rms_norm
from ..ops.rotary import (apply_rotary_interleaved, yarn_frequencies,
                          yarn_mscale)
from ._paged import (LayerPool, gather_rows, init_latent_pool,
                     latent_attention_step, row_positions)
from .mixtral import _bank_apart
from .mixtral import moe_rows  # noqa: F401  (the same shape facts: the
#                                engine reads them off the family's module)

Params = Dict[str, Any]


@dataclass(frozen=True)
class AxK1Config:
    vocab_size: int = 163840
    hidden_size: int = 7168
    dense_intermediate_size: int = 18432   # the leading dense layers' FFN
    intermediate_size: int = 2048          # ONE expert's (routed or shared)
    num_layers: int = 61
    first_k_dense: int = 1                 # dense layers ahead of the sparse
    num_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    num_experts: int = 192
    top_k: int = 8
    num_shared_experts: int = 1
    n_group: int = 8
    topk_group: int = 4
    route_scale: float = 2.5
    norm_topk_prob: bool = True
    max_seq_len: int = 131072
    rope_theta: float = 10000.0
    rope_factor: float = 32.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    rms_norm_eps: float = 1e-6
    capacity_factor: float = 1.25
    min_capacity: int = 4
    drop_tokens: bool = True          # training; serving never drops
    moe_dispatch: str = "einsum"
    # one chip's share of an expert-parallel deployment: ``(first, count)``
    # of the ``num_experts`` the router chooses among (moe/layer.py)
    experts_held: Optional[Tuple[int, int]] = None

    @property
    def head_size(self) -> int:
        """A query's (and an expanded key's) width a head."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """What one token keeps a layer: its latent and its roped key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return self.head_size ** -0.5 * m * m

    @classmethod
    def tiny(cls, **kw) -> "AxK1Config":
        base = dict(vocab_size=256, hidden_size=64,
                    dense_intermediate_size=96, intermediate_size=32,
                    num_layers=3, first_k_dense=1, num_heads=4,
                    q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, num_experts=8,
                    top_k=2, n_group=4, topk_group=2, max_seq_len=128,
                    rope_factor=4.0, rope_original_max_len=32)
        base.update(kw)
        return cls(**base)


def _check(cfg: AxK1Config) -> None:
    if not 0 <= cfg.first_k_dense < cfg.num_layers:
        raise ValueError(f"{cfg.first_k_dense} dense layers ahead of a "
                         f"stack of {cfg.num_layers} leave no sparse layer")
    if cfg.num_shared_experts < 1:
        raise ValueError("this family's sparse layer has a shared expert")


def latent_kind(cfg: AxK1Config) -> Dict[str, int]:
    """The family's ONE kind of KV state, a latent pool: what the serving
    engine builds, counts tiles for and refuses features over
    (``inference.engine.ModelFamily.latent_kind``)."""
    return {"key_width": cfg.latent_width, "value_width": cfg.kv_lora_rank}


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def _normal(key, shape, fan_in, dtype):
    return (jax.random.normal(key, shape, jnp.float32)
            * fan_in ** -0.5).astype(dtype)


def _init_attn(cfg, L, key, dtype) -> Params:
    """``L`` stacked layers' attention and norms."""
    h, nh, r = cfg.hidden_size, cfg.num_heads, cfg.kv_lora_rank
    ks = jax.random.split(key, 5)
    return {
        "attn_norm": jnp.ones((L, h), dtype),
        "w_dq": _normal(ks[0], (L, h, cfg.q_lora_rank), h, dtype),
        "q_norm": jnp.ones((L, cfg.q_lora_rank), dtype),
        "w_uq": _normal(ks[1], (L, cfg.q_lora_rank, nh * cfg.head_size),
                        cfg.q_lora_rank, dtype),
        "w_dkv": _normal(ks[2], (L, h, cfg.latent_width), h, dtype),
        "kv_norm": jnp.ones((L, r), dtype),
        # a head's key and value up-projections side by side
        "w_ukv": _normal(ks[3], (L, r, nh * (cfg.qk_nope_head_dim
                                             + cfg.v_head_dim)), r, dtype),
        "wo": _normal(ks[4], (L, nh * cfg.v_head_dim, h),
                      nh * cfg.v_head_dim, dtype),
        "ffn_norm": jnp.ones((L, h), dtype),
    }


def init(cfg: AxK1Config, rng: jax.Array, dtype=jnp.float32) -> Params:
    _check(cfg)
    h, D, S = cfg.hidden_size, cfg.first_k_dense, \
        cfg.num_layers - cfg.first_k_dense
    si = cfg.num_shared_experts * cfg.intermediate_size
    keys = jax.random.split(rng, 8)

    def one_moe(k):
        held = cfg.num_experts if cfg.experts_held is None \
            else cfg.experts_held[1]
        p = init_moe_ffn(k, held, h, cfg.intermediate_size, dtype,
                         routed=cfg.num_experts)
        ks = jax.random.split(jax.random.fold_in(k, 7), 3)
        p["shared_w_gate"] = _normal(ks[0], (h, si), h, dtype)
        p["shared_w_up"] = _normal(ks[1], (h, si), h, dtype)
        p["shared_w_down"] = _normal(ks[2], (si, h), si, dtype)
        return p

    di = cfg.dense_intermediate_size
    dk = jax.random.split(keys[3], 3)
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, h), h, dtype),
        "dense_layers": {
            **_init_attn(cfg, D, keys[1], dtype),
            "w_gate": _normal(dk[0], (D, h, di), h, dtype),
            "w_up": _normal(dk[1], (D, h, di), h, dtype),
            "w_down": _normal(dk[2], (D, di, h), di, dtype),
        },
        "layers": {
            **_init_attn(cfg, S, keys[2], dtype),
            "moe": jax.vmap(one_moe)(jax.random.split(keys[4], S)),
        },
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": _normal(keys[5], (h, cfg.vocab_size), h, dtype),
    }


def param_logical_axes(cfg: AxK1Config) -> Params:
    attn = {
        "attn_norm": ("layers", "embed"),
        "w_dq": ("layers", "embed", None),
        "q_norm": ("layers", None),
        "w_uq": ("layers", None, "heads"),
        "w_dkv": ("layers", "embed", None),
        "kv_norm": ("layers", None),
        "w_ukv": ("layers", None, "heads"),
        "wo": ("layers", "heads", "embed"),
        "ffn_norm": ("layers", "embed"),
    }
    moe_axes = {k: ("layers",) + tuple(v)
                for k, v in moe_ffn_logical_axes().items()}
    moe_axes.update({"shared_w_gate": ("layers", "embed", "mlp"),
                     "shared_w_up": ("layers", "embed", "mlp"),
                     "shared_w_down": ("layers", "mlp", "embed")})
    return {
        "embed": ("vocab", "embed"),
        "dense_layers": {**attn,
                         "w_gate": ("layers", "embed", "mlp"),
                         "w_up": ("layers", "embed", "mlp"),
                         "w_down": ("layers", "mlp", "embed")},
        "layers": {**attn, "moe": moe_axes},
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# --------------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------------- #
def _moe(cfg: AxK1Config, drop_tokens: bool) -> MoELayer:
    return MoELayer(cfg.num_experts, cfg.top_k, cfg.capacity_factor,
                    cfg.min_capacity, drop_tokens,
                    norm_topk=cfg.norm_topk_prob, dispatch=cfg.moe_dispatch,
                    held=cfg.experts_held, score="sigmoid",
                    groups=(cfg.n_group, cfg.topk_group),
                    route_scale=cfg.route_scale)


def _rope(cfg):
    return yarn_frequencies(
        cfg.qk_rope_head_dim, cfg.max_seq_len, cfg.rope_theta,
        cfg.rope_factor, cfg.rope_original_max_len, cfg.rope_beta_fast,
        cfg.rope_beta_slow,
        table_scale=yarn_mscale(cfg.rope_factor, cfg.rope_mscale)
        / yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim))


def _latents(cfg, w, h, cos, sin, positions):
    """One layer's queries and what it caches, from its normed input:
    ``q_n [b, t, nh, nope]``, ``q_r [b, t, nh, rope]`` (roped) and the
    token's row ``[b, t, 1, latent_width]`` - the latent after its norm, the
    shared key after its rope."""
    b, t, _ = h.shape
    eps = cfg.rms_norm_eps
    c_q = rms_norm(h @ w["w_dq"], w["q_norm"], eps)
    q = (c_q @ w["w_uq"]).reshape(b, t, cfg.num_heads, cfg.head_size)
    q_n, q_r = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    ckr = h @ w["w_dkv"]
    c = rms_norm(ckr[..., :cfg.kv_lora_rank], w["kv_norm"], eps)
    k_r = ckr[..., None, cfg.kv_lora_rank:]              # [b, t, 1, rope]
    q_r = apply_rotary_interleaved(q_r, cos, sin, positions)
    k_r = apply_rotary_interleaved(k_r, cos, sin, positions)
    return q_n, q_r, jnp.concatenate([c[:, :, None], k_r], axis=-1)


def _w_ukv(cfg, w):
    """``(W_uk, W_uv)``, ``[rank, nh, nope]`` and ``[rank, nh, v]``."""
    up = w["w_ukv"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                            cfg.qk_nope_head_dim + cfg.v_head_dim)
    return up[..., :cfg.qk_nope_head_dim], up[..., cfg.qk_nope_head_dim:]


def _expanded(cfg, w, q_n, q_r, row):
    """The EXPANDED form over whole sequences, causal: every head's keys and
    values rebuilt from the rows' latents, the shared roped key beside each
    head's. ``row [b, s, 1, latent_width]``."""
    b, s = row.shape[:2]
    w_uk, w_uv = _w_ukv(cfg, w)
    c, k_r = row[:, :, 0, :cfg.kv_lora_rank], row[..., cfg.kv_lora_rank:]
    with jax.named_scope("mla_expand"):
        k_n = jnp.einsum("bsr,rhd->bshd", c, w_uk)
        v = jnp.einsum("bsr,rhd->bshd", c, w_uv)
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(
        k_r, (b, s, cfg.num_heads, cfg.qk_rope_head_dim))], axis=-1)
    # (the XLA form by name: keys 192 wide and values 128 are two widths,
    # and the flash kernels have one)
    return attention_xla(q, k, v, causal=True, scale=cfg.softmax_scale)


def _absorb_q(cfg, w, q_n, q_r):
    """Each head's query against a cached row: ``[q_n W_uk^T | q_r]``."""
    with jax.named_scope("mla_absorb"):
        q_c = jnp.einsum("bthd,rhd->bthr", q_n, _w_ukv(cfg, w)[0])
    return jnp.concatenate([q_c, q_r], axis=-1)


def _absorb_out(cfg, w, o):
    """The weighted latents ``[b, t, nh, rank]`` as each head's values."""
    with jax.named_scope("mla_absorb"):
        return jnp.einsum("bthr,rhd->bthd", o, _w_ukv(cfg, w)[1])


def _absorbed(cfg, w, q_n, q_r, rows, mask):
    """The ABSORBED form over dense cached rows ``[b, s, 1, latent_width]``
    (the v1 cache): multi-query attention at one KV head."""
    out = attention_xla(_absorb_q(cfg, w, q_n, q_r), rows,
                        rows[..., :cfg.kv_lora_rank], causal=False,
                        mask=mask, scale=cfg.softmax_scale)
    return _absorb_out(cfg, w, out)


def _attn_half(cfg, x, w, attend):
    """``x + attention`` of one layer; ``attend(h) -> (mix [b, t, nh, v],
    pool)`` with the layer's normed input. Returns the sum, its FFN norm
    and the pool."""
    b, t, _ = x.shape
    with jax.named_scope("norm"):
        h = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn"):   # the pool update inside is "kv_write"
        with jax.named_scope("attn_latent"):
            mix, pool = attend(h)
        x = x + mix.reshape(b, t, -1) @ w["wo"]
    with jax.named_scope("norm"):
        return x, rms_norm(x, w["ffn_norm"], cfg.rms_norm_eps), pool


def _dense_block(cfg, x, w, attend):
    x, h, pool = _attn_half(cfg, x, w, attend)
    with jax.named_scope("ffn"), jax.named_scope("dense_ffn"):
        y = (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
    return x + y, pool


def _sparse_block(cfg, x, w, bank, index, moe_layer, attend):
    x, h, pool = _attn_half(cfg, x, w, attend)
    m, aux = moe_layer({**w["moe"], **bank}, h,
                       layer=index if bank else None)
    return x + m, pool, aux


def _stack(cfg, params, x, pool, attend, moe_layer, compute_dtype):
    """The whole stack: the leading dense layers unrolled, then a scan over
    the sparse layers; ``pool`` (None without a cache: the training forward,
    whose expert banks stay in the scanned layers) is carried through both.
    ``attend(w, h, pool, layer) -> (mix, pool)``; ``layer`` indexes the pool
    (the dense layers hold its first entries). Returns ``(x, pool, aux)``."""
    def cast(tree):
        return jax.tree.map(lambda p: p.astype(compute_dtype)
                            if jnp.issubdtype(p.dtype, jnp.floating) else p,
                            tree)

    dense = cast(params["dense_layers"])
    for i in range(cfg.first_k_dense):
        w = jax.tree.map(lambda a: a[i], dense)
        x, pool = _dense_block(
            cfg, x, w, lambda h: attend(w, h, pool, jnp.int32(i)))
    layers = cast(params["layers"])
    layers, bank = (layers, {}) if pool is None \
        else _bank_apart(layers, moe_layer)

    def step(carry, scanned):
        x, pool, aux = carry
        w, index = scanned
        x, pool, more = _sparse_block(
            cfg, x, w, bank, index, moe_layer,
            lambda h: attend(w, h, pool, index + cfg.first_k_dense))
        return (x, pool, aux + more), None

    n = cfg.num_layers - cfg.first_k_dense
    with jax.named_scope("kv_write"):   # as _paged.scan_layers names its scan
        (x, pool, aux), _ = lax.scan(
            step, (x, pool, jnp.zeros((), jnp.float32)),
            (layers, jnp.arange(n, dtype=jnp.int32)))
    return x, pool, aux


def _embed(params, tokens, compute_dtype):
    with jax.named_scope("embed"):
        return embedding_lookup(params["embed"], tokens, compute_dtype)


def _head(cfg, params, x, compute_dtype):
    with jax.named_scope("norm"):
        x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                     cfg.rms_norm_eps)
    with jax.named_scope("logits"):
        return (x @ params["lm_head"].astype(compute_dtype)) \
            .astype(jnp.float32)


# --------------------------------------------------------------------------- #
# entry points
# --------------------------------------------------------------------------- #
def apply(cfg: AxK1Config, params: Params, tokens: jnp.ndarray, *,
          compute_dtype=jnp.bfloat16):
    """Whole sequences with no cache, attention in the EXPANDED form →
    (logits [b, s, vocab] fp32, total aux loss)."""
    _check(cfg)
    cos, sin = _rope(cfg)

    def attend(w, h, _pool, _layer):
        q_n, q_r, row = _latents(cfg, w, h, cos, sin, None)
        return _expanded(cfg, w, q_n, q_r, row), None

    x, _, aux = _stack(cfg, params, _embed(params, tokens, compute_dtype),
                       None, attend, _moe(cfg, cfg.drop_tokens),
                       compute_dtype)
    return _head(cfg, params, x, compute_dtype), aux


# ---- latent-cached decode (v1-engine path): one dense row a token ---- #
def init_cache(cfg: AxK1Config, batch_size: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    return {"latent": jnp.zeros((cfg.num_layers, batch_size, max_len, 1,
                                 cfg.latent_width), dtype)}


def cache_logical_axes(cfg: AxK1Config) -> Params:
    return {"latent": ("layers", None, None, None, None)}


def apply_cached(cfg: AxK1Config, params: Params, tokens: jnp.ndarray,
                 cache: Params, cache_len: jnp.ndarray, *,
                 compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """The absorbed form over the dense latent cache."""
    _check(cfg)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (tokens.shape[0],))
    t = tokens.shape[1]
    cos, sin = _rope(cfg)
    positions = cache_len[:, None] + jnp.arange(t)[None, :]
    mask = jnp.arange(cache["latent"].shape[2])[None, None, None, :] \
        <= positions[:, None, :, None]

    def attend(w, h, pool, layer):
        q_n, q_r, row = _latents(cfg, w, h, cos, sin, positions)
        rows = jax.vmap(lambda c, n, s: lax.dynamic_update_slice(
            c, n.astype(c.dtype), (s, 0, 0)))(
                lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False),
                row, cache_len)
        pool = lax.dynamic_update_index_in_dim(pool, rows, layer, 0)
        return _absorbed(cfg, w, q_n, q_r, rows.astype(h.dtype), mask), pool

    x, pool, _ = _stack(cfg, params, _embed(params, tokens, compute_dtype),
                        cache["latent"], attend, _moe(cfg, False),
                        compute_dtype)
    return _head(cfg, params, x, compute_dtype), {"latent": pool}


# --------------------------------------------------------------------------- #
# Paged (blocked) latent cache — the v2 continuous-batching protocol
# --------------------------------------------------------------------------- #
def init_paged_cache(cfg: AxK1Config, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16) -> Params:
    """``{"latent": [L, num_blocks, 1, block_size, row width]}``: ONE pool
    (``_paged.init_latent_pool``). No quantized-KV mode."""
    _check(cfg)
    return init_latent_pool(cfg.num_layers, num_blocks, block_size,
                            cfg.latent_width, dtype)


def apply_paged(cfg: AxK1Config, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the latent pool (prefill rows, chunks, decode
    steps or a mixed call): ``llama.apply_paged``'s contract (``rows``: the
    head scores those rows alone). Every row - a chunk's as a decode's -
    attends in the absorbed form through ``_paged.latent_attention_step``."""
    _check(cfg)
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    cos, sin = _rope(cfg)
    positions = row_positions(block_tables, context_lens, t)

    def attend(w, h, pool, layer):
        q_n, q_r, row = _latents(cfg, w, h, cos, sin, positions)
        out, entry = latent_attention_step(
            _absorb_q(cfg, w, q_n, q_r), row, LayerPool(pool, None, layer),
            block_tables, context_lens, valid,
            value_width=cfg.kv_lora_rank, scale=cfg.softmax_scale)
        return _absorb_out(cfg, w, out), entry.pool

    x, pool, _ = _stack(cfg, params, _embed(params, tokens, compute_dtype),
                        cache["latent"], attend, _moe(cfg, False),
                        compute_dtype)
    return _head(cfg, params, gather_rows(x, rows), compute_dtype), \
        {**cache, "latent": pool}
