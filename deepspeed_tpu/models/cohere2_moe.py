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

import itertools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.layer import MoELayer, init_moe_ffn, moe_ffn_logical_axes
from ..ops.attention import attention
from ..ops.embedding import embedding_lookup
from ..ops.norms import layer_norm
from ..ops.rotary import apply_rotary_interleaved, rope_frequencies
from ._paged import (LayerPool, gather_rows, init_kind_pools, kind_tables,
                     paged_attention_step, row_positions)
from .mixtral import _bank_apart
from .mixtral import moe_rows  # noqa: F401  (the same shape facts: the
#                                engine reads them off the family's module)

Params = Dict[str, Any]

# layer type -> the kind of KV state it keeps (the cache leaves' suffix)
KINDS = {"full_attention": "full", "sliding_attention": "window"}


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
        """``layer_types`` for every layer: the tuple as given where it
        names them all, one period of it repeated otherwise."""
        types = tuple(self.layer_types)
        if self.num_layers % len(types):
            raise ValueError(f"{self.num_layers} layers are no whole number "
                             f"of the {len(types)}-layer pattern")
        return types * (self.num_layers // len(types))

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


def _check(cfg: Cohere2MoeConfig) -> None:
    types = cfg.resolved_layer_types()
    unknown = set(types) - set(KINDS)
    if unknown:
        raise ValueError(f"layer_types names {sorted(unknown)}; this family "
                         f"has {sorted(KINDS)}")
    if "full_attention" not in types:
        raise ValueError("a stack of window layers alone has no full kind "
                         "of KV state: this family wants one full layer a "
                         "period at least")


def layer_plan(cfg: Cohere2MoeConfig):
    """``(periods, runs, layers of each type a period)``: the smallest
    period the pattern repeats with and that period's runs of one type as
    ``(type, first layer of the run inside the period, first layer of the
    type inside the period, count)``."""
    types = cfg.resolved_layer_types()
    n = len(types)
    period = next(p for p in range(1, n + 1) if n % p == 0 and all(
        types[i] == types[i % p] for i in range(n)))
    runs, seen, at = [], {t: 0 for t in KINDS}, 0
    for kind, group in itertools.groupby(types[:period]):
        count = len(list(group))
        runs.append((kind, at, seen[kind], count))
        seen[kind] += count
        at += count
    return n // period, period, runs, seen


def window_kinds(cfg: Cohere2MoeConfig) -> Dict[str, int]:
    """The kinds of KV state beside the full one, each with its window: what
    the serving engine sizes a pool and an allocator for
    (``inference.engine.ModelFamily.window_kinds``)."""
    return {"window": cfg.sliding_window} \
        if cfg.count("sliding_attention") else {}


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def init(cfg: Cohere2MoeConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    _check(cfg)
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


def _scan_nest(cfg, x, layers, pools, blocks):
    """The stack as ``layer_types`` spells it: an outer scan over the
    pattern's periods whose body scans each run of one type. A layer takes
    its weights by its index into the stacked ``layers`` (what a scan's
    per-step slice of its inputs is), so no period's slab is cut out on the
    way. ``blocks[type](x, weights, pools, layer index, index among its
    type) -> (x, pools, aux)``; ``pools`` (None without a cache) is the
    carry of every scan, beside ``x`` and the summed aux loss."""
    periods, period, runs, per_period = layer_plan(cfg)

    def run(kind, carry, p, at, first, count):
        def step(carry, i):
            index = p * period + at + i
            w = jax.tree.map(lambda a: lax.dynamic_index_in_dim(
                a, index, 0, keepdims=False), layers)
            x, pools, aux = carry
            x, pools, more = blocks[kind](
                x, w, pools, index, p * per_period[kind] + first + i)
            return (x, pools, aux + more), None

        return lax.scan(step, carry, jnp.arange(count, dtype=jnp.int32))[0]

    def one_period(carry, p):
        for kind, at, first, count in runs:
            carry = run(kind, carry, p, at, first, count)
        return carry, None

    with jax.named_scope("kv_write"):   # as _paged.scan_layers names its scan
        x, pools, aux = lax.scan(
            one_period, (x, pools, jnp.zeros((), jnp.float32)),
            jnp.arange(periods, dtype=jnp.int32))[0]
    return x, pools, aux


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
    _check(cfg)
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

    x, _, aux = _scan_nest(
        cfg, _embed(params, tokens, compute_dtype), layers, None,
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
    _check(cfg)
    if cache_len.ndim == 0:
        cache_len = jnp.broadcast_to(cache_len, (tokens.shape[0],))
    b, t = tokens.shape
    cos, sin = _rope(cfg)
    positions = cache_len[:, None] + jnp.arange(t)[None, :]
    moe_layer = _moe(cfg, False)
    layers, bank = _compute_layers(params, compute_dtype, moe_layer)
    kv_pos = jnp.arange(cache["k"].shape[2])[None, None, None, :]
    q_abs = positions[:, None, :, None]

    def write(pool, index, new):
        def one(c, n, s):
            return lax.dynamic_update_slice(c, n.astype(c.dtype), (s, 0, 0))

        layer = jax.vmap(one)(lax.dynamic_index_in_dim(
            pool, index, 0, keepdims=False), new, cache_len)
        return lax.dynamic_update_index_in_dim(pool, layer, index, 0), layer

    def block(layer_type, window):
        mask = kv_pos <= q_abs
        if window is not None:
            mask = mask & (q_abs - kv_pos < window)

        def attend(h, w, pools, index):
            q, k, v = _qkv(cfg, w, h, layer_type, cos, sin, positions)
            k_pool, k_c = write(pools["k"], index, k)
            v_pool, v_c = write(pools["v"], index, v)
            with jax.named_scope("attn_" + KINDS[layer_type]):
                mix = attention(q, k_c, v_c, causal=False, mask=mask)
            return mix, {"k": k_pool, "v": v_pool}

        return lambda x, w, pools, index, _i: _block(
            cfg, x, w, bank, index, moe_layer,
            lambda h: attend(h, w, pools, index))

    x, cache, _ = _scan_nest(
        cfg, _embed(params, tokens, compute_dtype), layers, dict(cache),
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
    _check(cfg)
    blocks = {"full": num_blocks,
              **{kind: (window_blocks or {}).get(kind, num_blocks)
                 for kind in window_kinds(cfg)}}
    layers = {kind: cfg.count(layer_type)
              for layer_type, kind in KINDS.items() if kind in blocks}
    return init_kind_pools(layers, blocks, cfg.num_kv_heads, block_size,
                           cfg.head_size, dtype)


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
    _check(cfg)
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    cos, sin = _rope(cfg)
    positions = row_positions(block_tables, context_lens, t)
    moe_layer = _moe(cfg, False)
    layers, bank = _compute_layers(params, compute_dtype, moe_layer)
    kinds = window_kinds(cfg)
    block_size = cache["k"].shape[-2]
    # (the engine's width of the full kind's table: ``engine_v2``)
    full_width = max(2, -(-cfg.max_seq_len // block_size))
    parts = kind_tables(block_tables, context_lens, full_width, block_size)
    tables = {"full": parts[0], **dict.fromkeys(kinds, parts[-1])}

    def block(layer_type):
        kind = KINDS[layer_type]
        suffix = "" if kind == "full" else "_" + kind
        names = ("k" + suffix, "v" + suffix)

        def attend(h, w, pools, i):
            q, k, v = _qkv(cfg, w, h, layer_type, cos, sin, positions)
            with jax.named_scope("attn_" + kind):
                mix, k_c, v_c = paged_attention_step(
                    q, k, v, *(LayerPool(pools[n], None, i) for n in names),
                    *tables[kind], positions, valid, window=kinds.get(kind))
            return mix, {**pools, names[0]: k_c.pool, names[1]: v_c.pool}

        return lambda x, w, pools, index, i: _block(
            cfg, x, w, bank, index, moe_layer,
            lambda h: attend(h, w, pools, i))

    x, cache, _ = _scan_nest(
        cfg, _embed(params, tokens, compute_dtype), layers, dict(cache),
        {layer_type: block(layer_type) for layer_type in KINDS})
    return _head(cfg, params, gather_rows(x, rows), compute_dtype), cache
