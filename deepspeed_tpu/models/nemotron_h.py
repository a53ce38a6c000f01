"""Nemotron-H family (HF ``nemotron_h``; Nemotron-3-Nano-30B-A3B): a stack
of THREE kinds of layer, each a mixer OR a feed-forward alone - one norm and
one residual a layer - in the order ``hybrid_override_pattern`` spells, one
character a layer:

    x = E[token]
    x = x + mixer_l(RMSNorm(x))          l = 0 .. L - 1
    logits = RMSNorm(x) W_head           (untied)

``M``: a Mamba-2 mixer (``models/granite_hybrid.py``'s, whose functions this
family imports) with ``mamba_groups`` groups of B and C - head ``h`` reads
group ``h // (heads / groups)``, the convolution runs over ``d_inner + 2 *
groups * N`` channels, the gate's norm over each group's part of the inner
width - and ``d_inner = heads * head size`` (NOT ``expand * hidden``).

``E``: a sparse feed-forward of TWO-matrix experts, ``down(relu(up(y)) **
2)`` (``moe/layer.py BANK``), under DeepSeek-V3's router WITH its
score-correction bias: ``s = sigmoid(float32(y) W_r)``, the ``top_k`` largest
of ``s + bias`` chosen (``n_group`` 1: no group limit), gates ``s[chosen] /
sum`` times ``route_scale``; and one shared expert of the same form, added
ungated. The router is a float32 matrix and stays one in a served engine
(``FLOAT32_PARAMS``).

``*``: grouped-query attention without bias and WITHOUT a rotary embedding
(the Mamba layers carry position), softmax of ``q k^T / sqrt(head size)``.

Layout: weights stacked BY KIND (``params["mamba"]`` ``[L_M, ...]``,
``params["moe"]`` ``[L_E, ...]``, ``params["attn"]`` ``[L_*, ...]``); the
stack runs as the scan nest the pattern spells (``_paged.scan_nest``: the
published 52 layers are 5 x ``MEMEM*E``, 3 x ``ME``, ``M``, ``*``, 4 x
``EM``, ``E``). ``experts_held``: one chip's share of an expert-parallel
deployment, as ``models/mixtral.py`` has it.

Serving: the cache is Granite-4.0-H's two kinds of leaf - the attention
layers' paged ``k`` / ``v`` pools and ``ssm [L_M, slots + 1, N + 8, heads *
P]``, one row a sequence slot a Mamba layer (``ops/ssm.py``) - and the
engine refuses over it what it refuses over Granite's (``RecurrentState
Error``). Training through this family and a mesh over it are not written:
``loss_fn`` and a tensor-parallel engine are refused by name.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.layer import MoELayer, init_moe_ffn, moe_ffn_logical_axes
from ..ops.attention import attention
from ..ops.embedding import embedding_lookup
from ..ops.norms import rms_norm
from ..utils.tree import cast_floating
from ._paged import (LayerPool, gather_rows, init_paged_pools,
                     paged_attention_step, row_positions, scan_nest)
from .granite_hybrid import state_rows  # noqa: F401 (``ssm_chunk_rows``)
from .granite_hybrid import (MambaSizes, _mamba_mixer, _mixer_paged, draw_dt,
                             init_mixer, mixer_logical_axes, state_call)
from . import mixtral
from .mixtral import _bank_apart

Params = Dict[str, Any]
F32 = jnp.float32
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}   # pattern -> params key
STATE_LEAVES = ("ssm",)           # the cache leaves with no block axis
# leaves a served engine keeps in float32 beside its narrower weights: the
# router is published as a float32 matrix applied to float32 rows, and its
# choice bias beside it
FLOAT32_PARAMS = ("router", "router_bias")
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclass(frozen=True)
class NemotronHConfig(MambaSizes):
    vocab_size: int = 131072
    hidden_size: int = 2688
    pattern: str = PUBLISHED_PATTERN     # hybrid_override_pattern
    num_heads: int = 32
    num_kv_heads: int = 2
    head_dim: int = 128
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 8
    mamba_conv: int = 4
    mamba_chunk: int = 128      # how the scan is blocked, not part of the result
    intermediate_size: int = 1856          # ONE routed expert's
    shared_intermediate_size: int = 3712   # the shared expert's
    num_experts: int = 128
    top_k: int = 6
    route_scale: float = 2.5
    norm_topk_prob: bool = True
    max_seq_len: int = 262144
    rms_norm_eps: float = 1e-5
    capacity_factor: float = 1.25
    min_capacity: int = 4
    drop_tokens: bool = False         # serving never drops
    moe_dispatch: str = "einsum"
    # one chip's share of an expert-parallel deployment: ``(first, count)``
    # of the ``num_experts`` the router chooses among (moe/layer.py)
    experts_held: Optional[Tuple[int, int]] = None
    state_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return tuple(self.pattern)

    @property
    def num_layers(self) -> int:
        return len(self.pattern)

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def expert_lanes(self) -> int:
        """Columns of an expert's ``up`` (rows of its ``down``) as the bank
        is LAID OUT: ``intermediate_size`` rounded up to whole 128-lane
        tiles (1856 -> 1920), the padding zeros - zero columns of ``up`` and
        zero rows of ``down`` add nothing. A stack whose minor dimension is
        no multiple of 128 does not get the row-major layout a Mosaic
        operand has, and every program copied the whole 1.8 GB of ``w_up``
        into it (compiled for a described v5e, PR 50), as Granite's
        ``in_proj`` once was. Widths under one tile (the tests') stay."""
        f = self.intermediate_size
        return f if f < 128 else -(-f // 128) * 128

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        """All three kinds in a pattern with no period, two B / C groups,
        the published RATIOS of the Mamba widths, for CPU tests."""
        base = dict(vocab_size=256, hidden_size=32, pattern="MEM*EMEME",
                    num_heads=4, num_kv_heads=2, head_dim=16, mamba_heads=8,
                    mamba_head_dim=8, mamba_state=16, mamba_groups=2,
                    mamba_chunk=16, intermediate_size=24,
                    shared_intermediate_size=48, num_experts=8, top_k=3,
                    max_seq_len=256)
        base.update(kw)
        return cls(**base)


def _check(cfg: NemotronHConfig) -> None:
    unknown = set(cfg.pattern) - set(KINDS)
    if unknown or not cfg.pattern:
        raise ValueError(f"the pattern names {sorted(unknown)}; this family "
                         f"has {sorted(KINDS)}")
    if cfg.mamba_heads % cfg.mamba_groups:
        raise ValueError(f"{cfg.mamba_groups} groups of B and C do not "
                         f"divide {cfg.mamba_heads} heads")


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def init(cfg: NemotronHConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Random weights: fan-in scaled normals; the Mamba mixers by
    ``granite_hybrid.init_mixer`` (Mamba-2's published draws); the router a
    float32 matrix whatever ``dtype`` and its choice bias zeros, as the
    release initialises it (a configuration that wants the bias to matter
    draws one: ``benchmark/families/nemotron_h.py``)."""
    _check(cfg)
    h, v = cfg.hidden_size, cfg.vocab_size
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    m, e, a = (cfg.count(kind) for kind in "ME*")
    keys = iter(jax.random.split(rng, 24))

    def normal(shape, fan_in, dtype=dtype):
        # a layer at a time: the float32 draw of a whole stack must not
        # stand beside the model
        one = lambda key: (jax.random.normal(key, shape[1:], F32)
                           * fan_in ** -0.5).astype(dtype)
        return lax.map(one, jax.random.split(next(keys), shape[0]))

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, F32, lo, hi)

    held = cfg.num_experts if cfg.experts_held is None \
        else cfg.experts_held[1]
    si = cfg.shared_intermediate_size

    def one_moe(key):
        bank = init_moe_ffn(key, held, h, cfg.intermediate_size, dtype,
                            routed=cfg.num_experts, gated=False)
        return pad_bank(cfg, bank["w_up"], bank["w_down"])

    return {     # (``normal`` draws a stack: one matrix is a stack of one)
        "embed": normal((1, v, h), h)[0],
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": normal((1, h, v), h)[0],
        "mamba": init_mixer(cfg, m, draw_dt(cfg, m, uniform), normal,
                            uniform, dtype),
        "moe": {
            "norm": jnp.ones((e, h), dtype),
            "router": normal((e, h, cfg.num_experts), h, F32),
            "router_bias": jnp.zeros((e, cfg.num_experts), F32),
            **lax.map(one_moe, jax.random.split(next(keys), e)),
            "shared_w_up": normal((e, h, si), h),
            "shared_w_down": normal((e, si, h), si)},
        "attn": {
            "norm": jnp.ones((a, h), dtype),
            "wq": normal((a, h, nh * hd), h),
            "wk": normal((a, h, nkv * hd), h),
            "wv": normal((a, h, nkv * hd), h),
            "wo": normal((a, nh * hd, h), nh * hd)},
    }


def pad_bank(cfg: NemotronHConfig, w_up, w_down) -> Params:
    """An expert bank ``[.., h, F]`` / ``[.., F, h]`` in the layout the
    program keeps it in: ``cfg.expert_lanes`` wide, zeros past ``F``."""
    more = cfg.expert_lanes - cfg.intermediate_size
    wide = [(0, 0)] * (w_up.ndim - 1)
    return {"w_up": jnp.pad(w_up, wide + [(0, more)]),
            "w_down": jnp.pad(w_down, wide[:-1] + [(0, more), (0, 0)])}


def moe_rows(cfg: NemotronHConfig, rows: int) -> Dict[str, int]:
    """``mixtral.moe_rows`` (the shape facts the engine puts on a call's
    span) at the width the bank is laid out in."""
    return mixtral.moe_rows(
        dataclasses.replace(cfg, intermediate_size=cfg.expert_lanes), rows)


def param_logical_axes(cfg: NemotronHConfig) -> Params:
    """Attention as ``llama``; the Mamba mixer's weights unsharded; the
    expert bank over ``expert`` (``moe_ffn_logical_axes``, less the gate
    matrix a two-matrix bank has not)."""
    moe = {k: ("layers",) + tuple(v)
           for k, v in moe_ffn_logical_axes().items() if k != "w_gate"}
    return {
        "embed": ("vocab", "embed"), "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
        "mamba": mixer_logical_axes(),
        "moe": {**moe, "norm": ("layers", "embed"),
                "router_bias": ("layers", None),
                "shared_w_up": ("layers", "embed", "mlp"),
                "shared_w_down": ("layers", "mlp", "embed")},
        "attn": {"norm": ("layers", "embed"),
                 "wq": ("layers", "embed", "heads"),
                 "wk": ("layers", "embed", "kv_heads"),
                 "wv": ("layers", "embed", "kv_heads"),
                 "wo": ("layers", "heads", "embed")},
    }


# --------------------------------------------------------------------------- #
# the blocks
# --------------------------------------------------------------------------- #
def _moe(cfg: NemotronHConfig) -> MoELayer:
    """The MoE layer of every forward here (serving: it never drops a
    token); the experts' form, the choice bias and the float32 router are
    the parameters' (``moe/layer.py``)."""
    return MoELayer(cfg.num_experts, cfg.top_k, cfg.capacity_factor,
                    cfg.min_capacity, cfg.drop_tokens,
                    norm_topk=cfg.norm_topk_prob, dispatch=cfg.moe_dispatch,
                    held=cfg.experts_held, score="sigmoid",
                    route_scale=cfg.route_scale)


def _normed(cfg, x, w):
    with jax.named_scope("norm"):
        return rms_norm(x, w["norm"], cfg.rms_norm_eps)


def _qkv(cfg, y, w):
    b, t, _ = y.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    return ((y @ w["wq"]).reshape(b, t, nh, hd),
            (y @ w["wk"]).reshape(b, t, nkv, hd),
            (y @ w["wv"]).reshape(b, t, nkv, hd))


def _experts_block(cfg, moe_layer, bank):
    """``blocks["E"]``: the stacked ``bank`` (empty where the calls build
    slabs: ``mixtral._bank_apart``) is read at the layer's index."""
    def block(x, w, pools, index):
        out, _aux = moe_layer({**w, **bank}, _normed(cfg, x, w),
                              layer=index if bank else None)
        return x + out, pools
    return block


def _compute_layers(cfg, params, compute_dtype, moe_layer):
    """``(compute type, layers by pattern character, bank)``: every floating
    leaf in the compute type but ``FLOAT32_PARAMS``, the expert banks apart
    where the MoE calls take the grouped form."""
    compute_dtype = jnp.dtype(compute_dtype or cfg.compute_dtype)
    layers, bank = _bank_apart(
        cast_floating({key: params[key] for key in KINDS.values()},
                      compute_dtype, keep=FLOAT32_PARAMS), moe_layer)
    return compute_dtype, {kind: layers[key]
                           for kind, key in KINDS.items()}, bank


def _embed(params, tokens, compute_dtype):
    with jax.named_scope("embed"):
        return embedding_lookup(params["embed"], tokens, compute_dtype)


def _logits(cfg, params, x, compute_dtype):
    with jax.named_scope("norm"):
        x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                     cfg.rms_norm_eps)
    with jax.named_scope("logits"):
        return (x @ params["lm_head"].astype(compute_dtype)).astype(F32)


# --------------------------------------------------------------------------- #
# entry points
# --------------------------------------------------------------------------- #
def apply(cfg: NemotronHConfig, params: Params, tokens: jnp.ndarray, *,
          compute_dtype=None) -> jnp.ndarray:
    """Whole sequences with no cache: ``tokens [b, s]`` -> logits ``[b, s,
    vocab]`` float32. Every Mamba layer starts from a zero state."""
    _check(cfg)
    b, s = tokens.shape
    moe_layer = _moe(cfg)
    compute_dtype, layers, bank = _compute_layers(cfg, params, compute_dtype,
                                                  moe_layer)
    everywhere = jnp.ones((b, s), bool)

    def mamba(x, w, _pools, _index):
        y = _normed(cfg, x, w)
        with jax.named_scope("attn"):
            out, _, _ = _mamba_mixer(
                cfg, y, w,
                jnp.zeros((b, cfg.mamba_conv - 1, cfg.conv_dim), x.dtype),
                jnp.zeros((b, cfg.mamba_heads, cfg.mamba_head_dim,
                           cfg.mamba_state), F32), everywhere)
        return x + out, None

    def attn(x, w, _pools, _index):
        y = _normed(cfg, x, w)
        with jax.named_scope("attn"):
            out = attention(*_qkv(cfg, y, w), causal=True)
            return x + out.reshape(b, s, -1) @ w["wo"], None

    x, _ = scan_nest(cfg.layer_types, layers,
                     _embed(params, tokens, compute_dtype), None,
                     {"M": mamba, "*": attn,
                      "E": _experts_block(cfg, moe_layer, bank)})
    return _logits(cfg, params, x, compute_dtype)


def loss_fn(cfg: NemotronHConfig, params: Params, batch, **kw):
    raise NotImplementedError(
        "nemotron_h is a serving family: training through it (a backward "
        "through the state pool's kernels, an aux loss over its layers) is "
        "not written")


def state_slot_bytes(cfg: NemotronHConfig) -> int:
    """Bytes of recurrent state ONE sequence slot holds over every Mamba
    layer (``granite_hybrid.state_slot_bytes``): its presence is how a
    family declares recurrent state to the engine."""
    return cfg.count("M") * cfg.state_row_bytes


def init_paged_cache(cfg: NemotronHConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, slots: int = 1) -> Params:
    """The attention layers' block pools and the Mamba layers' per-slot
    pool, ``slots`` rows and the trash row, as Granite-4.0-H's. No
    quantized-KV mode."""
    _check(cfg)
    return {
        **init_paged_pools(cfg.count("*"), num_blocks, cfg.num_kv_heads,
                           block_size, cfg.head_size, dtype),
        "ssm": jnp.zeros((cfg.count("M"), slots + 1, cfg.state_sublanes,
                          cfg.d_inner), jnp.dtype(cfg.state_dtype))}


def apply_paged(cfg: NemotronHConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                slots: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=None) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the two-kind cache: ``granite_hybrid.
    apply_paged``'s contract (``slots``, a mixed call, ``rows``); a mixed
    call's ``slots + t`` rows go through the expert bank as one call's."""
    _check(cfg)
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    moe_layer = _moe(cfg)
    compute_dtype, layers, bank = _compute_layers(cfg, params, compute_dtype,
                                                  moe_layer)
    positions = row_positions(block_tables, context_lens, t)
    state_rows, fresh, call = state_call(cache["ssm"], block_tables,
                                         context_lens, valid, slots)

    def mamba(x, w, pools, index):
        y = _normed(cfg, x, w)
        with jax.named_scope("attn"):       # this layer's token mixer
            out, state = _mixer_paged(cfg, y, w, pools["ssm"], index,
                                      state_rows, fresh, valid, call)
        return x + out, {**pools, "ssm": state}

    def attn(x, w, pools, index):
        y = _normed(cfg, x, w)
        with jax.named_scope("attn"):   # the pool update inside is "kv_write"
            out, k_c, v_c = paged_attention_step(
                *_qkv(cfg, y, w), LayerPool(pools["k"], None, index),
                LayerPool(pools["v"], None, index), block_tables,
                context_lens, positions, valid)
            x = x + out.reshape(b, t, -1) @ w["wo"]
        return x, {**pools, "k": k_c.pool, "v": v_c.pool}

    x, cache = scan_nest(cfg.layer_types, layers,
                         _embed(params, tokens, compute_dtype), dict(cache),
                         {"M": mamba, "*": attn,
                          "E": _experts_block(cfg, moe_layer, bank)})
    return _logits(cfg, params, gather_rows(x, rows), compute_dtype), cache


def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    raise NotImplementedError(
        "nemotron_h has no dense-cache path (engine v1); serve it through "
        "build_engine_v2 (the paged cache with per-slot state)")


def apply_cached(cfg, params, tokens, cache, cache_len, **kw):
    init_cache(cfg, 0, 0)

