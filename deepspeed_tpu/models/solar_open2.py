"""Solar-Open2 family (HF ``solar_open2``; Solar-Open2-250B): a stack of TWO
kinds of token mixer - gated delta-rule layers (Kimi Delta Attention, "KDA")
with a grouped-query softmax layer among every few (``gqa_layers``) - each
followed by a sparse feed-forward with one shared expert. No rotary
embedding anywhere: the delta layers carry position. With ``u =
RMSNorm(x)``:

KDA layer (``delta_heads`` heads of ``delta_head_dim``, every head its own
key and value; ``ops/delta.py`` has the recurrence):

    q, k, v = silu(conv(W_q u)), silu(conv(W_k u)), silu(conv(W_v u))
    q_h <- q_h / |q_h| * dk^-1/2     k_h <- k_h / |k_h|           (float32)
    log a = -exp(A_log_h) softplus(W_f2 (W_f1 u) + dt_bias)   [H, dk] float32
    beta  = 2 sigmoid(W_b u)                                      [H]
    S_t = (I - beta k k^T) Diag(a) S_(t-1) + beta k v^T;   o_t = S_t^T q_t
    x <- x + W_o concat_h(RMSNorm_head(o) * sigmoid(W_g2 (W_g1 u)))

(the convolutions causal, depthwise, ``delta_conv`` taps, no bias:
``_state.short_conv``; the factor 2 on ``beta`` lets the transition's
eigenvalue along ``k`` reach -1).

GQA layer: ``o = softmax_causal(q k^T / sqrt(head size)) v`` over ``num_heads``
query and ``num_kv_heads`` key-value heads, then an elementwise output gate:
``x <- x + W_o (o * sigmoid(W_gate u))``.

Feed-forward, every layer: ``n = RMSNorm(x)``; scores ``sigmoid(W_r n)`` in
float32 over ``num_experts``, the ``top_k`` largest of ``score + bias`` chosen
(the bias enters the choice alone), gates the chosen scores over their sum,
times ``route_scale``; ``x <- x + sum_i g_i E_i(n) + E_shared(n)``, every
expert a SwiGLU of ``intermediate_size`` (``moe/layer.py``). ``experts_held``:
one chip's share of an expert-parallel deployment, as ``models/mixtral.py``
has it.

Layout: weights stacked BY KIND (``params["delta"]`` ``[L_delta, ...]``,
``params["attn"]`` ``[L_attn, ...]``, each with its layers' ``moe``); the
stack runs as the scan nest ``layer_types`` spells (``_paged.scan_nest``:
the published 48 layers are 12 x [1 GQA, 3 KDA]). A KDA layer's three
projections are one matrix ``w_qkv [h, q | k | v]`` and its three narrow ones
one, ``w_low [h, f1 | g1 | b | zeros]``, in whole 128-lane tiles.

Serving: the cache has two kinds of leaf - the GQA layers' paged ``k`` /
``v`` pools and ``delta [L_delta, slots + 1, dk + tail, heads * dv]``, one
row a sequence slot a KDA layer: the state (float32: it is rewritten every
token) over the convolutions' tail (``ops/delta.py``; ``_state.tail_part``)
- and the engine refuses over it what it refuses over Granite's and
Nemotron's (``RecurrentStateError``). Training through this family and a
mesh over it are not written: ``loss_fn`` and a tensor-parallel engine are
refused by name.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.layer import MoELayer, init_moe_ffn, moe_ffn_logical_axes
from ..ops.attention import attention
from ..ops.embedding import embedding_lookup
from ..ops.norms import rms_norm, rms_norm_xla
from ..ops import delta
from ..ops.pallas import delta as _kernels  # noqa: F401 (registers; and
#                                     ops/pallas/ssm.py's row-table kernels)
from ..ops.registry import get_op
from ..utils.tree import cast_floating
from ._paged import (LayerPool, MixedCall, gather_rows, init_paged_pools,
                     paged_attention_step, row_positions, scan_nest)
from ._state import (next_tail, pack_tail, short_conv, state_call, tail_part,
                     unpack_tail)
from .mixtral import _bank_apart
from .mixtral import moe_rows  # noqa: F401  (the same shape facts: the
#                               engine puts them on a call's span)

Params = Dict[str, Any]
F32 = jnp.float32
KINDS = {"delta": "delta", "attention": "attn"}   # layer type -> params key
STATE_LEAVES = ("delta",)         # the cache leaves with no block axis
# leaves a served engine keeps in float32 beside its narrower weights: the
# router scores float32 rows, and its choice bias beside it
FLOAT32_PARAMS = ("router", "router_bias")
L2_EPS = 1e-6       # under the root of a head's squared norm (the release's)


@dataclass(frozen=True)
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    num_layers: int = 48
    gqa_layers: Tuple[int, ...] = tuple(range(0, 48, 4))
    num_heads: int = 64
    num_kv_heads: int = 8
    head_dim: int = 128
    delta_heads: int = 64
    delta_head_dim: int = 128       # of a key AND of a value
    delta_conv: int = 4
    delta_rank: int = 128   # the decay's and the output gate's bottleneck
    intermediate_size: int = 1280          # ONE expert's, routed or shared
    num_shared_experts: int = 1
    num_experts: int = 320
    top_k: int = 8
    route_scale: float = 1.0
    norm_topk_prob: bool = True
    max_seq_len: int = 1048576
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
        return tuple("attention" if l in self.gqa_layers else "delta"
                     for l in range(self.num_layers))

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def d_inner(self) -> int:
        """A KDA layer's q, k or v, every head's side by side: the state
        pool's lanes."""
        return self.delta_heads * self.delta_head_dim

    @property
    def low_lanes(self) -> int:
        """Columns of ``w_low`` as laid out: ``[f1 | g1 | b]`` rounded up to
        whole 128-lane tiles, the padding zeros."""
        return -(-(2 * self.delta_rank + self.delta_heads) // 128) * 128

    @property
    def tail_part(self) -> Tuple[int, int, int]:
        """The three convolutions' tail ``[K - 1, 3 * d_inner]``: its part of
        a slot's row, under the state's ``dk`` sublanes."""
        return tail_part(self.delta_head_dim,
                         (self.delta_conv - 1) * 3 * self.d_inner,
                         self.d_inner)

    @property
    def state_sublanes(self) -> int:
        return self.delta_head_dim + self.tail_part[1]

    @property
    def state_row_bytes(self) -> int:
        """Bytes of ONE slot's row of ONE KDA layer, in ``state_dtype``."""
        return self.state_sublanes * self.d_inner \
            * jnp.dtype(self.state_dtype).itemsize

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    @classmethod
    def tiny(cls, periods: int = 2, **kw) -> "SolarOpen2Config":
        """Whole periods of [1 GQA, 3 KDA] at the published RATIOS (a KV
        group of two, the bottleneck the head size), for CPU tests."""
        base = dict(vocab_size=256, hidden_size=32, num_layers=4 * periods,
                    gqa_layers=tuple(range(0, 4 * periods, 4)), num_heads=4,
                    num_kv_heads=2, head_dim=16, delta_heads=4,
                    delta_head_dim=16, delta_rank=16,
                    intermediate_size=24, num_experts=8, top_k=3,
                    max_seq_len=256)
        base.update(kw)
        return cls(**base)


def _check(cfg: SolarOpen2Config) -> None:
    if not cfg.gqa_layers or set(cfg.gqa_layers) - set(range(cfg.num_layers)):
        raise ValueError(f"gqa_layers {cfg.gqa_layers} are not layers of a "
                         f"stack of {cfg.num_layers} (and one at least)")
    if cfg.count("delta") == 0:
        raise ValueError("this family's stack has delta-rule layers; a "
                         "stack of GQA layers alone is models/mixtral.py's")
    if cfg.num_heads % cfg.num_kv_heads or cfg.delta_head_dim % 8:
        raise ValueError("query heads in whole groups of a key-value head, "
                         "delta heads of whole sublane tiles")
    if cfg.num_shared_experts < 1:
        raise ValueError("this family's sparse layer has a shared expert")


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def init(cfg: SolarOpen2Config, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Random weights: fan-in scaled normals; ``A_log`` (log of uniform
    1-16) and ``dt_bias`` (inverse softplus of log-uniform 0.001-0.1) as the
    release's layer draws them (Mamba-2's); the convolutions as
    ``torch.nn.Conv1d`` draws them (uniform within ``K ** -0.5``); the
    router a float32 matrix whatever ``dtype`` and its choice bias zeros."""
    _check(cfg)
    h, v = cfg.hidden_size, cfg.vocab_size
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    H, d_in, r, K = cfg.delta_heads, cfg.d_inner, cfg.delta_rank, \
        cfg.delta_conv
    m, a = cfg.count("delta"), cfg.count("attention")
    keys = iter(jax.random.split(rng, 32))

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
    si = cfg.num_shared_experts * cfg.intermediate_size

    def moe(n):
        one = lambda key: init_moe_ffn(key, held, h, cfg.intermediate_size,
                                       dtype, routed=cfg.num_experts)
        bank = lax.map(one, jax.random.split(next(keys), n))
        return {"ffn_norm": jnp.ones((n, h), dtype),
                "moe": {**bank,
                        "router": normal((n, h, cfg.num_experts), h, F32),
                        "router_bias": jnp.zeros((n, cfg.num_experts), F32),
                        "shared_w_gate": normal((n, h, si), h),
                        "shared_w_up": normal((n, h, si), h),
                        "shared_w_down": normal((n, si, h), si)}}

    low = jnp.pad(normal((m, h, 2 * r + H), h),
                  ((0, 0), (0, 0), (0, cfg.low_lanes - 2 * r - H)))
    dt = jnp.exp(uniform((m, d_in), math.log(1e-3), math.log(1e-1)))
    return {     # (``normal`` draws a stack: one matrix is a stack of one)
        "embed": normal((1, v, h), h)[0],
        "final_norm": jnp.ones((h,), dtype),
        "lm_head": normal((1, h, v), h)[0],
        "delta": {
            "norm": jnp.ones((m, h), dtype),
            "w_qkv": normal((m, h, 3 * d_in), h),
            "conv_w": uniform((m, K, 3 * d_in), -K ** -0.5,
                              K ** -0.5).astype(dtype),
            "w_low": low,
            "w_f2": normal((m, r, d_in), r),
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(uniform((m, H), 1.0, 16.0)).astype(dtype),
            "w_g2": normal((m, r, d_in), r),
            "o_norm": jnp.ones((m, cfg.delta_head_dim), dtype),
            "wo": normal((m, d_in, h), d_in),
            **moe(m)},
        "attn": {
            "norm": jnp.ones((a, h), dtype),
            "wq": normal((a, h, nh * hd), h),
            "wk": normal((a, h, nkv * hd), h),
            "wv": normal((a, h, nkv * hd), h),
            "w_gate": normal((a, h, nh * hd), h),
            "wo": normal((a, nh * hd, h), nh * hd),
            **moe(a)},
    }


def param_logical_axes(cfg: SolarOpen2Config) -> Params:
    """Attention as ``llama``; the delta mixer's weights unsharded (one chip
    serves its layers' mixers whole); the expert bank over ``expert``."""
    flat = ("layers", None)
    across = ("layers", "embed", None)
    moe = {k: ("layers",) + tuple(v)
           for k, v in moe_ffn_logical_axes().items()}
    moe.update({"router_bias": flat,
                "shared_w_gate": ("layers", "embed", "mlp"),
                "shared_w_up": ("layers", "embed", "mlp"),
                "shared_w_down": ("layers", "mlp", "embed")})
    ffn = {"ffn_norm": ("layers", "embed"), "moe": moe}
    return {
        "embed": ("vocab", "embed"), "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
        "delta": {"norm": ("layers", "embed"), "w_qkv": across,
                  "conv_w": ("layers", None, None), "w_low": across,
                  "w_f2": ("layers", None, None), "dt_bias": flat,
                  "A_log": flat, "w_g2": ("layers", None, None),
                  "o_norm": flat, "wo": ("layers", None, "embed"), **ffn},
        "attn": {"norm": ("layers", "embed"),
                 "wq": ("layers", "embed", "heads"),
                 "wk": ("layers", "embed", "kv_heads"),
                 "wv": ("layers", "embed", "kv_heads"),
                 "w_gate": ("layers", "embed", "heads"),
                 "wo": ("layers", "heads", "embed"), **ffn},
    }


# --------------------------------------------------------------------------- #
# the blocks
# --------------------------------------------------------------------------- #
def _moe(cfg: SolarOpen2Config) -> MoELayer:
    """The MoE layer of every forward here (serving: it never drops a
    token); the choice bias, the float32 router and the shared expert are
    the parameters' (``moe/layer.py``)."""
    return MoELayer(cfg.num_experts, cfg.top_k, cfg.capacity_factor,
                    cfg.min_capacity, cfg.drop_tokens,
                    norm_topk=cfg.norm_topk_prob, dispatch=cfg.moe_dispatch,
                    held=cfg.experts_held, score="sigmoid",
                    route_scale=None if cfg.route_scale == 1.0
                    else cfg.route_scale)


def _normed(cfg, x, weight):
    with jax.named_scope("norm"):
        return rms_norm(x, weight, cfg.rms_norm_eps)


def _ffn(cfg, moe_layer, bank, x, w, index):
    """A layer's sparse half; the stacked ``bank`` of the layer's kind
    (empty where the calls build slabs: ``mixtral._bank_apart``) is read at
    the layer's index."""
    out, _aux = moe_layer({**w["moe"], **bank},
                          _normed(cfg, x, w["ffn_norm"]),
                          layer=index if bank else None)
    return x + out


def _delta_in(cfg, u, w, valid):
    """A KDA layer's projections of its normed input: ``(qkv [b, t, 3 *
    d_inner]`` before the convolution, ``log_a [b, t, H, dk]`` and ``beta
    [b, t, H]`` float32 - 0 on a row's padding, which then neither decays
    nor writes the state -, the output ``gate [b, t, d_inner])``."""
    H, dk, r = cfg.delta_heads, cfg.delta_head_dim, cfg.delta_rank
    with jax.named_scope("delta_proj"):
        qkv = u @ w["w_qkv"]
        f1, g1, b = jnp.split((u @ w["w_low"])[..., :2 * r + H],
                              [r, 2 * r], axis=-1)
        # (float32 out of the matmul: the decay is exp(A) times this, A up
        # to 16, and a rounded sum would move it by what a token forgets)
        step = jax.nn.softplus(
            jnp.matmul(f1, w["w_f2"], preferred_element_type=F32)
            + w["dt_bias"].astype(F32))
        log_a = -jnp.exp(w["A_log"].astype(F32))[:, None] \
            * step.reshape(step.shape[:2] + (H, dk))
        beta = 2.0 * jax.nn.sigmoid(b.astype(F32))
        real = valid[..., None]
        return (qkv, jnp.where(real[..., None], log_a, 0.0),
                jnp.where(real, beta, 0.0), jax.nn.sigmoid(g1 @ w["w_g2"]))


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _delta_qkv(cfg, qkv, tail, w):
    """The three convolutions and what the recurrence takes of them: ``(q,
    k [b, t, H, dk]`` float32, each head's of unit length and ``q`` times
    ``dk ** -0.5``; ``v [b, t, H, dv]``; the rows the convolution ran
    over)``."""
    b, t, _ = qkv.shape
    H, d = cfg.delta_heads, cfg.delta_head_dim
    out, ext = short_conv(qkv, tail, w["conv_w"])
    q, k, v = (a.reshape(b, t, H, d) for a in jnp.split(out, 3, axis=-1))
    return (_unit(q.astype(F32)) * d ** -0.5, _unit(k.astype(F32)), v, ext)


def _delta_out(cfg, o, gate, w):
    """Each head's output normed (a learned weight a channel, shared by the
    heads), gated, and ``W_o``. ``o [b, t, H, dv]`` float32."""
    b, t = o.shape[:2]
    with jax.named_scope("delta_proj"):
        # (the XLA form by name: rows of a head's width, not the model's)
        o = rms_norm_xla(o, w["o_norm"].astype(F32), cfg.rms_norm_eps)
        return (o.astype(gate.dtype).reshape(b, t, -1) * gate) @ w["wo"]


def _delta_rows(cfg, w, state, index, rows, fresh, qkv, log_a, beta,
                n_valid):
    """The convolutions and the recurrence of ONE segment's rows over the
    state pool: ``qkv [b, t, 3 * d_inner]``, ``log_a``, ``beta``
    (``_delta_in``'s) from each row's state at ``[index, rows[i]]`` (zeros
    where ``fresh[i]``), advanced over the row's ``n_valid[i]`` real tokens
    and written back there. One token a row is the in-place
    ``delta_decode_update``; more are ``delta_chunk``. Returns ``(state
    pool, o [b, t, H, dv] float32)``."""
    t, k = qkv.shape[1], cfg.delta_conv - 1
    read, write = get_op("state_rows_read"), get_op("state_rows_write")
    with jax.named_scope("delta_conv"):
        tail = jnp.where(fresh[:, None, None], 0, unpack_tail(
            read(state, index, rows, cfg.tail_part), k, 3 * cfg.d_inner,
            qkv.dtype))
        q, key, v, ext = _delta_qkv(cfg, qkv, tail, w)
        # the last K - 1 rows of [tail | the row's real tokens]
        new_tail = ext[:, 1:] if t == 1 else next_tail(ext, n_valid, k)
        state = write(state, index, rows, pack_tail(new_tail, cfg.tail_part),
                      cfg.tail_part)
    if t == 1:
        with jax.named_scope("delta_state"):
            state, o = get_op("delta_decode_update")(
                state, index, rows, fresh, q[:, 0], key[:, 0], v[:, 0],
                log_a[:, 0], beta[:, 0])
        return state, o[:, None]
    with jax.named_scope("delta_chunk"):
        return get_op("delta_chunk")(state, index, rows, fresh, q, key, v,
                                     log_a, beta)


def _qkv(cfg, u, w):
    b, t, _ = u.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    return ((u @ w["wq"]).reshape(b, t, nh, hd),
            (u @ w["wk"]).reshape(b, t, nkv, hd),
            (u @ w["wv"]).reshape(b, t, nkv, hd))


def _gated_out(out, u, w):
    """The GQA layer's output gate, elementwise, before ``W_o``."""
    with jax.named_scope("attn_gate"):
        gate = jax.nn.sigmoid(u @ w["w_gate"])
    return (out.reshape(gate.shape) * gate) @ w["wo"]


def _compute_layers(cfg, params, compute_dtype, moe_layer):
    """``(compute type, layers by kind, banks by kind)``: every floating
    leaf in the compute type but ``FLOAT32_PARAMS``, each kind's expert
    banks apart where the MoE calls take the grouped form."""
    compute_dtype = jnp.dtype(compute_dtype or cfg.compute_dtype)
    layers, banks = {}, {}
    for kind, key in KINDS.items():
        layers[kind], banks[kind] = _bank_apart(
            cast_floating(params[key], compute_dtype, keep=FLOAT32_PARAMS),
            moe_layer)
    return compute_dtype, layers, banks


def _embed(params, tokens, compute_dtype):
    with jax.named_scope("embed"):
        return embedding_lookup(params["embed"], tokens, compute_dtype)


def _logits(cfg, params, x, compute_dtype):
    x = _normed(cfg, x, params["final_norm"].astype(compute_dtype))
    with jax.named_scope("logits"):
        return (x @ params["lm_head"].astype(compute_dtype)).astype(F32)


# --------------------------------------------------------------------------- #
# entry points
# --------------------------------------------------------------------------- #
def apply(cfg: SolarOpen2Config, params: Params, tokens: jnp.ndarray, *,
          compute_dtype=None, form: str = "chunked") -> jnp.ndarray:
    """Whole sequences with no cache: ``tokens [b, s]`` -> logits ``[b, s,
    vocab]`` float32, every KDA layer from a zero state. ``form``: the
    ``"chunked"`` form (tiles of ``ops/delta.py TILE``) or the ``"recurrence"``, a
    token at a time."""
    _check(cfg)
    b, s = tokens.shape
    moe_layer = _moe(cfg)
    compute_dtype, layers, banks = _compute_layers(cfg, params, compute_dtype,
                                                   moe_layer)
    everywhere = jnp.ones((b, s), bool)
    S0 = jnp.zeros((b, cfg.delta_heads, cfg.delta_head_dim,
                    cfg.delta_head_dim), F32)

    def delta_layer(x, w, _pools, index):
        u = _normed(cfg, x, w["norm"])
        with jax.named_scope("attn"):
            qkv, log_a, beta, gate = _delta_in(cfg, u, w, everywhere)
            with jax.named_scope("delta_conv"):
                q, k, v, _ = _delta_qkv(
                    cfg, qkv, jnp.zeros((b, cfg.delta_conv - 1,
                                         3 * cfg.d_inner), qkv.dtype), w)
            with jax.named_scope("delta_chunk"):
                if form == "recurrence":
                    o, _ = delta.delta_recurrence(q, k, v, log_a, beta, S0)
                else:
                    o, _ = delta.delta_chunked(q, k, v, log_a, beta, S0)
            x = x + _delta_out(cfg, o, gate, w)
        return _ffn(cfg, moe_layer, banks["delta"], x, w, index), None

    def attn_layer(x, w, _pools, index):
        u = _normed(cfg, x, w["norm"])
        with jax.named_scope("attn"):
            x = x + _gated_out(attention(*_qkv(cfg, u, w), causal=True), u,
                               w)
        return _ffn(cfg, moe_layer, banks["attention"], x, w, index), None

    x, _ = scan_nest(cfg.layer_types, layers,
                     _embed(params, tokens, compute_dtype), None,
                     {"delta": delta_layer, "attention": attn_layer})
    return _logits(cfg, params, x, compute_dtype)


def loss_fn(cfg: SolarOpen2Config, params: Params, batch, **kw):
    raise NotImplementedError(
        "solar_open2 is a serving family: training through it (a backward "
        "through the delta rule's triangular systems and the state pool's "
        "kernels, an aux loss over its layers) is not written")


def state_slot_bytes(cfg: SolarOpen2Config) -> int:
    """Bytes of recurrent state ONE sequence slot holds over every KDA layer
    (the state and, under it, the convolutions' tail, in ``state_dtype``):
    what an admission occupies beside its KV blocks. Its presence is how a
    family declares recurrent state to the engine."""
    return cfg.count("delta") * cfg.state_row_bytes


def state_rows(cfg: SolarOpen2Config, rows: int,
               chunk_rows: int) -> Dict[str, int]:
    """What a step's span says of ONE KDA layer of its call: ``delta_rows``,
    the live single-token rows whose state the decode update advances, and
    ``delta_chunk_rows``, the tokens of the chunk that rides with them
    (``telemetry/schema.py``)."""
    return {"delta_rows": rows, "delta_chunk_rows": chunk_rows}


def init_paged_cache(cfg: SolarOpen2Config, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, slots: int = 1) -> Params:
    """The GQA layers' block pools and the KDA layers' per-slot pool,
    ``slots`` rows and the trash row (the engine passes its
    ``max_tracked_sequences``). No quantized-KV mode."""
    _check(cfg)
    return {
        **init_paged_pools(cfg.count("attention"), num_blocks,
                           cfg.num_kv_heads, block_size, cfg.head_size,
                           dtype),
        "delta": jnp.zeros((cfg.count("delta"), slots + 1,
                            cfg.state_sublanes, cfg.d_inner),
                           jnp.dtype(cfg.state_dtype))}


def apply_paged(cfg: SolarOpen2Config, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                slots: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=None) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the two-kind cache: ``granite_hybrid.
    apply_paged``'s contract (``slots``, a mixed call, ``rows``). In a mixed
    call a KDA layer's projections, gate and ``W_o`` see every row at once
    and only the convolutions and the state split into the two segments, the
    chunk's first as the two programs ran; its ``slots + t`` rows go through
    the expert bank as one call's."""
    _check(cfg)
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    moe_layer = _moe(cfg)
    compute_dtype, layers, banks = _compute_layers(cfg, params, compute_dtype,
                                                   moe_layer)
    positions = row_positions(block_tables, context_lens, t)
    state_rows_, fresh, call = state_call(cache["delta"], block_tables,
                                          context_lens, valid, slots)

    def delta_layer(x, w, pools, index):
        u = _normed(cfg, x, w["norm"])
        state = pools["delta"]
        with jax.named_scope("attn"):       # this layer's token mixer
            qkv, log_a, beta, gate = _delta_in(cfg, u, w, valid)
            if call is None:
                state, o = _delta_rows(
                    cfg, w, state, index, state_rows_, fresh, qkv, log_a,
                    beta, jnp.sum(valid, axis=1, dtype=jnp.int32))
            else:
                parts = [call.split(a) for a in (qkv, log_a, beta)]
                state, o_c = _delta_rows(
                    cfg, w, state, index, state_rows_[1], fresh[1],
                    *(p[1] for p in parts), call.chunk_valid[None])
                state, o_d = _delta_rows(
                    cfg, w, state, index, state_rows_[0], fresh[0],
                    *(p[0] for p in parts), None)
                o = MixedCall.join(o_d, o_c)
            x = x + _delta_out(cfg, o, gate, w)
        x = _ffn(cfg, moe_layer, banks["delta"], x, w, index)
        return x, {**pools, "delta": state}

    def attn_layer(x, w, pools, index):
        u = _normed(cfg, x, w["norm"])
        with jax.named_scope("attn"):   # the pool update inside is "kv_write"
            out, k_c, v_c = paged_attention_step(
                *_qkv(cfg, u, w), LayerPool(pools["k"], None, index),
                LayerPool(pools["v"], None, index), block_tables,
                context_lens, positions, valid)
            x = x + _gated_out(out, u, w)
        x = _ffn(cfg, moe_layer, banks["attention"], x, w, index)
        return x, {**pools, "k": k_c.pool, "v": v_c.pool}

    x, cache = scan_nest(cfg.layer_types, layers,
                         _embed(params, tokens, compute_dtype), dict(cache),
                         {"delta": delta_layer, "attention": attn_layer})
    return _logits(cfg, params, gather_rows(x, rows), compute_dtype), cache


def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    raise NotImplementedError(
        "solar_open2 has no dense-cache path (engine v1); serve it through "
        "build_engine_v2 (the paged cache with per-slot state)")


def apply_cached(cfg, params, tokens, cache, cache_len, **kw):
    init_cache(cfg, 0, 0)
