"""Granite-4.0-H family (HF ``granitemoehybrid``, dense variant): a stack of
TWO kinds of layer - Mamba-2 state-space mixers with a full-attention layer
among every few - each followed by a fused gate-and-up SwiGLU
(``shared_mlp``; the sparse branch of the family is not here:
``num_local_experts`` must be 0).

    x = embedding_multiplier * E[token]
    x = x + residual_multiplier * mixer(RMSNorm(x))
    x = x + residual_multiplier * mlp(RMSNorm(x))            (every layer)
    logits = RMSNorm(x) E^T / logits_scaling                 (tied table)

Attention layers: GQA without bias and WITHOUT positional embedding
(``position_embedding_type`` "nope"), softmax of ``attention_multiplier * q
k^T``. Mamba-2 layers: ``in_proj -> [z | xBC | dt]``; ``xBC <- silu(conv1d(xBC)
+ b)`` (depthwise, causal, over the sequence's own previous ``K - 1`` rows);
``xBC -> x | B | C``; ``dt <- softplus(dt + dt_bias)``; per head ``H_t =
exp(dt_t A) H_(t-1) + dt_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``; ``y <-
RMSNorm(y * silu(z))`` (gate first, then norm) and ``out_proj``. The mixer's
functions take any number of B / C groups (``mamba_groups``: ``B``, ``C``
``[.., G, N]``, the gate's norm over each group's part of the inner width -
``models/nemotron_h.py`` runs them at 8); this family's release has one, and
with one the traced program is what it was before groups were written. (The
published ``in_proj`` is kept as two matrices: its ``[z | xBC]`` columns and
its ``dt`` columns, ``dt_proj``.)

Layout: weights are stacked BY KIND (``params["mamba"]`` ``[L_mamba, ...]``,
``params["attn"]`` ``[L_attn, ...]``) and the stack runs as the scan nest
``layer_types`` spells: an outer scan over the pattern's periods whose body
scans each run of one kind (Granite-4.0-H-Micro: 4 x [5 Mamba, 1 attention,
4 Mamba]), so one body a kind of run is compiled whatever the depth.

Serving. The cache has two kinds of leaf, all carried through the scan nest
and written where they lie: the attention layers' paged ``k``/``v`` pools
``[L_attn, blocks, nkv / pack, bs, pack * hd]`` (``_paged.
paged_attention_step``, as every family; lane-packed there: two heads of 64
share a 128-lane row), and one fixed-size row a SEQUENCE SLOT a Mamba layer
with no block axis - ``ssm [L_mamba, slots + 1, N + 8, heads * P]`` (float32: a KV entry
is rounded once, this state is rewritten every token and its rounding
compounds), the state on a row's first ``N`` sublanes and the convolution's
tail ``[K - 1, conv_dim]`` under it (``tail_part``; ``ops/ssm.py`` has the
layout, the trash row and why one pool). A
call row is addressed by its sequence's slot; a row whose context offset is
0 starts from zeros inside the program; a row that is not ``valid`` leaves
both states exactly as they were. Only the kernels of ``ops/pallas/ssm.py``
and ``ops/pallas/ssm_scan.py`` (a segment of many tokens) touch the pools.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import ssm
from ..ops.attention import attention
from ..ops.embedding import embedding_lookup
from ..ops.norms import rms_norm, rms_norm_xla
from ..ops.pallas import ssm as _ssm_kernels  # noqa: F401 (registers)
from ..ops.pallas import ssm_scan as _ssm_scan
from ..ops.registry import backend_of, get_op
from ._paged import layer_plan  # noqa: F401  (this family's plan, by name)
from ._paged import (LayerPool, gather_rows, init_paged_pools,
                     paged_attention_step, row_positions, scan_nest)
from ._state import state_call  # noqa: F401  (the state families import it)
from ._state import (next_tail, pack_tail, short_conv, tail_part,
                     unpack_tail)

Params = Dict[str, Any]
F32 = jnp.float32
KINDS = {"mamba": "mamba", "attention": "attn"}   # layer type -> params key
STATE_LEAVES = ("ssm",)           # the cache leaves with no block axis


class MambaSizes:
    """What the Mamba-2 mixer's functions below read off a configuration
    beside its ``mamba_*`` fields and ``rms_norm_eps`` (this family's and
    ``models/nemotron_h.py``'s share them)."""

    @property
    def d_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_groups * self.mamba_state

    @property
    def state_part(self) -> Tuple[int, int, int]:
        """The recurrent state's part of a slot's row: ``(first sublane,
        sublanes, lanes)`` = the first ``N`` sublanes, every lane."""
        return 0, self.mamba_state, self.d_inner

    @property
    def tail_part(self) -> Tuple[int, int, int]:
        """The convolution tail's part of a slot's row, under the state:
        ``[K - 1, conv_dim]`` flattened into whole sublanes of as few whole
        128-lane tiles as hold it (8 x 1664 for Granite's published 3 x
        4352, 8 x 2304 for Nemotron-3-Nano's 3 x 6144)."""
        return tail_part(self.mamba_state,
                         (self.mamba_conv - 1) * self.conv_dim, self.d_inner)

    @property
    def state_sublanes(self) -> int:
        """Sublanes of a slot's row: the state and the tail under it."""
        return self.mamba_state + self.tail_part[1]

    @property
    def state_row_bytes(self) -> int:
        """Bytes of ONE slot's row of ONE Mamba layer, in ``state_dtype``."""
        return self.state_sublanes * self.d_inner \
            * jnp.dtype(self.state_dtype).itemsize


@dataclass(frozen=True)
class GraniteHybridConfig(MambaSizes):
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192        # shared_intermediate_size
    layer_types: Tuple[str, ...] = (("mamba",) * 5 + ("attention",)
                                    + ("mamba",) * 4) * 4
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: Optional[int] = None
    max_seq_len: int = 131072
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_groups: int = 1       # groups of B and C (the release has one)
    mamba_conv: int = 4
    mamba_chunk: int = 256      # how the scan is blocked, not part of the result
    state_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    @property
    def head_size(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    @classmethod
    def tiny(cls, periods: int = 1, **kw) -> "GraniteHybridConfig":
        """A whole 10-layer period a ``periods`` at the published RATIOS of
        widths (inner = 2 x hidden = heads x head size, group 4, N = 2 x
        the Mamba head size), for CPU tests."""
        base = dict(vocab_size=256, hidden_size=32, intermediate_size=128,
                    layer_types=(("mamba",) * 5 + ("attention",)
                                 + ("mamba",) * 4) * periods,
                    num_heads=8, num_kv_heads=2, max_seq_len=256,
                    mamba_heads=8, mamba_head_dim=8, mamba_state=16,
                    mamba_chunk=16)
        base.update(kw)
        return cls(**base)


def _check(cfg: GraniteHybridConfig) -> None:
    unknown = set(cfg.layer_types) - set(KINDS)
    if unknown:
        raise ValueError(f"layer_types names {sorted(unknown)}; this family "
                         f"has {sorted(KINDS)}")


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def init(cfg: GraniteHybridConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Random weights: fan-in scaled normals; Mamba-2's published
    initialisation for ``A_log`` (log of uniform 1-16), ``dt_bias`` (inverse
    softplus of log-uniform 0.001-0.1) and ``D`` (ones); the convolution as
    ``torch.nn.Conv1d`` draws it (uniform within ``K ** -0.5``); the tied
    table at standard deviation ``logits_scaling / sqrt(hidden)``, so that
    logits (a unit-RMS vector times the table, over ``logits_scaling``) have
    about unit variance, as an untied fan-in head gives."""
    _check(cfg)
    h, i, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    keys = iter(jax.random.split(rng, 16))

    def normal(shape, fan_in):
        # a layer at a time: the float32 draw of a whole stack (4.8 GB for
        # the 36 fused gate-and-up matrices) must not stand beside the model
        one = lambda key: (jax.random.normal(key, shape[1:], F32)
                           * fan_in ** -0.5).astype(dtype)
        return lax.map(one, jax.random.split(next(keys), shape[0]))

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, F32, lo, hi)

    def mlp(n):
        return {"mlp_norm": jnp.ones((n, h), dtype),
                "w_in": normal((n, h, 2 * i), h),
                "w_out": normal((n, i, h), i)}

    m, a = cfg.count("mamba"), cfg.count("attention")
    dt = draw_dt(cfg, m, uniform)
    params: Params = {
        "embed": (normal((v, h), h) * cfg.logits_scaling).astype(dtype),
        "final_norm": jnp.ones((h,), dtype),
        "mamba": {**init_mixer(cfg, m, dt, normal, uniform, dtype),
                  **mlp(m)},
        "attn": {
            "norm": jnp.ones((a, h), dtype),
            "wq": normal((a, h, nh * hd), h),
            "wk": normal((a, h, nkv * hd), h),
            "wv": normal((a, h, nkv * hd), h),
            "wo": normal((a, nh * hd, h), nh * hd),
            **mlp(a)},
    }
    return params


def draw_dt(cfg, m: int, uniform):
    """Mamba-2's published time steps, log-uniform 0.001-0.1, ``[m,
    heads]``: what :func:`init_mixer` makes ``dt_bias`` of."""
    return jnp.exp(uniform((m, cfg.mamba_heads), math.log(1e-3),
                           math.log(1e-1)))


def init_mixer(cfg, m: int, dt, normal, uniform, dtype) -> Params:
    """``m`` stacked Mamba-2 mixers with their norm, by :func:`init`'s rule
    (``normal(shape, fan_in)`` and ``uniform(shape, lo, hi)`` are the
    caller's draws)."""
    h, H, K = cfg.hidden_size, cfg.mamba_heads, cfg.mamba_conv
    C, d_in = cfg.conv_dim, cfg.d_inner
    return {
        "norm": jnp.ones((m, h), dtype),
        # in_proj's columns [z | xBC] and, by themselves, [dt]: the whole
        # (Granite's 8512, Nemotron-3's 10304) is no multiple of 128 lanes,
        # the device's default layout of the stack is then not row-major,
        # and every program copied all 1.25 GB of it (compiled for a
        # described v5e)
        "in_proj": normal((m, h, d_in + C), h),
        "dt_proj": normal((m, h, H), h),
        "conv_w": uniform((m, K, C), -K ** -0.5, K ** -0.5).astype(dtype),
        "conv_b": uniform((m, C), -K ** -0.5, K ** -0.5).astype(dtype),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
        "A_log": jnp.log(uniform((m, H), 1.0, 16.0)).astype(dtype),
        "D": jnp.ones((m, H), dtype),
        "gate_norm": jnp.ones((m, d_in), dtype),
        "out_proj": normal((m, d_in, h), d_in)}


def mixer_logical_axes() -> Params:
    """The Mamba mixer's own weights unsharded (one chip serves the model
    whole; a tensor-parallel mixer splits its heads and is not written)."""
    flat = ("layers", None)
    return {"norm": ("layers", "embed"),
            "in_proj": ("layers", "embed", None),
            "dt_proj": ("layers", "embed", None),
            "conv_w": ("layers", None, None), "conv_b": flat,
            "dt_bias": flat, "A_log": flat, "D": flat,
            "gate_norm": flat, "out_proj": ("layers", None, "embed")}


def param_logical_axes(cfg: GraniteHybridConfig) -> Params:
    """Attention and feed-forward as ``llama``; the Mamba mixer's as
    :func:`mixer_logical_axes` has them."""
    mlp = {"mlp_norm": ("layers", "embed"), "w_in": ("layers", "embed", None),
           "w_out": ("layers", "mlp", "embed")}
    return {
        "embed": ("vocab", "embed"), "final_norm": ("embed",),
        "mamba": {**mixer_logical_axes(), **mlp},
        "attn": {"norm": ("layers", "embed"),
                 "wq": ("layers", "embed", "heads"),
                 "wk": ("layers", "embed", "kv_heads"),
                 "wv": ("layers", "embed", "kv_heads"),
                 "wo": ("layers", "heads", "embed"), **mlp},
    }


# --------------------------------------------------------------------------- #
# the blocks
# --------------------------------------------------------------------------- #
def _mlp(cfg, x, w):
    with jax.named_scope("norm"):
        y = rms_norm(x, w["mlp_norm"], cfg.rms_norm_eps)
    with jax.named_scope("ffn"):
        gate, up = jnp.split(y @ w["w_in"], 2, axis=-1)
        return x + cfg.residual_multiplier * (
            (jax.nn.silu(gate) * up) @ w["w_out"])


def _qkv(cfg, y, w):
    b, t, _ = y.shape
    nh, nkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_size
    return ((y @ w["wq"]).reshape(b, t, nh, hd),
            (y @ w["wk"]).reshape(b, t, nkv, hd),
            (y @ w["wv"]).reshape(b, t, nkv, hd))


def _mixer_in(cfg, y, w, token_valid):
    """``in_proj`` and what the recurrence takes of it: ``(z, xBC, dt, A)``
    with ``dt`` float32 after its softplus and 0 on padding."""
    with jax.named_scope("ssm_proj"):
        z, xbc = jnp.split(y @ w["in_proj"], [cfg.d_inner], axis=-1)
        dt = jax.nn.softplus((y @ w["dt_proj"]).astype(F32)
                             + w["dt_bias"].astype(F32))
        dt = jnp.where(token_valid[..., None], dt, 0.0)
        return z, xbc, dt, -jnp.exp(w["A_log"].astype(F32))


def _conv(cfg, xbc, tail, w):
    """The causal depthwise convolution of ``xbc [b, t, C]`` after the
    sequence's previous ``K - 1`` rows ``tail``: ``(silu(conv + b) -> x, B,
    C``, the rows it ran over ``[b, K - 1 + t, C])``."""
    out, ext = short_conv(xbc, tail, w["conv_w"], w["conv_b"])
    G, N = cfg.mamba_groups, cfg.mamba_state
    x, B, C = jnp.split(out, [cfg.d_inner, cfg.d_inner + G * N], axis=-1)
    if G > 1:       # [b, t, G, N]: head h reads group h // (heads / G)
        B, C = (a.reshape(a.shape[:2] + (G, N)) for a in (B, C))
    return x, B, C, ext


def _pack_tail(cfg, tail):
    return pack_tail(tail, cfg.tail_part)


def _unpack_tail(cfg, part, dtype):
    return unpack_tail(part, cfg.mamba_conv - 1, cfg.conv_dim, dtype)


def _mixer_out(cfg, y, x, z, w):
    """The skip ``D x``, the gate, the norm over each GROUP's part of the
    inner width - the whole of it with one group - (gate first, then norm)
    and ``out_proj``. ``y [b, t, heads * P]`` float32."""
    with jax.named_scope("ssm_proj"):
        y = y + jnp.repeat(w["D"].astype(F32), cfg.mamba_head_dim) \
            * x.astype(F32)
        y = (y * jax.nn.silu(z.astype(F32))).astype(z.dtype)
        if cfg.mamba_groups > 1:
            # (the XLA form by name: the kernel's weight is one row)
            by_group = y.shape[:-1] + (cfg.mamba_groups, -1)
            y = rms_norm_xla(y.reshape(by_group),
                             w["gate_norm"].reshape(cfg.mamba_groups, -1),
                             cfg.rms_norm_eps).reshape(y.shape)
        else:
            y = rms_norm(y, w["gate_norm"], cfg.rms_norm_eps)
        return y @ w["out_proj"]


def _mamba_mixer(cfg, y, w, tail, h0, token_valid):
    """The mixer over whole rows of tokens from the given state:
    ``(out [b, t, h], the convolution's rows, state after the last real
    token [b, H, P, N])``."""
    b, t, _ = y.shape
    z, xbc, dt, A = _mixer_in(cfg, y, w, token_valid)
    with jax.named_scope("ssm_conv"):
        x, B, C, ext = _conv(cfg, xbc, tail, w)
    with jax.named_scope("ssm_state"):
        mixed, h_t = ssm.ssd_chunked_scan(
            x.reshape(b, t, cfg.mamba_heads, cfg.mamba_head_dim), dt, A, B,
            C, h0, cfg.mamba_chunk)
    return _mixer_out(cfg, mixed.reshape(b, t, -1), x, z, w), ext, h_t


def _ssm_rows(cfg, w, state, index, rows, fresh, xbc, dt, A, n_valid):
    """The convolution and the recurrence of ONE segment's rows over the
    state pool, between ``in_proj`` and the gate: ``xbc [b, t, C]`` and ``dt
    [b, t, H]`` (``_mixer_in``'s) from each row's state at ``[index,
    rows[i]]`` (zeros where ``fresh[i]``), which is advanced over the row's
    ``n_valid[i]`` real tokens and written back there. One token a row is
    the in-place ``ssm_decode_update``; more are ``ssm_chunk_scan`` from
    the rows where they lie, then their write. Returns ``(state pool, y [b,
    t, heads * P] float32, x [b, t, heads * P])``."""
    b, t = xbc.shape[:2]
    read, write = get_op("state_rows_read"), get_op("state_rows_write")
    with jax.named_scope("ssm_conv"):
        tail = jnp.where(fresh[:, None, None], 0, _unpack_tail(
            cfg, read(state, index, rows, cfg.tail_part), xbc.dtype))
    if t == 1:
        with jax.named_scope("ssm_conv"):
            xs, B, C, ext = _conv(cfg, xbc, tail, w)
            state = write(state, index, rows,
                          _pack_tail(cfg, ext[:, 1:]), cfg.tail_part)
        with jax.named_scope("ssm_state"):
            per_lane = lambda a: jnp.repeat(a, cfg.mamba_head_dim, axis=-1)
            state, mixed = get_op("ssm_decode_update")(
                state, index, rows, fresh,
                per_lane(jnp.exp(dt[:, 0] * A)),
                per_lane(dt[:, 0]) * xs[:, 0].astype(F32),
                B[:, 0], C[:, 0])
        return state, mixed[:, None], xs
    with jax.named_scope("ssm_conv"):
        xs, B, C, ext = _conv(cfg, xbc, tail, w)
    with jax.named_scope("ssm_state"):
        mixed, new = get_op("ssm_chunk_scan")(
            state, index, rows, fresh,
            xs.reshape(b, t, cfg.mamba_heads, cfg.mamba_head_dim), dt, A, B,
            C, cfg.mamba_chunk)
    with jax.named_scope("ssm_conv"):
        # the last K - 1 rows of [tail | the row's real tokens]
        new_tail = next_tail(ext, n_valid, cfg.mamba_conv - 1)
        state = write(state, index, rows,
                      _pack_tail(cfg, new_tail), cfg.tail_part)
    with jax.named_scope("ssm_state"):
        # (the tail's write, then the state's: the order the compiler
        # leaves every pool in place for, ``ops/pallas/ssm_scan.py``)
        state = write(state, index, rows, new, cfg.state_part)
    return state, mixed, xs


def _mixer_paged(cfg, y, w, state, index, rows, fresh, valid, call=None):
    """One Mamba mixer over the state pool (``_ssm_rows``), from the
    layer's normed input: ``(out [b, t, h], state pool)``. ``rows`` already
    aims rows that must write nothing at the trash row. In a mixed call
    (``call``, a ``_paged.MixedCall``; ``y [1, slots + t, h]``) ``in_proj``,
    the gate and ``out_proj`` see every row at once; only the state's part
    splits into the two segments, the chunk's first as the two programs
    ran, and ``rows`` / ``fresh`` are (the decode rows', the chunk's)
    pairs."""
    z, xbc, dt, A = _mixer_in(cfg, y, w, valid)
    if call is None:
        state, mixed, xs = _ssm_rows(
            cfg, w, state, index, rows, fresh, xbc, dt, A,
            jnp.sum(valid, axis=1, dtype=jnp.int32))
    else:
        (xbc_d, xbc_c), (dt_d, dt_c) = call.split(xbc), call.split(dt)
        state, mixed_c, xs_c = _ssm_rows(
            cfg, w, state, index, rows[1], fresh[1], xbc_c, dt_c, A,
            call.chunk_valid[None])
        state, mixed_d, xs_d = _ssm_rows(
            cfg, w, state, index, rows[0], fresh[0], xbc_d, dt_d, A,
            None)
        mixed = call.join(mixed_d, mixed_c)
        xs = call.join(xs_d, xs_c)
    return _mixer_out(cfg, mixed, xs, z, w), state


def _mamba_paged(cfg, x, w, pools, index, rows, fresh, valid, call=None):
    """One Mamba layer over the state pools (``_mixer_paged``), then its
    MLP."""
    with jax.named_scope("norm"):
        y = rms_norm(x, w["norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn"):       # this layer's token mixer
        out, state = _mixer_paged(cfg, y, w, pools["ssm"], index, rows,
                                  fresh, valid, call)
        x = x + cfg.residual_multiplier * out
    return _mlp(cfg, x, w), {**pools, "ssm": state}


def _attention_paged(cfg, x, w, pools, index, tables, ctx, positions, valid):
    b, t, _ = x.shape
    with jax.named_scope("norm"):
        y = rms_norm(x, w["norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn"):   # the pool update inside is "kv_write"
        q, k, v = _qkv(cfg, y, w)
        out, k_c, v_c = paged_attention_step(
            q, k, v, LayerPool(pools["k"], None, index),
            LayerPool(pools["v"], None, index), tables, ctx, positions,
            valid, scale=cfg.attention_multiplier)
        x = x + cfg.residual_multiplier * (out.reshape(b, t, -1) @ w["wo"])
    return _mlp(cfg, x, w), {**pools, "k": k_c.pool, "v": v_c.pool}


def _scan_nest(cfg, x, layers, pools, blocks):
    """The stack as ``layer_types`` spells it (``_paged.scan_nest``), the
    weights stacked by kind under ``KINDS``' keys."""
    return scan_nest(cfg.layer_types,
                     {kind: layers[key] for kind, key in KINDS.items()},
                     x, pools, blocks)


def _compute_layers(cfg, params, compute_dtype):
    compute_dtype = jnp.dtype(compute_dtype or cfg.compute_dtype)
    cast = lambda p: p.astype(compute_dtype) \
        if jnp.issubdtype(p.dtype, jnp.floating) else p
    return compute_dtype, {k: jax.tree.map(cast, params[k])
                           for k in KINDS.values()}


def _embed(cfg, params, tokens, compute_dtype):
    with jax.named_scope("embed"):
        return embedding_lookup(params["embed"], tokens, compute_dtype) \
            * jnp.asarray(cfg.embedding_multiplier, compute_dtype)


def _logits(cfg, params, x, compute_dtype):
    with jax.named_scope("norm"):
        x = rms_norm(x, params["final_norm"].astype(compute_dtype),
                     cfg.rms_norm_eps)
    with jax.named_scope("logits"):      # the tied table
        return (x @ params["embed"].astype(compute_dtype).T) \
            .astype(F32) / cfg.logits_scaling


# --------------------------------------------------------------------------- #
# entry points
# --------------------------------------------------------------------------- #
def apply(cfg: GraniteHybridConfig, params: Params, tokens: jnp.ndarray, *,
          compute_dtype=None) -> jnp.ndarray:
    """Whole sequences with no cache: ``tokens [b, s]`` -> logits ``[b, s,
    vocab]`` float32. Every Mamba layer starts from a zero state."""
    _check(cfg)
    b, s = tokens.shape
    compute_dtype, layers = _compute_layers(cfg, params, compute_dtype)
    everywhere = jnp.ones((b, s), bool)

    def mamba(x, w, _pools, _index):
        with jax.named_scope("norm"):
            y = rms_norm(x, w["norm"], cfg.rms_norm_eps)
        with jax.named_scope("attn"):
            out, _, _ = _mamba_mixer(
                cfg, y, w,
                jnp.zeros((b, cfg.mamba_conv - 1, cfg.conv_dim), x.dtype),
                jnp.zeros((b, cfg.mamba_heads, cfg.mamba_head_dim,
                           cfg.mamba_state), F32), everywhere)
            x = x + cfg.residual_multiplier * out
        return _mlp(cfg, x, w), None

    def attn(x, w, _pools, _index):
        with jax.named_scope("norm"):
            y = rms_norm(x, w["norm"], cfg.rms_norm_eps)
        with jax.named_scope("attn"):
            q, k, v = _qkv(cfg, y, w)
            out = attention(q, k, v, causal=True,
                            scale=cfg.attention_multiplier)
            x = x + cfg.residual_multiplier * (out.reshape(b, s, -1) @ w["wo"])
        return _mlp(cfg, x, w), None

    x, _ = _scan_nest(cfg, _embed(cfg, params, tokens, compute_dtype), layers,
                      None, {"mamba": mamba, "attention": attn})
    return _logits(cfg, params, x, compute_dtype)


def state_slot_bytes(cfg: GraniteHybridConfig) -> int:
    """Bytes of recurrent state ONE sequence slot holds over every Mamba
    layer (the state and, under it, the convolution's tail, in
    ``state_dtype``): what an admission occupies beside its KV blocks. Its
    presence is how a family declares recurrent state to the engine."""
    return cfg.count("mamba") * cfg.state_row_bytes


def state_rows(cfg, rows: int, chunk_rows: int) -> Dict[str, int]:
    """What a step's span says of ONE Mamba layer of its call beside
    ``ssm_rows`` / ``ssm_tokens`` (``telemetry/schema.py``):
    ``ssm_chunk_rows``, the tokens of the chunk that rides with the decode
    rows where the Mosaic scan takes them (``ops/pallas/ssm_scan.py``) - 0
    where no chunk rides and where ``ssm_chunk_scan`` resolves to the XLA
    form (off a TPU, or at sizes the kernel does not tile)."""
    del rows
    mosaic = backend_of("ssm_chunk_scan") == "pallas" and _ssm_scan.takes(
        cfg.mamba_state, cfg.mamba_heads, cfg.mamba_head_dim,
        cfg.mamba_groups, cfg.state_dtype)
    return {"ssm_chunk_rows": chunk_rows if mosaic else 0}


def init_paged_cache(cfg: GraniteHybridConfig, num_blocks: int,
                     block_size: int, dtype=jnp.bfloat16,
                     slots: int = 1) -> Params:
    """The attention layers' block pools (``_paged.init_paged_pools``) and
    the Mamba layers' per-slot pool, ``slots`` rows and the trash row
    (the engine passes its ``max_tracked_sequences``). No quantized-KV mode."""
    _check(cfg)
    m = cfg.count("mamba")
    return {
        **init_paged_pools(cfg.count("attention"), num_blocks,
                           cfg.num_kv_heads, block_size, cfg.head_size,
                           dtype, lane_pack=True),
        "ssm": jnp.zeros((m, slots + 1, cfg.state_sublanes, cfg.d_inner),
                         jnp.dtype(cfg.state_dtype))}


def apply_paged(cfg: GraniteHybridConfig, params: Params,
                tokens: jnp.ndarray, cache: Params,
                block_tables: jnp.ndarray, context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                slots: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=None) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the two-kind cache (prefill rows, chunks or
    decode steps): ``llama.apply_paged``'s contract, and ``slots [b]``, each
    row's sequence slot (``arange(b)`` when not given: row i is slot i). A
    row at context offset 0 starts its recurrent state from zeros; a row
    with no valid token leaves its slot's state as it was. A mixed call
    (``block_tables`` a ``_paged.MixedCall``): decode row i is slot i and
    the chunk's rows are ``chunk_slot``'s. ``rows [b, r]``: the head scores
    those rows alone (``_paged.gather_rows``; the state advances over every
    valid row as it did)."""
    _check(cfg)
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    compute_dtype, layers = _compute_layers(cfg, params, compute_dtype)
    positions = row_positions(block_tables, context_lens, t)
    state_rows, fresh, call = state_call(cache["ssm"], block_tables,
                                         context_lens, valid, slots)

    def mamba(x, w, pools, index):
        return _mamba_paged(cfg, x, w, pools, index, state_rows, fresh,
                            valid, call)

    def attn(x, w, pools, index):
        return _attention_paged(cfg, x, w, pools, index, block_tables,
                                context_lens, positions, valid)

    x, cache = _scan_nest(cfg, _embed(cfg, params, tokens, compute_dtype),
                          layers, dict(cache),
                          {"mamba": mamba, "attention": attn})
    return _logits(cfg, params, gather_rows(x, rows), compute_dtype), cache


def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    raise NotImplementedError(
        "granite_hybrid has no dense-cache path (engine v1); serve it "
        "through build_engine_v2 (the paged cache with per-slot state)")


def apply_cached(cfg, params, tokens, cache, cache_len, **kw):
    init_cache(cfg, 0, 0)
