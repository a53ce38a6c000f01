"""Brumby family (``manifestai/Brumby-14B-Base``, ``model_type`` "brumby"):
Qwen3's dense decoder with every attention layer replaced by POWER RETENTION
(``ops/retention.py``: a gated linear attention of degree 2 over a fixed
state). With ``u = RMSNorm(x)``, key-value head ``j`` and each query head
``i`` of its group:

    q = rope(rmsnorm_head(W_q^i u))   k = rope(rmsnorm_head(W_k^j u))
    v = W_v^j u                        log g = logsigmoid(w_g^j . u)  (float32)
    o_t = sum_{s<=t} a_ts v_s / (sum_{s<=t} a_ts + eps),
    a_ts = exp(sum_{r=s+1..t} log g_r) (q_t . k_s)^2
    x <- x + W_o concat_i(o^i);   x <- x + W_down(silu(W_gate n) * W_up n)

What is ``models/llama.py``'s is taken from there - the projections with
their per-head q/k norm (``_qkv_proj``), the rotary tables, the SwiGLU half
of a served block, the final norm and the head - and only the mixer is this
family's. There is no softmax attention anywhere and NO key-value cache: a
sequence lives in its state from its first token (the package's switch-over
form, which keeps keys and values until a context is long enough, computes
the same numbers and is not written).

Serving. The cache is ONE leaf with no block axis, ``ret [layers, slots + 1,
nkv * d + 8, R]`` float32 (``ops/retention.py``: one row a sequence slot a
layer, 35.9 MB at 8 heads of 128; the last row the trash row), carried
through the layer scan and written where it lies by the two kernels of
``ops/pallas/retention.py``: a call's single-token rows by the decode
update, rows of several tokens by the chunked form. The family declares no
leaf with a block axis, and the engine allocates none: a free slot is all
an admission needs, and a sequence runs to any length in the slot's
footprint (``docs/serving.md`` "A state and no cache"). Training through the
chunked form (a backward for its scan) is not written: ``loss_fn`` refuses
by name.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import retention
from ..ops.embedding import embedding_lookup
from ..ops.norms import rms_norm
from ..ops.pallas import retention as _kernels  # noqa: F401 (registers)
from ..ops.registry import get_op
from ..ops.rotary import apply_rotary, rope_frequencies
from ..utils.tree import cast_floating
from . import llama
from ._paged import MixedCall, gather_rows, row_positions, scan_nest
from .granite_hybrid import state_call

Params = Dict[str, Any]
F32 = jnp.float32
STATE_LEAVES = ("ret",)           # the cache leaves with no block axis
KIND = "retention"                # every layer's kind (``_paged.layer_plan``)


@dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_layers: int = 40
    num_heads: int = 40
    num_kv_heads: int = 8
    head_dim: int = 128
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-6
    # the retention's own sizes: the published config carries none of them
    # (benchmark/configs/brumby-14b-base.json ``assumed``)
    retention_degree: int = 2           # p: the release's
    retention_gate: str = "kv_head"     # one scalar a key-value head a token
    retention_eps: float = retention.EPS
    retention_tile: int = 128   # how the chunked form is blocked, not a result
    state_dtype: str = "float32"
    compute_dtype: str = "bfloat16"

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def layer_types(self) -> Tuple[str, ...]:
        return (KIND,) * self.num_layers

    @property
    def state_row_bytes(self) -> int:
        """Bytes of ONE slot's row of ONE layer, in ``state_dtype``."""
        _, _, sublanes, lanes = retention.state_shape(
            1, 0, self.num_kv_heads, self.head_dim)
        return sublanes * lanes * jnp.dtype(self.state_dtype).itemsize

    @classmethod
    def tiny(cls, **kw) -> "BrumbyConfig":
        """The published RATIOS at a toy width (a group of two query heads a
        key-value head, head size 16), for CPU tests."""
        base = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
                    num_layers=3, num_heads=4, num_kv_heads=2, head_dim=16,
                    max_seq_len=256, rope_theta=10000.0, retention_tile=8)
        base.update(kw)
        return cls(**base)


def _check(cfg: BrumbyConfig) -> None:
    if cfg.retention_degree != 2 or cfg.retention_gate != "kv_head":
        raise ValueError(
            "models/brumby.py runs the release's retention: degree 2 and "
            f"one gate a key-value head (got degree {cfg.retention_degree}, "
            f"gate {cfg.retention_gate!r})")
    if cfg.num_heads % cfg.num_kv_heads or cfg.head_dim % 8:
        raise ValueError("query heads in whole groups of a key-value head, "
                         "heads of whole sublane tiles")


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def _llama_cfg(cfg: BrumbyConfig) -> llama.LlamaConfig:
    """The Qwen3 skeleton this family's weights and projections are."""
    return llama.LlamaConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size, num_layers=cfg.num_layers,
        num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, max_seq_len=cfg.max_seq_len,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        qk_norm=True)


def init(cfg: BrumbyConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    """``llama.init``'s tree (untied, per-head q/k norms) and, a layer, the
    gate's bias-less ``[hidden, kv heads]`` projection ``wg``, a fan-in
    scaled normal."""
    _check(cfg)
    params = llama.init(_llama_cfg(cfg), rng, dtype)
    shape = (cfg.num_layers, cfg.hidden_size, cfg.num_kv_heads)
    params["layers"]["wg"] = (
        jax.random.normal(jax.random.fold_in(rng, 0x6A7E), shape, F32)
        * cfg.hidden_size ** -0.5).astype(dtype)
    return params


def param_logical_axes(cfg: BrumbyConfig) -> Params:
    axes = llama.param_logical_axes(_llama_cfg(cfg))
    axes["layers"]["wg"] = ("layers", "embed", "kv_heads")
    return axes


# --------------------------------------------------------------------------- #
# the block
# --------------------------------------------------------------------------- #
def _projections(cfg, y, w, cos, sin, positions, valid):
    """``(q, k, v, log g)`` of ``y [b, t, h]``: Qwen3's projections, norms
    and rotary embedding, and the gate in float32. A row's padding leaves
    with ``k = v = 0`` and ``log g = 0``: it neither decays nor feeds a
    state (``ops/retention.py``)."""
    with jax.named_scope("retention_proj"):
        q, k, v = llama._qkv_proj(cfg, y, w)
        q = apply_rotary(q, cos, sin, positions)
        k = apply_rotary(k, cos, sin, positions)
        log_g = jax.nn.log_sigmoid((y @ w["wg"]).astype(F32))
        real = valid[..., None]
        return (q, jnp.where(real[..., None], k, 0),
                jnp.where(real[..., None], v, 0), jnp.where(real, log_g, 0.0))


def _retention_rows(cfg, state, index, rows, fresh, q, k, v, log_g):
    """ONE segment's rows over the state pool: one token a row is the
    in-place decode update, more are the chunked form. ``(state, o [b, t,
    nh, d] float32)``."""
    if q.shape[1] == 1:
        with jax.named_scope("retention_state"):
            state, o = get_op("retention_decode_update")(
                state, index, rows, fresh, q[:, 0], k[:, 0], v[:, 0],
                log_g[:, 0], eps=cfg.retention_eps)
        return state, o[:, None]
    with jax.named_scope("retention_chunk"):
        return get_op("retention_chunk")(
            state, index, rows, fresh, q, k, v, log_g,
            eps=cfg.retention_eps, tile=cfg.retention_tile)


def _block(cfg, x, w, tables, retain):
    """One layer: the token mixer around ``retain(q, k, v, log g) -> o`` -
    the projections before it and ``W_o`` after -, then the SwiGLU half."""
    b, t, _ = x.shape
    with jax.named_scope("norm"):
        y = rms_norm(x, w["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn"):       # this layer's token mixer
        o = retain(*_projections(cfg, y, w, *tables))
        with jax.named_scope("retention_proj"):
            x = x + o.astype(y.dtype).reshape(b, t, -1) @ w["wo"]
    return llama.swiglu_block(cfg, x, w)


def _tables(cfg, positions, valid):
    cos, sin = rope_frequencies(cfg.head_size, cfg.max_seq_len,
                                cfg.rope_theta)
    return cos, sin, positions, valid


def _layers(cfg, params, compute_dtype):
    compute_dtype = jnp.dtype(compute_dtype or cfg.compute_dtype)
    return compute_dtype, {KIND: cast_floating(params["layers"],
                                               compute_dtype)}


def _embed(params, tokens, compute_dtype):
    with jax.named_scope("embed"):
        return embedding_lookup(params["embed"], tokens, compute_dtype)


# --------------------------------------------------------------------------- #
# entry points
# --------------------------------------------------------------------------- #
def apply(cfg: BrumbyConfig, params: Params, tokens: jnp.ndarray, *,
          compute_dtype=None, form: str = "chunked") -> jnp.ndarray:
    """Whole sequences with no state pool: ``tokens [b, s]`` -> logits ``[b,
    s, vocab]`` float32, every layer from an empty state. ``form``: the
    ``"chunked"`` form (tiles of ``retention_tile``) or the ``"quadratic"``
    one, the equations as they stand (``[b, heads, s, s]`` of memory)."""
    _check(cfg)
    b, s = tokens.shape
    compute_dtype, layers = _layers(cfg, params, compute_dtype)
    tables = _tables(cfg, jnp.arange(s)[None] + jnp.zeros((b, 1), jnp.int32),
                     jnp.ones((b, s), bool))
    lanes = retention.phi_rows(cfg.head_dim)

    def retain(q, k, v, log_g):
        if form == "quadratic":
            return retention.retention_quadratic(q, k, v, log_g,
                                                 cfg.retention_eps)
        return retention.retention_chunked(
            q, k, v, log_g,
            jnp.zeros((b, cfg.num_kv_heads, cfg.head_dim, lanes), F32),
            jnp.zeros((b, cfg.num_kv_heads, lanes), F32),
            cfg.retention_tile, cfg.retention_eps)[0]

    def block(x, w, _pools, _index):
        return _block(cfg, x, w, tables, retain), None

    x, _ = scan_nest(cfg.layer_types, layers,
                     _embed(params, tokens, compute_dtype), None,
                     {KIND: block})
    return llama._head(cfg, params, x, compute_dtype)


def loss_fn(cfg: BrumbyConfig, params: Params, batch, **kw):
    raise NotImplementedError(
        "brumby is a serving family: training through it (a backward for "
        "the chunked retention's scan over the state) is not written")


def state_slot_bytes(cfg: BrumbyConfig) -> int:
    """Bytes of recurrent state ONE sequence slot holds over every layer:
    its presence is how a family declares recurrent state to the engine,
    and here it is ALL a sequence holds."""
    return cfg.num_layers * cfg.state_row_bytes


def state_rows(cfg: BrumbyConfig, rows: int,
               chunk_rows: int) -> Dict[str, int]:
    """What a step's span says of ONE retention layer of its call:
    ``retention_rows``, the live single-token rows whose state the decode
    update advances, and ``retention_chunk_rows``, the tokens of the chunk
    that rides with them (``telemetry/schema.py``)."""
    return {"retention_rows": rows, "retention_chunk_rows": chunk_rows}


def init_paged_cache(cfg: BrumbyConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, slots: int = 1) -> Params:
    """The state pool, ``slots`` rows and the trash row - and nothing with a
    block axis: ``num_blocks`` and ``block_size`` size no leaf here."""
    _check(cfg)
    del num_blocks, block_size, dtype
    return {"ret": jnp.zeros(
        retention.state_shape(cfg.num_layers, slots, cfg.num_kv_heads,
                              cfg.head_dim), jnp.dtype(cfg.state_dtype))}


def apply_paged(cfg: BrumbyConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables, context_lens, *,
                valid: Optional[jnp.ndarray] = None,
                slots: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=None) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the state pool: ``granite_hybrid.apply_paged``'s
    contract (``slots``, a mixed call, ``rows``) with no block pool behind
    it - ``block_tables`` is read for what KIND of call this is (a
    ``_paged.MixedCall`` or not) and nothing is looked up in it. A row at
    context offset 0 starts from an empty state; a row with no valid token
    leaves its slot's state as it was. In a mixed call the projections see
    every row at once and only the retention splits: the chunk's rows first,
    then the decode rows, as the two programs ran."""
    _check(cfg)
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    compute_dtype, layers = _layers(cfg, params, compute_dtype)
    tables = _tables(cfg, row_positions(block_tables, context_lens, t), valid)
    state_rows_, fresh, call = state_call(cache["ret"], block_tables,
                                          context_lens, valid, slots)

    def block(x, w, pools, index):
        state = pools["ret"]

        def retain(q, k, v, log_g):
            nonlocal state
            if call is None:
                state, o = _retention_rows(cfg, state, index, state_rows_,
                                           fresh, q, k, v, log_g)
                return o
            parts = [call.split(a) for a in (q, k, v, log_g)]
            state, o_c = _retention_rows(cfg, state, index, state_rows_[1],
                                         fresh[1], *(p[1] for p in parts))
            state, o_d = _retention_rows(cfg, state, index, state_rows_[0],
                                         fresh[0], *(p[0] for p in parts))
            return MixedCall.join(o_d, o_c)

        x = _block(cfg, x, w, tables, retain)
        return x, {**pools, "ret": state}

    x, cache = scan_nest(cfg.layer_types, layers,
                         _embed(params, tokens, compute_dtype), dict(cache),
                         {KIND: block})
    return llama._head(cfg, params, gather_rows(x, rows), compute_dtype), \
        cache


def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    raise NotImplementedError(
        "brumby has no dense-cache path (engine v1); serve it through "
        "build_engine_v2 (the per-slot state pool)")


def apply_cached(cfg, params, tokens, cache, cache_len, **kw):
    init_cache(cfg, 0, 0)
