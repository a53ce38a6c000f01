"""ZAYA family (``model_type`` ``zaya``: ZAYA1-8B), written TPU-first from the
catalog's configuration and the two public descriptions the family rests on
(Compressed Convolutional Attention, arXiv:2510.04476; the ZAYA1 report,
arXiv:2511.17127). Every layer is TWO sublayers - attention in a convolved,
compressed latent (CCA), then a top-1 mixture of experts behind an MLP
router - on a residual path with a learned scale and bias on both branches.
With ``n = RMSNorm`` (a weight, ``rms_norm_eps``), ``H`` query and ``G``
key-value heads of ``d = head_dim`` and ``g(h) = h // (H / G)``:

Residual path, after EVERY sublayer (``s``, ``b`` learned ``[hidden]``,
ones and zeros at init; the first sublayer's stream is the embedding):

    u = n(r);  y = sublayer(u);  r <- (r + b_res) s_res + (y + b_out) s_out

and ``logits = n_final(r) E^T`` with ``E`` the embedding table (tied).

CCA sublayer (``cca_mix`` is steps 2-8 and the tail's read and write):

    1. p_t = [Wq u_t | Wk u_t]        (H d + G d numbers: the latent)
    2. c0_t = a0 p_(t-1) + a1 p_t + beta0         depthwise, causal, K = 2
    3. c1_t[j] = A0[j] c0_(t-1)[j] + A1[j] c0_t[j] + beta1[j]
       grouped by head: H + G blocks of d channels, K = 2 (each
       convolution's input is ZERO before the sequence: c0_(-1) = 0)
    4. mq_t[h] = (qp_t[h] + kp_t[g(h)]) / 2
       mk_t[g] = (mean_{h in g} qp_t[h] + kp_t[g]) / 2    (the q-k mean)
    5. q_t[h] = c1_t[h] + mq_t[h];  k_t[g] = c1_t[H + g] + mk_t[g]
    6. q <- sqrt(d) q / |q|;  k <- tau_g sqrt(d) k / |k|        (float32)
    7. v_t = [Wv1 u_t | Wv2 u_(t-1)]: KV head 0 this token's value, KV
       head 1 the token before's (the value shift; two KV heads)
    8. rope on the first ``partial_rotary_factor`` of each head of q and k
    9. causal GQA attention, scale 1 / sqrt(d);  y_t = Wo o_t

So a row reads ``p_(t-2), p_(t-1)`` and ``Wv2 u_(t-1)`` of its own sequence:
the TAIL, ``2 (H + G) d + d`` numbers a sequence a layer.

MoE sublayer, the router's state ``z`` carried from the layer before:

    z_l = Wd u + bd + gamma_l z_(l-1)          (``router_hidden_size``)
    logits = W3 gelu(W2 gelu(W1 n(z_l) + b1) + b2)     E + 1 outputs
    P = softmax(logits);  e = argmax(P + beta);  w = P[e]  (not normalised)
    e < E: y = w Wdown_e (silu(Wgate_e u) * Wup_e u);  e = E (skip): y = 0

the whole router in float32 (``FLOAT32_PARAMS``). The skip is
``MoELayer(E + 1, top_k=1, held=(0, E))``: a row whose top-1 lies outside
the held range is not dispatched - it has no place in the grouped form and
no column in the slabs - and adds nothing (``moe/layer.py``).

Serving: a layer owns paged K/V AND a row of a per-slot pool,
``cache["tail"] [L, slots + 1, sublanes, lanes]`` (``_state.pack_tail``:
``[p_(t-2) | p_(t-1) | Wv2 u_(t-1)]`` in whole lane tiles, in the compute
type; the last row the trash row). The stream, the router's state and the
tail pool ride ``_paged.scan_layers``' carry beside the K and V pools; a
fresh row (``context == 0``) starts from zeros, and in a mixed call the
decode rows and the chunk read and write their own slots' rows
(``_state.state_call``). The engine refuses over the tail what it refuses
over any recurrent state (``RecurrentStateError``: a cached prefix's blocks
do not hold the tail at their end).

SERVING ONLY: no ``loss_fn`` (training through CCA and a top-1 grouped bank
is ROADMAP.md B-I's, by mechanism) and no dense-cache path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.layer import MoELayer, init_moe_ffn, moe_ffn_logical_axes
from ..moe.sharded_moe import compute_capacity, row_tile
from ..ops.attention import attention
from ..ops.embedding import embedding_lookup
from ..ops.norms import rms_norm, rms_norm_xla
from ..ops.pallas import ssm as _kernels  # noqa: F401 (registers the
#                                          state pool's row-table kernels)
from ..ops.registry import get_op
from ..ops.rotary import apply_rotary_partial, rope_frequencies
from ..utils.tree import cast_floating
from ._paged import (MixedCall, gather_rows, init_paged_pools,
                     paged_attention_step, row_positions, scan_layers)
from ._state import (next_tail, pack_tail, short_conv, state_call, tail_part,
                     unpack_tail)
from .mixtral import ROW_SUBTILE, _bank_apart, _expected_tiles

Params = Dict[str, Any]
F32 = jnp.float32
STATE_LEAVES = ("tail",)            # the cache leaf with no block axis
# leaves a served engine keeps in float32 beside its narrower weights: the
# whole router (``moe/layer.py`` scores float32 rows) and its choice bias
FLOAT32_PARAMS = ("router_down", "router_down_bias", "router_carry",
                  "router_norm", "router_w1", "router_b1", "router_w2",
                  "router_b2", "router_out", "router_bias")
L2_EPS = 1e-6                       # under the root of a head's squared norm
CONV_TAPS = 2                       # both convolutions (``cca_time0 / 1``)


@dataclass(frozen=True)
class ZayaConfig:
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_layers: int = 40
    num_heads: int = 8
    num_kv_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    partial_rotary_factor: float = 0.5
    rope_theta: float = 5000000.0
    num_experts: int = 16                 # the router has one output more
    top_k: int = 1
    intermediate_size: int = 2048         # ONE expert's width
    router_hidden_size: int = 256
    max_seq_len: int = 131072
    rms_norm_eps: float = 1e-5
    # (serving never drops a token: the capacity only sizes the slabs of a
    # program over several devices)
    capacity_factor: float = 1.25
    min_capacity: int = 4

    @property
    def head_size(self) -> int:
        return self.head_dim

    @property
    def latent(self) -> int:
        """Channels of ``p = [q | k]``: the convolutions' width."""
        return (self.num_heads + self.num_kv_heads) * self.head_dim

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def tail_width(self) -> int:
        """Numbers of ONE slot's tail of ONE layer: the last two rows of
        ``p`` and the last row of ``Wv2 u``."""
        return CONV_TAPS * self.latent + self.head_dim

    @property
    def tail_part(self) -> Tuple[int, int, int]:
        """The tail's row of the pool, ``(0, sublanes, lanes)``: whole
        16-sublane tiles of the compute type, as few 128-lane tiles as hold
        it (16 x 256 for the published 2688 numbers)."""
        lanes = 128 * -(-self.tail_width // (16 * 128))
        return tail_part(0, self.tail_width, lanes)

    @classmethod
    def tiny(cls, **kw) -> "ZayaConfig":
        """The published RATIOS (a KV group of four, two KV heads, half a
        head roped) at a toy size, for CPU tests."""
        base = dict(vocab_size=256, hidden_size=64, num_layers=3,
                    num_heads=8, num_kv_heads=2, head_dim=16, num_experts=4,
                    intermediate_size=32, router_hidden_size=16,
                    max_seq_len=128, rope_theta=10000.0)
        base.update(kw)
        return cls(**base)


def _check(cfg: ZayaConfig) -> None:
    if cfg.cca_time0 != CONV_TAPS or cfg.cca_time1 != CONV_TAPS:
        raise ValueError(f"models/zaya.py convolves over {CONV_TAPS} taps "
                         f"(cca_time0 / cca_time1 {cfg.cca_time0} / "
                         f"{cfg.cca_time1}): the tail is two rows of p")
    if cfg.num_kv_heads != 2:
        raise ValueError("the value shift fills TWO key-value heads (this "
                         "token's value and the one before's)")
    if cfg.num_heads % cfg.num_kv_heads or cfg.top_k != 1 \
            or cfg.rotary_dim % 2:
        raise ValueError("query heads in whole groups of a key-value head, "
                         "top-1 routing, an even number of roped dimensions")


# --------------------------------------------------------------------------- #
# parameters
# --------------------------------------------------------------------------- #
def init(cfg: ZayaConfig, rng: jax.Array, dtype=jnp.float32) -> Params:
    """Random weights: fan-in scaled normals; both convolutions and every
    bias of a linear map as ``torch.nn`` draws them (uniform within ``fan_in
    ** -0.5``); the residual path's scales ones and biases zeros, ``tau``
    and the router's carry scale ones; the router float32 whatever
    ``dtype``, its choice bias zeros."""
    _check(cfg)
    h, v, L, r = cfg.hidden_size, cfg.vocab_size, cfg.num_layers, \
        cfg.router_hidden_size
    nh, nkv, d, C = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, cfg.latent
    E = cfg.num_experts
    keys = iter(jax.random.split(rng, 24))

    def normal(shape, fan_in, dtype=dtype):
        # a layer at a time: the float32 draw of a whole stack must not
        # stand beside the model
        one = lambda key: (jax.random.normal(key, shape[1:], F32)
                           * fan_in ** -0.5).astype(dtype)
        return lax.map(one, jax.random.split(next(keys), shape[0]))

    def uniform(shape, fan_in, dtype=dtype):
        bound = fan_in ** -0.5
        return jax.random.uniform(next(keys), shape, F32, -bound,
                                  bound).astype(dtype)

    bank = lax.map(
        lambda key: {n: w for n, w in init_moe_ffn(
            key, E, h, cfg.intermediate_size, dtype).items()
            if n != "router"}, jax.random.split(next(keys), L))
    ones = lambda *shape: jnp.ones(shape, dtype)
    zeros = lambda *shape: jnp.zeros(shape, dtype)
    path = lambda: {"b_res": zeros(L, h), "s_res": ones(L, h),
                    "b_out": zeros(L, h), "s_out": ones(L, h)}
    return {
        "embed": normal((1, v, h), h)[0],         # and the head (tied)
        "final_norm": ones(h),
        "layers": {
            "attn_norm": ones(L, h),
            # [Wq | Wk | Wv1 | Wv2]: one matmul
            "w_in": normal((L, h, C + nkv * d), h),
            "conv0_w": uniform((L, CONV_TAPS, C), CONV_TAPS),
            "conv0_b": uniform((L, C), CONV_TAPS),
            # [tap, block, in, out]: ``y = x @ W`` a block
            "conv1_w": uniform((L, CONV_TAPS, nh + nkv, d, d),
                               CONV_TAPS * d),
            "conv1_b": uniform((L, C), CONV_TAPS * d),
            "tau": ones(L, nkv),
            "wo": normal((L, nh * d, h), nh * d),
            "attn_path": path(),
            "mlp_norm": ones(L, h),
            "moe": {
                **bank,
                "router_down": normal((L, h, r), h, F32),
                "router_down_bias": uniform((L, r), h, F32),
                "router_carry": jnp.ones((L, r), F32),
                "router_norm": jnp.ones((L, r), F32),
                "router_w1": normal((L, r, r), r, F32),
                "router_b1": uniform((L, r), r, F32),
                "router_w2": normal((L, r, r), r, F32),
                "router_b2": uniform((L, r), r, F32),
                "router_out": normal((L, r, E + 1), r, F32),
                "router_bias": jnp.zeros((L, E + 1), F32)},
            "mlp_path": path(),
        },
    }


def param_logical_axes(cfg: ZayaConfig) -> Params:
    """The expert bank over ``expert``; everything else whole on every
    device (one chip serves its layers' mixers whole)."""
    flat = ("layers", None)
    path = dict.fromkeys(("b_res", "s_res", "b_out", "s_out"),
                         ("layers", "embed"))
    moe = {k: ("layers",) + tuple(v)
           for k, v in moe_ffn_logical_axes().items() if k != "router"}
    moe.update({"router_down": ("layers", "embed", None),
                "router_w1": ("layers", None, None),
                "router_w2": ("layers", None, None),
                "router_out": ("layers", None, None),
                **dict.fromkeys(("router_down_bias", "router_carry",
                                 "router_norm", "router_b1", "router_b2",
                                 "router_bias"), flat)})
    return {
        "embed": ("vocab", "embed"), "final_norm": ("embed",),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "w_in": ("layers", "embed", None),
            "conv0_w": ("layers", None, None), "conv0_b": flat,
            "conv1_w": ("layers", None, None, None, None), "conv1_b": flat,
            "tau": flat, "wo": ("layers", None, "embed"),
            "attn_path": dict(path), "mlp_norm": ("layers", "embed"),
            "moe": moe, "mlp_path": dict(path)},
    }


# --------------------------------------------------------------------------- #
# the two sublayers
# --------------------------------------------------------------------------- #
def _moe(cfg: ZayaConfig) -> MoELayer:
    """The expert layer of every forward here: the router's last output is
    the skip, an expert nobody holds; gates are the softmax's own."""
    return MoELayer(cfg.num_experts + 1, 1, cfg.capacity_factor,
                    cfg.min_capacity, drop_tokens=False, norm_topk=False,
                    held=(0, cfg.num_experts))


def _normed(cfg, x, weight):
    with jax.named_scope("norm"):
        return rms_norm(x, weight, cfg.rms_norm_eps)


def _merge(r, y, w):
    """The residual path after a sublayer."""
    with jax.named_scope("residual"):
        return (r + w["b_res"]) * w["s_res"] + (y + w["b_out"]) * w["s_out"]


def _unit(x, d: int):
    """Each head of ``x [..., d]`` (float32) at length ``sqrt(d)``."""
    return x * (lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
                * d ** 0.5)


def cca_mix(cfg, w, p, v_now, v_shift, tail, fresh, positions, table):
    """Steps 2-8 over ONE segment's rows: ``p [b, t, C]`` and the two value
    halves ``[b, t, d]`` after each row's ``tail [b, 1, tail_width]`` (zeros
    where ``fresh [b]``: the sequence starts here). Returns ``(q [b, t, H,
    d], k, v [b, t, G, d], the rows [tail | this call's] of p and of
    v_shift)`` - the next tail is their last rows."""
    b, t, C = p.shape
    H, G, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    tail = jnp.where(fresh[:, None, None], 0, tail)
    p_tail = tail[..., :CONV_TAPS * C].reshape(b, CONV_TAPS, C)
    v_tail = tail[..., CONV_TAPS * C:]
    # conv 0 at this call's rows AND the row before them (conv 1's tap)
    c0, p_ext = short_conv(jnp.concatenate([p_tail[:, 1:], p], axis=1),
                           p_tail[:, :1], w["conv0_w"], w["conv0_b"],
                           silu=False)
    # ... whose input is zero, not conv 0 of zeros, before the sequence
    c0 = c0.astype(F32).reshape(b, t + 1, H + G, d) * jnp.concatenate(
        [(~fresh)[:, None], jnp.ones((b, t), bool)], axis=1)[..., None, None]
    taps = w["conv1_w"].astype(F32)
    c1 = sum(jnp.einsum("btjc,jcd->btjd", c0[:, k:k + t], taps[k])
             for k in range(CONV_TAPS)) \
        + w["conv1_b"].astype(F32).reshape(H + G, d)
    qp, kp = (a.astype(F32).reshape(b, t, -1, d)
              for a in jnp.split(p, [H * d], axis=-1))
    per = H // G            # query heads a key-value head: g(h) = h // per
    q = c1[:, :, :H] + 0.5 * (qp + jnp.repeat(kp, per, axis=2))
    k = c1[:, :, H:] + 0.5 * (
        jnp.mean(qp.reshape(b, t, G, per, d), axis=3) + kp)
    q = _unit(q, d)
    k = _unit(k, d) * w["tau"].astype(F32)[:, None]
    v_ext = jnp.concatenate([v_tail, v_shift], axis=1)      # [b, 1 + t, d]
    v = jnp.stack([v_now, v_ext[:, :t]], axis=2)
    q, k = (apply_rotary_partial(a.astype(p.dtype), *table, positions,
                                 rotary_dim=cfg.rotary_dim) for a in (q, k))
    return q, k, v, p_ext, v_ext


def _cca_in(cfg, u, w):
    """The sublayer's projections of its normed input, one matmul: ``(p [b,
    t, C], Wv1 u, Wv2 u [b, t, d])``."""
    C, d = cfg.latent, cfg.head_dim
    return jnp.split(u @ w["w_in"], [C, C + d], axis=-1)


def _router_logits(cfg, u, z, w):
    """``(logits [b * t, E + 1] float32, z_l [b, t, r])``: the router MLP
    over the carried state."""
    dot = lambda x, m: jnp.dot(x, m, precision=lax.Precision.HIGHEST)
    with jax.named_scope("moe_router"):
        z = dot(u.astype(F32), w["router_down"]) + w["router_down_bias"] \
            + w["router_carry"] * z
        hid = rms_norm_xla(z, w["router_norm"], cfg.rms_norm_eps)
        for m, bias in (("router_w1", "router_b1"),
                        ("router_w2", "router_b2")):
            hid = jax.nn.gelu(dot(hid, w[m]) + w[bias], approximate=False)
        logits = dot(hid, w["router_out"])
    return logits.reshape(-1, logits.shape[-1]), z


def _layer(cfg, stream, w, bank, index, moe_layer, mix):
    """One layer over the stream ``(r, z)``. ``mix(u) -> (o [b, t, H, d],
    what the attention kept)``; ``bank``: the stacked expert banks of a
    grouped call ({}: the layer's own are in ``w``). Returns ``(stream,
    what the attention kept)``."""
    r, z = stream
    b, t, _ = r.shape
    with jax.named_scope("attn"):   # the pool update inside is "kv_write"
        o, kept = mix(_normed(cfg, r, w["attn_norm"]))
        y = o.reshape(b, t, -1) @ w["wo"]
    r = _merge(r, y, w["attn_path"])
    u = _normed(cfg, r, w["mlp_norm"])
    logits, z = _router_logits(cfg, u, z, w["moe"])
    y, _aux = moe_layer({**w["moe"], **bank}, u,
                        layer=index if bank else None, logits=logits)
    return (_merge(r, y, w["mlp_path"]), z), kept


def _compute(cfg, params, compute_dtype, moe_layer):
    layers, bank = _bank_apart(
        cast_floating(params["layers"], compute_dtype, keep=FLOAT32_PARAMS),
        moe_layer)
    return layers, bank, rope_frequencies(cfg.rotary_dim, cfg.max_seq_len,
                                          cfg.rope_theta)


def _stream(cfg, params, tokens, compute_dtype):
    with jax.named_scope("embed"):
        r = embedding_lookup(params["embed"], tokens, compute_dtype)
    return r, jnp.zeros(r.shape[:2] + (cfg.router_hidden_size,), F32)


def _head(cfg, params, r, compute_dtype):
    r = _normed(cfg, r, params["final_norm"].astype(compute_dtype))
    with jax.named_scope("logits"):      # the tied table
        return (r @ params["embed"].astype(compute_dtype).T).astype(F32)


# --------------------------------------------------------------------------- #
# entry points
# --------------------------------------------------------------------------- #
def apply(cfg: ZayaConfig, params: Params, tokens: jnp.ndarray, *,
          compute_dtype=jnp.bfloat16) -> jnp.ndarray:
    """Whole sequences with no cache: ``tokens [b, s]`` -> logits ``[b, s,
    vocab]`` float32, every sequence from an empty tail."""
    _check(cfg)
    b, s = tokens.shape
    moe_layer = _moe(cfg)
    layers, bank, table = _compute(cfg, params, compute_dtype, moe_layer)
    fresh = jnp.ones((b,), bool)
    tail = jnp.zeros((b, 1, cfg.tail_width), compute_dtype)

    def body(stream, scanned):
        w, index = scanned

        def mix(u):
            with jax.named_scope("cca_mix"):
                q, k, v, _, _ = cca_mix(cfg, w, *_cca_in(cfg, u, w), tail,
                                        fresh, None, table)
            return attention(q, k, v, causal=True), None

        return _layer(cfg, stream, w, bank, index, moe_layer, mix)[0], None

    (r, _), _ = lax.scan(
        body, _stream(cfg, params, tokens, compute_dtype),
        (layers, jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    return _head(cfg, params, r, compute_dtype)


def state_slot_bytes(cfg: ZayaConfig) -> int:
    """Bytes of tail ONE sequence slot holds over every layer (its row of
    the pool as laid out, in the pool's type - the engine's pools are
    bfloat16): what an admission occupies beside its KV blocks. Its
    presence is how a family declares per-slot state to the engine."""
    _, sublanes, lanes = cfg.tail_part
    return cfg.num_layers * sublanes * lanes \
        * jnp.dtype(jnp.bfloat16).itemsize


def state_rows(cfg: ZayaConfig, rows: int, chunk_rows: int) -> Dict[str, int]:
    """What a step's span says of ONE layer's CCA mixing of its call
    (``telemetry/schema.py``): ``cca_rows``, the rows mixed - the live
    single-token rows and the tokens of the chunk riding with them - and
    ``cca_tail_rows``, the pool rows their tails come from and go back to:
    one a live row, one for a chunk (a sequence's FIRST chunk reads its row
    too, and takes zeros in its place)."""
    return {"cca_rows": rows + chunk_rows,
            "cca_tail_rows": rows + int(chunk_rows > 0)}


def moe_rows(cfg: ZayaConfig, rows: int) -> Dict[str, int]:
    """``mixtral.moe_rows`` of a top-1 layer whose router has one output
    more than there are experts, from shapes alone, in expectation under a
    uniform router: ``moe_rows_skipped``, the rows it sends to the skip (no
    expert, no place, no tile: ``rows / (E + 1)``), ``moe_rows_routed`` the
    rest, ``moe_rows_computed`` the sub-tiles the sixteen experts' rows
    fill, ``moe_row_tile`` the tile (0 where the capacity slabs ran)."""
    E = cfg.num_experts
    routed = rows * E // (E + 1)
    out = {"moe_rows_routed": routed, "moe_rows_skipped": rows - routed}
    if not _moe(cfg).grouped():
        capacity = max(compute_capacity(rows, E + 1, 1, cfg.capacity_factor,
                                        cfg.min_capacity), rows)
        return {**out, "moe_rows_computed": E * capacity, "moe_row_tile": 0}
    tile = row_tile(rows, E + 1, 1, E, cfg.intermediate_size)
    sub = min(tile, ROW_SUBTILE)
    passes = E * _expected_tiles(rows, 1 / (E + 1), sub)
    return {**out, "moe_rows_computed": round(passes * sub),
            "moe_row_tile": tile}


def init_paged_cache(cfg: ZayaConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, slots: int = 1) -> Params:
    """Every layer's block pools and every layer's per-slot tails, ``slots``
    rows and the trash row (the engine passes its
    ``max_tracked_sequences``). No quantized-KV mode."""
    _check(cfg)
    _, sublanes, lanes = cfg.tail_part
    return {
        **init_paged_pools(cfg.num_layers, num_blocks, cfg.num_kv_heads,
                           block_size, cfg.head_size, dtype),
        "tail": jnp.zeros((cfg.num_layers, slots + 1, sublanes, lanes),
                          dtype)}


def _cca_rows(cfg, w, table, pool, index, rows, fresh, parts, positions,
              n_valid):
    """:func:`cca_mix` of ONE segment's rows over the tail pool: each row's
    tail from ``pool[index, rows[i]]``, the last rows of ``[tail | the row's
    n_valid[i] real tokens]`` written back there (``n_valid`` None: one
    token a row). Returns ``(pool, q, k, v)``."""
    p = parts[0]
    read, write = get_op("state_rows_read"), get_op("state_rows_write")
    tail = unpack_tail(read(pool, index, rows, cfg.tail_part), 1,
                       cfg.tail_width, p.dtype)
    q, k, v, p_ext, v_ext = cca_mix(cfg, w, *parts, tail, fresh, positions,
                                     table)
    if n_valid is None:
        new = p_ext[:, 1:], v_ext[:, 1:]
    else:
        new = next_tail(p_ext, n_valid, CONV_TAPS), \
            next_tail(v_ext, n_valid, 1)
    new = jnp.concatenate([a.reshape(a.shape[0], 1, -1) for a in new],
                          axis=-1)
    return write(pool, index, rows, pack_tail(new, cfg.tail_part),
                 cfg.tail_part), q, k, v


def apply_paged(cfg: ZayaConfig, params: Params, tokens: jnp.ndarray,
                cache: Params, block_tables: jnp.ndarray,
                context_lens: jnp.ndarray, *,
                valid: Optional[jnp.ndarray] = None,
                slots: Optional[jnp.ndarray] = None,
                rows: Optional[jnp.ndarray] = None,
                compute_dtype=jnp.bfloat16) -> Tuple[jnp.ndarray, Params]:
    """Ragged forward over the block pools and the tail pool:
    ``granite_hybrid.apply_paged``'s contract (``slots``: each row's
    sequence slot; a mixed call; ``rows``: the rows the head scores). In a
    mixed call the projections, ``W_o``, the router and the expert bank see
    every row at once; only the mixing splits into the two segments (the
    chunk's first), each over its own slots' tails, and the attention step
    splits them again for its two kernels."""
    _check(cfg)
    b, t = tokens.shape
    if valid is None:
        valid = jnp.ones((b, t), bool)
    moe_layer = _moe(cfg)
    layers, bank, table = _compute(cfg, params, compute_dtype, moe_layer)
    positions = row_positions(block_tables, context_lens, t)
    tail_rows, fresh, call = state_call(cache["tail"], block_tables,
                                        context_lens, valid, slots)

    def body(carry, scanned):
        stream, tails = carry
        w, k_c, v_c = scanned

        def mix(u):
            parts = _cca_in(cfg, u, w)
            with jax.named_scope("cca_mix"):
                if call is None:
                    pool, q, k, v = _cca_rows(
                        cfg, w, table, tails, k_c.layer, tail_rows, fresh,
                        parts, positions,
                        None if t == 1
                        else jnp.sum(valid, axis=1, dtype=jnp.int32))
                else:
                    split = [call.split(a) for a in parts]
                    pos_d, pos_c = call.split(positions[..., None])
                    pool, *chunk = _cca_rows(
                        cfg, w, table, tails, k_c.layer, tail_rows[1],
                        fresh[1], [s[1] for s in split], pos_c[..., 0],
                        call.chunk_valid[None])
                    pool, *decode = _cca_rows(
                        cfg, w, table, pool, k_c.layer, tail_rows[0],
                        fresh[0], [s[0] for s in split], pos_d[..., 0], None)
                    q, k, v = (MixedCall.join(d, c)
                               for d, c in zip(decode, chunk))
            o, k_w, v_w = paged_attention_step(
                q, k, v, k_c, v_c, block_tables, context_lens, positions,
                valid)
            return o, (pool, k_w, v_w)

        stream, (tails, k_w, v_w) = _layer(cfg, stream, w, bank, k_c.layer,
                                           moe_layer, mix)
        return (stream, tails), (k_w, v_w)

    ((r, _), tails), pools = scan_layers(
        body, (_stream(cfg, params, tokens, compute_dtype), cache["tail"]),
        layers, {n: cache[n] for n in ("k", "v")})
    return _head(cfg, params, gather_rows(r, rows), compute_dtype), \
        {**pools, "tail": tails}


def loss_fn(cfg: ZayaConfig, params: Params, batch, **kw):
    raise NotImplementedError(
        "zaya is a serving family: training through it (a backward through "
        "the grouped top-1 bank and the tail pool's kernels) is not written")


def init_cache(cfg, batch_size: int, max_len: int, dtype=jnp.bfloat16):
    raise NotImplementedError(
        "zaya has no dense-cache path (engine v1); serve it through "
        "build_engine_v2 (the paged cache with per-slot tails)")


def apply_cached(cfg, params, tokens, cache, cache_len, **kw):
    init_cache(cfg, 0, 0)
