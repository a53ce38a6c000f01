"""Plain reference of the Mellum 2 decoder
(``JetBrains/Mellum2-12B-A2.5B-Instruct`` ``config.json``, ``model_type``
``mellum``), written from that configuration and the catalog's description
of it, not imported from ``deepspeed_tpu``. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, no cache, no
batching: one sequence, one layer at a time, ONE expert's matrices at a time.

One layer ``l`` over ``x [seq, hidden]`` (published keys in brackets; ``R``
is RMSNorm with a learned weight and eps [rms_norm_eps]; no bias anywhere):

    u = R(x);  q = u Wq ([num_attention_heads] heads of [head_dim]),
               k = u Wk, v = u Wv ([num_key_value_heads] heads)
    [layer_types][l] == "sliding_attention":
        rope on q and k, f_j = theta^(-2j/d), theta, d = [rope_parameters.
        sliding_attention.rope_theta], [head_dim]; a row at p reads the keys
        at p - [sliding_window] + 1 .. p (the window counts the row itself)
    "full_attention": YaRN ([rope_parameters.full_attention]):
        f'_j = f_j / factor * r_j + f_j (1 - r_j),
        r_j = clip((j - low) / (high - low), 0, 1),
        low = floor(c(beta_fast)), high = ceil(c(beta_slow)),
        c(b) = d ln(original_max_position_embeddings / (2 pi b)) / (2 ln theta)
        cos and sin BOTH times attention_factor; plain causal
    x = x + softmax(q k^T / sqrt(d), masked) v  Wo
    n = R(x);  s = softmax(n Wr) in float32 over [num_experts]
    the [num_experts_per_tok] largest chosen (equal scores: the lower index
    first), w_i = s_i / sum of the chosen  [norm_topk_prob]
    x = x + sum_i w_i (silu(n Wg_i) * (n Wu_i)) Wd_i   [moe_intermediate_size]
    logits = R(x_L) W_head                              (untied)

Departures and assumptions, each because the published ``config.json`` does
not settle it (the configuration's ``assumed`` says the same): the block is
pre-norm and sequential (the Llama / Mixtral shape the key set belongs to);
rope in the half-split convention (dimension ``j`` pairs with ``j + d/2``)
over the whole head; YaRN's ramp with ``truncate`` at its default and
``attention_factor`` at EVERY position; NO per-head q/k norm (the file names
none); [max_window_layers] 0 and [use_sliding_window] read as "[layer_types]
decides"; no capacity limit and no dropped token; [intermediate_size] is the
width of a dense feed-forward NO layer has ([mlp_layer_types] all "sparse");
the multi-token-prediction head the release describes has no key and is
left out; tokens are text ids.

Everything runs in blocks so that a 12 k-token probe fits beside a serving
engine: attention one KV head's group of query heads and one block of query
rows at a time, ONE expert upcast to float32 at a time, the head a slice of
the vocabulary at a time.
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
from .cohere2_moe import Disagreement
from .solar_open2 import (HELD, decode_rows,  # noqa: F401
                          disagreements, held)

F32 = blocks.F32
Q_BLOCK = 256       # query rows of one attention block
HEAD_ROWS, HEAD_COLS = 512, 16384      # the head: rows x vocabulary a block


@dataclasses.dataclass(frozen=True)
class Form:
    """What the reference computes; the defaults are the model. Each other
    value is one deliberately wrong variant (``mellum_variants``)."""
    window_on_full: bool = False    # the window on the full layers too
    window_on_window: bool = True   # (False: the window layers read it all)
    yarn_on_window: bool = False    # the YaRN table on the window layers
    yarn_on_full: bool = True       # (False: the plain table there too)
    attention_factor: bool = True   # (False: left out of cos and sin)
    half_split: bool = True         # (False: adjacent pairs rotated)
    window_off_by: int = 0          # the window one shorter or one longer
    norm_gates: bool = True         # (False: the chosen scores as they are)
    qk_norm: bool = False           # a per-head RMSNorm on q and k


RIGHT = Form()


def layer_types(cfg: dict):
    """The type of each of the ``num_hidden_layers`` that run: the published
    list's first so many (a depth cut keeps whole periods)."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def yarn_corrections(cfg: dict):
    """``(low, high)``: the dimensions YaRN's ramp runs between."""
    p = cfg["rope_parameters"]["full_attention"]
    d, theta = cfg["head_dim"], float(p["rope_theta"])

    def c(turns):
        return d * math.log(p["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))

    return (max(math.floor(c(p["beta_fast"])), 0),
            min(math.ceil(c(p["beta_slow"])), d - 1))


def rope_table(cfg: dict, layer_type: str, form: Form = RIGHT):
    """``(inverse frequencies [d / 2], what cos and sin are scaled by)`` of a
    layer type's rotary embedding."""
    d = cfg["head_dim"]
    yarn = form.yarn_on_window if layer_type == "sliding_attention" \
        else form.yarn_on_full
    p = cfg["rope_parameters"][layer_type]
    freq = float(p["rope_theta"]) ** (-np.arange(0, d, 2, dtype=np.float64)
                                      / d)
    if not yarn:
        return freq.astype(np.float32), 1.0
    p = cfg["rope_parameters"]["full_attention"]
    low, high = yarn_corrections(cfg)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    freq = freq / p["factor"] * ramp + freq * (1 - ramp)
    return freq.astype(np.float32), \
        float(p["attention_factor"]) if form.attention_factor else 1.0


def rope(x, positions, inv_freq, scale, half_split: bool = True):
    """``x [seq, heads, d]`` turned by ``positions * inv_freq``, cos and sin
    times ``scale``; dimension ``j`` pairs with ``j + d/2`` (or, not
    ``half_split``, ``2j`` with ``2j + 1``)."""
    angle = positions.astype(F32)[:, None] * jnp.asarray(inv_freq)[None, :]
    cos = (scale * jnp.cos(angle))[:, None, :]
    sin = (scale * jnp.sin(angle))[:, None, :]
    if half_split:
        x1, x2 = jnp.split(x, 2, axis=-1)
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def _unit_rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


@functools.partial(jax.jit, static_argnames=("hd", "window", "scale",
                                             "half_split", "qk_norm", "eps"))
def _attention_group(u, wq, wk, wv, wo, inv_freq, *, hd, window, scale,
                     half_split, qk_norm, eps):
    """One KV head and its group of query heads over the whole sequence:
    ``wq [hidden, g * hd]``, ``wk`` / ``wv`` ``[hidden, hd]``, ``wo
    [g * hd, hidden]``; returns the group's part of the layer's attention
    output ``[seq, hidden]``."""
    s = u.shape[0]
    g = wq.shape[1] // hd
    pos = jnp.arange(s)
    q = (u @ wq.astype(F32)).reshape(s, g, hd)
    k = (u @ wk.astype(F32)).reshape(s, 1, hd)
    v = u @ wv.astype(F32)
    if qk_norm:         # a wrong variant: the model has none
        q, k = _unit_rms(q, eps), _unit_rms(k, eps)
    q = rope(q, pos, inv_freq, scale, half_split)
    k = rope(k, pos, inv_freq, scale, half_split)[:, 0]
    rows = min(Q_BLOCK, s)

    def block(start):
        q_pos = start + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows)
        scores = jnp.einsum("qgd,kd->gqk", qb, k) * hd ** -0.5
        back = q_pos[:, None] - pos[None, :]
        keep = back >= 0
        if window is not None:
            keep = keep & (back < window)
        scores = jnp.where(keep[None], scores, -jnp.inf)
        mix = jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1), v)
        return mix.reshape(rows, g * hd)

    mix = jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s, g * hd)
    return mix @ wo.astype(F32)


def attention(u, w, cfg, layer_type: str, form: Form = RIGHT):
    """Grouped-query self-attention of one layer over one whole sequence
    ``u [seq, hidden]`` (``seq`` a multiple of the query block, or shorter
    than one), a KV head's group at a time."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    g = nh // nkv
    sliding = layer_type == "sliding_attention"
    windowed = form.window_on_window if sliding else form.window_on_full
    inv_freq, scale = rope_table(cfg, layer_type, form)
    out = jnp.zeros_like(u)
    for n in range(nkv):
        heads = slice(n * g * hd, (n + 1) * g * hd)
        one = slice(n * hd, (n + 1) * hd)
        out = out + _attention_group(
            u, w["q"][:, heads], w["k"][:, one], w["v"][:, one],
            w["o"][heads], inv_freq, hd=hd, scale=scale,
            window=cfg["sliding_window"] + form.window_off_by
            if windowed else None,
            half_split=form.half_split, qk_norm=form.qk_norm,
            eps=cfg["rms_norm_eps"])
    return out


def route(router_logits, cfg, form: Form = RIGHT):
    """``[seq, experts]`` weights: a token's top ``num_experts_per_tok``
    softmax scores, over their sum where ``norm_topk_prob``, zero
    elsewhere."""
    s = jax.nn.softmax(router_logits.astype(F32), axis=-1)
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"] and form.norm_gates:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32)
                   * top[..., None], axis=1)


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, eps):
    return blocks.rms_norm(x, weight, eps)


def route_margin(router_logits, router, k: int):
    """``[seq]``: how far each token's choice of experts is from another -
    the gap between its ``k``-th and ``k + 1``-th router logit (a softmax
    keeps their order), in logits of a unit-norm column: a gain on the
    router's columns moves a logit and what bf16 rows add to it alike."""
    top = jax.lax.top_k(router_logits.astype(F32), k + 1)[0]
    norm = jnp.sqrt(jnp.mean(jnp.sum(router * router, axis=0)))
    return (top[:, k - 1] - top[:, k]) / norm


@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _route(n, router, cfg, form):
    cfg, router = dict(cfg), router.astype(F32)
    z = n @ router
    return route(z, cfg, form), route_margin(z, router,
                                             cfg["num_experts_per_tok"])


@jax.jit
def _expert(n, weight, gate, up, down):
    return weight[:, None] * blocks.swiglu(n, gate, up, down)


def experts(n, w, cfg, form: Form = RIGHT, margins=None):
    """The expert layer's output for the normed input ``n``: every expert
    under its gate, one by one. ``w["experts"]`` are their ``(gate, up,
    down)`` in order. ``margins``: a list that takes the layer's
    ``route_margin``."""
    dense, margin = _route(n, w["router"], _freeze(cfg), form)
    if margins is not None:
        margins.append(margin)
    assert len(w["experts"]) == cfg["num_experts"], len(w["experts"])
    out = jnp.zeros_like(n)
    for e, bank in enumerate(w["experts"]):
        out = out + _expert(n, dense[:, e], *bank)
    return out


def layer(x, w, cfg, layer_type: str, form: Form = RIGHT, margins=None):
    """One block over one sequence."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(_norm(x, w["attn_norm"], eps), w, cfg, layer_type,
                      form)
    return x + experts(_norm(x, w["ffn_norm"], eps), w, cfg, form, margins)


def _freeze(cfg: dict):
    """The configuration's scalars as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def _published(cfg: dict) -> dict:
    for key in ("attention_bias", "tie_word_embeddings"):
        if cfg.get(key):
            raise ValueError(f"the mellum reference has no {key}")
    if not (cfg["hidden_act"] == "silu" and cfg["use_sliding_window"]
            and set(cfg["mlp_layer_types"]) == {"sparse"}
            and cfg["rope_parameters"]["full_attention"]["rope_type"]
            == "yarn"
            and cfg["rope_parameters"]["sliding_attention"]["rope_type"]
            == "default"
            and cfg.get("num_local_experts", cfg["num_experts"])
            == cfg["num_experts"]):
        raise ValueError("the configuration is not one the mellum reference "
                         "computes")
    return cfg


def hidden(cfg: dict, weights, tokens, form: Form = RIGHT, margins=None):
    """Final hidden states ``[len(tokens), hidden]``. The sequence is padded
    to whole query blocks with token 0 at its END: causal rows never read
    what follows them."""
    cfg = _published(cfg)
    n = len(tokens)
    pad = (-n) % Q_BLOCK if n > Q_BLOCK else 0
    tokens = jnp.concatenate([jnp.asarray(tokens, jnp.int32),
                              jnp.zeros((pad,), jnp.int32)])
    x = weights.embed[tokens].astype(F32)
    for i, layer_type in enumerate(layer_types(cfg)):
        x = layer(x, weights.layer(i), cfg, layer_type, form, margins)
    if margins is not None:     # (the padding's rows are no token's)
        margins[:] = [m[:n] for m in margins]
    return x[:n]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    return blocks.rms_norm(x, final_norm, eps) @ head.astype(F32)


def logits(cfg: dict, weights, tokens, form: Form = RIGHT, rows=None,
           margins=None):
    """Logits of one sequence as a HOST array ``[rows, vocab]`` (``rows``:
    the last so many positions; None: all of them): the head runs a block of
    rows and a slice of the vocabulary at a time. ``weights`` gives
    ``embed``, ``final_norm``, ``head [hidden, vocab]`` and ``layer(i)``."""
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


def loss(cfg: dict, weights, rows):
    """Mean next-token loss over ``rows`` of ``seq + 1`` tokens each."""
    each = []
    for row in rows:
        row = jnp.asarray(row, jnp.int32)
        each.append(blocks.next_token_loss(
            jnp.asarray(logits(cfg, weights, row[:-1])), row))
    return sum(each) / len(each)


# --------------------------------------------------------------------------- #
# What `correct` holds the program to BESIDE the served tokens, as Solar-
# Open2's cell does and by its functions (``reference/solar_open2.py``
# ``held``, ``disagreements``, ``decode_rows``): a served token is the top of
# the served logits and the harness's flat rule allows it 0.4 under the
# reference's top, which a window one token off, a rope table on the wrong
# kind of layer or a missing ``attention_factor`` need not move it by. Every
# probe's LOGITS - the program's ``apply_paged`` in the served precision AS
# THE WINDOW CALLS IT (``families/mixed_program.py``: every call the engine's
# mixed call over the role's slots, the probe in a slot and in blocks of its
# own draw, other sequences live in the other slots) - against this
# reference's: each judged row's mean absolute difference over the
# vocabulary, the chunked part's last rows and the rows that entered one
# token a tick apart (``paged_prefill`` and ``paged_decode`` are different
# kernels, and a window kind's decode walk is bounded where its chunk walk is
# masked), of each part the LOWER DECILE row and the MEDIAN row, each under a
# limit of its own. The quiet row: where the 8th and 9th router scores lie
# closer than the bf16 rows resolve, a row's eight experts differ by one and
# that row reads loud, while a wrong form moves every row. The median: a
# fault that leaves a tenth of a part's rows clean passes the quiet row's
# limit alone. The configuration states the four limits
# (``roles.serve.held``) with the readings they lie between;
# ``logits_and_margin`` raises beyond any. PERF.md section 6, PR 61.
# --------------------------------------------------------------------------- #
# the harness calls a position's routing "decided" where its margin is over
# ``closed_loop.ROUTER_MARGIN_TOL`` (0.05 of a router logit: what a bf16
# ROUTER may flip in Mixtral's) and wants a quarter of a run's positions
# decided. This router runs in float32 on bf16 rows, and a margin here is the
# least over eight layers of a top 8 of 64, whose 8th and 9th logits lie
# 0.076 apart in the mean: the margins are handed over times MARGIN_SCALE,
# as A.X-K1's, Nemotron's and Solar's are (the readings that chose it:
# PERF.md section 6, PR 61).
MARGIN_SCALE = 12.0


def routing_margin(margins, n: int):
    """The least ``route_margin`` over the layers at each of the first
    ``n`` positions, in the harness's units."""
    return MARGIN_SCALE * functools.reduce(jnp.minimum, margins)[:n]


def logits_and_margin(cfg: dict, weights, tokens):
    """Logits, and each position's routing margin: how far the reference's
    choice of experts is from another, the least over the layers. Mixtral's
    rule, not OLMoE's flat one: a served token is held to the flat
    tolerance where its routing is decided, and ONE decided position a run
    may lie beyond it (``closed_loop.judge_probes``) - every second row of
    this model carries an expert bf16 chose the other way in some layer, a
    token's reference logits lie 0.2 apart at the top of 98 304, and one
    served token in a thousand lay more than 0.4 under the reference's top
    on the chip with every logit reading inside its limits.

    Where the weights come with their program (the family's ``Weights`` do)
    the probe is ALSO held to the configuration's limits above: the reading
    is printed as a line of its own, and one beyond its limit raises
    ``Disagreement``."""
    margins = []
    out = logits(cfg, weights, tokens, margins=margins)
    program = getattr(weights, "program", None)
    if program is not None:
        decode = decode_rows(len(tokens))
        got = program.logits(cfg, tokens, decode)
        seen = held(got, out[-len(got):], decode)
        limits = {k: v for k, v in program.limits.items() if k != "why"}
        why = disagreements(seen, limits)
        print(json.dumps({"phase": "held", "tokens": len(tokens), **seen,
                          "limits": limits, "why_not": why}), flush=True)
        if why:
            raise Disagreement(f"a probe of {len(tokens)} tokens: "
                               + "; ".join(why))
    return out, routing_margin(margins, len(tokens))
