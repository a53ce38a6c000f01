"""Plain reference of the Nemotron-H decoder (``nvidia/NVIDIA-Nemotron-3-
Nano-30B-A3B-BF16`` ``config.json``, ``model_type`` ``nemotron_h``), written
from the layer equations of the published ``modeling_nemotron_h`` (the
Mamba-2 paper, arXiv:2405.21060, for the recurrence; DeepSeek-V3,
arXiv:2412.19437 section 2.1.2, for the router), not imported from
``deepspeed_tpu/models``. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, no cache, no
batching: one sequence, one layer at a time.

The stack (published keys in brackets; ``R`` is RMSNorm with a learned
weight and eps [layer_norm_epsilon]; no biases but the convolution's):

    x = E[token]
    for each character of [hybrid_override_pattern]:  x = x + mixer(R(x))
    logits = R(x) W_head                              (untied)

``M`` (Mamba-2, [mamba_num_heads] H heads of [mamba_head_dim] P, state
[ssm_state_size] N, [n_groups] G groups of B and C; ``d_inner = H P``, NOT
[expand] x hidden):

    [z | xBC | dt] = y W_in                 d_inner | d_inner + 2 G N | H
    xBC = silu(conv1d_[conv_kernel](xBC) + b)      depthwise, causal
    xBC -> x [H, P] | B [G, N] | C [G, N]
    dt = softplus(dt + dt_bias),  A = -exp(A_log)
    head h, g = h // (H / G):
        S_t = exp(dt_t A_h) S_(t-1) + dt_t x_t B_(g,t)^T
        y_t = S_t C_(g,t) + D_h x_t
    y = y * silu(z);  RMSNorm over EACH of the G groups of d_inner / G, with
    the weight [d_inner] (gate first, then norm);  out = y W_out

``E`` ([n_routed_experts] experts of [moe_intermediate_size], the
[num_experts_per_tok] best a token, one shared expert of
[moe_shared_expert_intermediate_size]; [mlp_hidden_act] relu2):

    s = sigmoid(float32(y) W_r)
    T = the k largest of s + bias      ([n_group] 1, [topk_group] 1: the
                                        group limit is the identity)
    w_e = s_e / (sum_T s + 1e-20) * [routed_scaling_factor]
    out = sum_{e in T, e held} w_e W_down,e relu(W_up,e y)^2
          + W_sdown relu(W_sup y)^2

``*``: q, k, v, o without bias, [num_attention_heads] query and
[num_key_value_heads] KV heads of [head_dim], causal softmax of ``q k^T /
sqrt(head_dim)``, NO rotary embedding (the published attention applies
none; ``rope_theta`` and ``partial_rotary_factor`` are in the file and
unused - the configuration's ``assumed``).

Departures: none in the mathematics. The recurrence is a plain ``lax.scan``
over TOKENS (the published code blocks it by [chunk_size], which is how it
is computed and no part of the result). Ties of equal scores go to the lower
index (``lax.top_k``). ``time_step_limit`` is (0, inf) and not applied.

One chip's share of the expert bank. ``num_experts`` is the experts HELD
(``experts_first`` .. + ``num_experts``, the first 0 where the key is
absent); the router runs over all [n_routed_experts] and normalises over the
token's top k wherever they live; only held experts add their term. The
shared expert is whole on every chip: the sixteen shares' ROUTED parts and
ONE shared expert are the uncut layer (``tests/test_nemotron_h.py``).

Everything runs in blocks, so that a 2 k-token probe fits beside a serving
engine that holds 13 of 16 GB: attention one KV group and one block of query
rows at a time, ONE expert's two matrices upcast to float32 at a time, the
head a block of rows and a slice of the vocabulary at a time. A sequence is
computed with zeros after it up to a whole number of ``BUCKET`` tokens
(causal: no logit of a real position moves).
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import axk1, blocks
from .cohere2_moe import Disagreement  # noqa: F401

F32 = blocks.F32
BUCKET = 256        # a sequence is padded to whole buckets
Q_BLOCK = 256       # query rows of one attention block
HEAD_ROWS = 512     # rows of one block of the head's matmul
HEAD_COLS = 16384   # vocabulary entries of one block of it


@dataclasses.dataclass(frozen=True)
class Form:
    """What the reference computes; the defaults are the model. Each other
    value is one deliberately wrong variant (``nemotron_h_variants``)."""
    grouped_bc: bool = True          # (False: group 0's B and C for all heads)
    grouped_norm: bool = True        # (False: the gate norm over all d_inner)
    gate_then_norm: bool = True      # (False: norm first, then the gate)
    state_dtype: str = "float32"     # ("bfloat16": the state kept rounded)
    squared_relu: bool = True        # (False: plain relu)
    two_matrix: bool = True          # (False: a SwiGLU-shaped reading: the
    #                                  first half of up's columns the gate)
    route_scale: bool = True         # (False: the routed sum unscaled)
    sigmoid_router: bool = True      # (False: softmax over the experts)
    bias_in_choice: bool = True      # (False: the top k of s alone)
    bias_in_gates: bool = False      # (True: gates from s + bias)
    shared_expert: bool = True       # (False: left out)
    rope: bool = False               # (True: rotary at rope_theta)


RIGHT = Form()


def held_experts(cfg: dict):
    """(first, count) of the experts this share of the layer holds."""
    return cfg.get("experts_first", 0), cfg["num_experts"]


def _published(cfg: dict) -> dict:
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias", "use_bias",
                "tie_word_embeddings"):
        if cfg.get(key):
            raise ValueError(f"the nemotron_h reference has no {key}")
    if not (cfg["use_conv_bias"] and cfg["mlp_hidden_act"] == "relu2"
            and cfg["mamba_hidden_act"] == "silu"
            and cfg["n_group"] == 1 and cfg["topk_group"] == 1
            and cfg["n_shared_experts"] == 1
            and len(cfg["hybrid_override_pattern"])
            == cfg["num_hidden_layers"]):
        raise ValueError("the configuration is not one the nemotron_h "
                         "reference computes")
    return cfg


def _freeze(cfg: dict):
    """The configuration's scalars as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


@functools.partial(jax.jit, static_argnames=("eps",))
def _norm(x, weight, eps):
    return blocks.rms_norm(x, weight, eps)


# --------------------------------------------------------------------------- #
# M: the Mamba-2 mixer
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _mamba(y, w, cfg, form):
    cfg = dict(cfg)
    s = y.shape[0]
    H, P, N = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"]
    G, K, d_in = cfg["n_groups"], cfg["conv_kernel"], H * P
    eps = cfg["layer_norm_epsilon"]
    z, xbc, dt = jnp.split(y @ w["in_proj"].astype(F32),
                           [d_in, d_in + d_in + 2 * G * N], axis=-1)
    # depthwise causal convolution: tap k meets the row K - 1 - k back
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), F32), xbc])
    taps = w["conv_w"].astype(F32)                              # [K, C]
    xbc = jax.nn.silu(sum(padded[k:k + s] * taps[k] for k in range(K))
                      + w["conv_b"].astype(F32))
    xs, B, C = jnp.split(xbc, [d_in, d_in + G * N], axis=-1)
    xs = xs.reshape(s, H, P)
    # each head's B and C: its group's
    group = jnp.arange(H) // (H // G) if form.grouped_bc \
        else jnp.zeros((H,), jnp.int32)
    B = B.reshape(s, G, N)[:, group]                            # [s, H, N]
    C = C.reshape(s, G, N)[:, group]
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(F32))
    A = -jnp.exp(w["A_log"].astype(F32))

    def token(h, t):
        x_t, dt_t, b_t, c_t = t
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        if form.state_dtype != "float32":   # a wrong variant: kept rounded
            # (reduce_precision, which the compiler must honour: it may
            # drop a convert to a narrower type and back as excess precision)
            kept = jnp.finfo(jnp.dtype(form.state_dtype))
            h = jax.lax.reduce_precision(h, kept.nexp, kept.nmant)
        return h, jnp.einsum("hpn,hn->hp", h, c_t)

    _, out = jax.lax.scan(token, jnp.zeros((H, P, N), F32), (xs, dt, B, C))
    out = (out + w["D"].astype(F32)[:, None] * xs).reshape(s, d_in)
    gate, weight = jax.nn.silu(z), w["gate_norm"].astype(F32)
    parts = G if form.grouped_norm else 1

    def norm(v):        # RMSNorm over each of ``parts`` equal parts
        v = v.reshape(s, parts, d_in // parts)
        v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
        return v.reshape(s, d_in) * weight

    out = norm(out * gate) if form.gate_then_norm else norm(out) * gate
    return out @ w["out_proj"].astype(F32)


# --------------------------------------------------------------------------- #
# E: the experts
# --------------------------------------------------------------------------- #
def scores(router_logits, form: Form = RIGHT):
    z = router_logits.astype(F32)
    return jax.nn.sigmoid(z) if form.sigmoid_router \
        else jax.nn.softmax(z, axis=-1)


def route(router_logits, bias, cfg, form: Form = RIGHT):
    """``[seq, experts]`` gates: a token's chosen experts' scores over their
    sum times the route scale, zero elsewhere."""
    s = scores(router_logits, form)
    n = s.shape[1]
    biased = s + bias.astype(F32)
    idx = jax.lax.top_k(biased if form.bias_in_choice else s,
                        cfg["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(biased if form.bias_in_gates else s, idx, 1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    if form.route_scale:
        top = top * float(cfg["routed_scaling_factor"])
    return jnp.sum(jax.nn.one_hot(idx, n, dtype=F32) * top[..., None],
                   axis=1)


def route_margin(router_logits, bias, cfg, spread=1.0):
    """``[seq]``: how far each token's routing (the right form's) is from a
    choice that would change what THIS share of the experts computes, in
    ROUTER LOGITS of a unit-norm column. The choice is by ``c = sigmoid(z) +
    bias``; what moves a row is a HELD expert entering or leaving its top k.
    So: the least, over the held experts, of the gap between that expert's
    ``c`` and the best unchosen one's (if it is chosen) or the last chosen
    one's (if it is not), as the logit gap that closes it (``c`` moves by ``s
    (1 - s)`` a unit of its logit, and what bf16 rows move an expert's logit
    by goes with its column's norm ``spread [experts]``: a column twice as
    long doubles its logit's noise with its logit; a gap closes when each
    side moves half of it)."""
    z = router_logits.astype(F32)
    s = jax.nn.sigmoid(z)
    c, move = s + bias.astype(F32), s * (1.0 - s) * spread
    n, k = s.shape[1], cfg["num_experts_per_tok"]
    first, count = held_experts(cfg)
    is_held = (jnp.arange(n) >= first) & (jnp.arange(n) < first + count)
    top, idx = jax.lax.top_k(c, k + 1)
    last, nxt = top[:, k - 1:k], top[:, k:k + 1]
    m_last = jnp.take_along_axis(move, idx[:, k - 1:k], 1)
    m_next = jnp.take_along_axis(move, idx[:, k:k + 1], 1)
    gap = jnp.where(c >= last, 2.0 * (c - nxt) / (move + m_next),
                    2.0 * (last - c) / (move + m_last))
    return jnp.min(jnp.where(is_held[None], gap, jnp.inf), axis=1)


@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _route(y, router, bias, cfg, form):
    router = router.astype(F32)
    z = y @ router
    return route(z, bias, dict(cfg), form), route_margin(
        z, bias, dict(cfg), jnp.sqrt(jnp.sum(router * router, axis=0)))


def activation(u, form: Form = RIGHT):
    r = jax.nn.relu(u)
    return r * r if form.squared_relu else r


@functools.partial(jax.jit, static_argnames=("form",))
def _expert(y, weight, up, down, form):
    up, down = up.astype(F32), down.astype(F32)
    if form.two_matrix:
        return weight[:, None] * (activation(y @ up, form) @ down)
    # the wrong reading: up's first half of columns a gate for its second
    f = up.shape[1] // 2
    return weight[:, None] * (
        (jax.nn.silu(y @ up[:, :f]) * (y @ up[:, f:2 * f])) @ down[:f])


def experts(y, w, cfg, form: Form = RIGHT, margins=None, loads=None):
    """A sparse layer's feed-forward of the normed input ``y``: the HELD
    routed experts under their (scaled) gates and the shared expert, one by
    one. ``w["experts"]`` are the held experts' ``(up, down)`` in order from
    ``experts_first``. ``margins``: a list that takes the layer's
    :func:`route_margin`; ``loads``: one that takes ``[seq, held]``, whether
    each row chose each held expert."""
    gates, margin = _route(y, w["router"], w["router_bias"], _freeze(cfg),
                           form)
    if margins is not None:
        margins.append(margin)
    first, count = held_experts(cfg)
    if loads is not None:
        loads.append(np.asarray(gates[:, first:first + count] > 0))
    assert len(w["experts"]) == count, (len(w["experts"]), count)
    out = jnp.zeros_like(y)
    # an expert is its published width: a program may LAY its bank OUT
    # wider (whole lane tiles), and what lies past the width is not the
    # model's - if it is not zeros there, the program's logits show it
    f = cfg["moe_intermediate_size"]
    for e, (up, down) in enumerate(w["experts"]):
        out = out + _expert(y, gates[:, first + e], up[:, :f], down[:f],
                            form=form)
    if form.shared_expert:
        out = out + _expert(y, jnp.ones((y.shape[0],), F32), *w["shared"],
                            form=form)
    return out


# --------------------------------------------------------------------------- #
# *: attention
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _attention(y, w, cfg, form):
    """Causal grouped-query attention over one sequence ``y [seq, hidden]``
    (``seq`` a multiple of the query block, or shorter than one), a KV group
    and a block of query rows at a time."""
    cfg = dict(cfg)
    s = y.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = (y @ w["q"].astype(F32)).reshape(s, nh, hd)
    k = (y @ w["k"].astype(F32)).reshape(s, nkv, hd)
    v = (y @ w["v"].astype(F32)).reshape(s, nkv, hd)
    pos = jnp.arange(s)
    if form.rope:       # a wrong variant: the model applies none
        q = blocks.rope(q, pos, cfg["rope_theta"])
        k = blocks.rope(k, pos, cfg["rope_theta"])
    rows = min(Q_BLOCK, s)
    q = q.reshape(s, nkv, nh // nkv, hd)

    def group(g):       # one KV head and the query heads it serves
        qg, kg, vg = q[:, g], k[:, g], v[:, g]

        def block(start):
            qb = jax.lax.dynamic_slice_in_dim(qg, start, rows)
            sc = jnp.einsum("qhd,kd->hqk", qb, kg) / jnp.sqrt(F32(hd))
            keep = (start + jnp.arange(rows))[:, None] >= pos[None, :]
            sc = jnp.where(keep[None], sc, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(sc, axis=-1), vg)

        return jax.lax.map(block, jnp.arange(0, s, rows)).reshape(
            s, nh // nkv, hd)

    mix = jnp.stack([group(g) for g in range(nkv)], axis=1)
    return mix.reshape(s, nh * hd) @ w["o"].astype(F32)


# --------------------------------------------------------------------------- #
# the stack
# --------------------------------------------------------------------------- #
def layer(x, w, cfg, form: Form = RIGHT, margins=None, loads=None):
    """One layer over one sequence, of the kind its weights say (``kind``
    beside them: a character of the pattern)."""
    y = _norm(x, w["norm"], cfg["layer_norm_epsilon"])
    kind = w["kind"]
    if kind == "M":
        keys = ("in_proj", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                "gate_norm", "out_proj")
        return x + _mamba(y, {k: w[k] for k in keys}, _freeze(cfg), form)
    if kind == "*":
        return x + _attention(y, {k: w[k] for k in "qkvo"}, _freeze(cfg),
                              form)
    return x + experts(y, w, cfg, form, margins, loads)


def hidden(cfg: dict, weights, tokens, form: Form = RIGHT, margins=None,
           loads=None):
    """Final hidden states ``[len(tokens), hidden]``; the sequence is padded
    to whole buckets with token 0 at its END."""
    cfg = _published(cfg)
    n = len(tokens)
    tokens = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, -n % BUCKET))
    x = weights.embed[tokens].astype(F32)
    pattern = cfg["hybrid_override_pattern"]
    for i, kind in enumerate(pattern):      # the j-th layer of its kind
        x = layer(x, weights.layer(kind, pattern[:i].count(kind)), cfg, form,
                  margins, loads)
    return x[:n]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    """Logits over one slice ``head [hidden, entries]`` of the vocabulary."""
    return blocks.rms_norm(x, final_norm, eps) @ head.astype(F32)


def logits(cfg: dict, weights, tokens, form: Form = RIGHT, rows=None,
           margins=None, loads=None):
    """Logits of one sequence as a HOST array ``[rows, vocab]`` (``rows``:
    the last so many positions; None: all of them): the head runs a block of
    rows and a slice of the vocabulary at a time. ``weights`` gives
    ``embed``, ``final_norm``, ``head`` and ``layer(kind, j)``: the matrices
    of the ``j``-th layer of a kind, with its ``kind``."""
    with jax.default_matmul_precision("highest"):
        x = hidden(cfg, weights, tokens, form, margins, loads)
        if rows is not None:
            x = x[-rows:]
        vocab = weights.head.shape[1]
        out = np.empty((x.shape[0], vocab), np.float32)
        for a in range(0, x.shape[0], HEAD_ROWS):
            for c in range(0, vocab, HEAD_COLS):
                out[a:a + HEAD_ROWS, c:c + HEAD_COLS] = np.asarray(_head(
                    x[a:a + HEAD_ROWS], weights.final_norm,
                    weights.head[:, c:c + HEAD_COLS],
                    cfg["layer_norm_epsilon"]))
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
# What `correct` holds the program to BESIDE the served tokens, as the
# Command A+ and A.X-K1 cells' references do and for their reason: a served
# token is the top of the served logits and the harness's flat rule allows
# it 0.4 under the reference's top, which one B / C group for every head, a
# gate norm over the whole width or a choice without its bias does not move
# it by. ``held`` reads every probe - the program's ``apply_paged`` logits in
# the served precision, prefill in the cell's chunks then decode through the
# pools (``families/nemotron_h.py`` ``Program``) - against this reference's:
# each judged row's mean absolute difference over the vocabulary, in TWO
# readings with a limit each, because the two halves of a probe run
# different code: ``logits_mean_abs_diff`` over the chunked part's last 64
# rows (the chunked scan, the bank at a chunk's rows, ``paged_prefill``) and
# ``decode_logits_mean_abs_diff`` over the DECODE_ROWS rows that entered one
# token a call (``ssm_decode_update`` on the state where it lies, the bank
# at a row, ``paged_decode``). One quantile over both would let 64 quiet
# prefill rows carry a fault that lives in the single-token call alone
# (``tools/nemotron_h_check.py`` plants two there). Of each part's rows the
# one at HELD_QUANTILE. Why a LOW quantile where A.X-K1's is 0.6 and
# command-a's the median: this model routes in 23 layers over a recurrent
# state, and although its router runs in float32 the rows it is applied to
# are bf16's, so here and there a held expert is chosen the other way than
# in the float32 reference - that row reads 0.05-0.2, and the rows AFTER it
# read loud for a while too, because the Mamba layers' state carries what
# the flipped expert added. A wrong form, on the other hand, moves EVERY row
# of the part it lives in: with 23 sparse layers all but one row in ten
# thousand has a held expert among its six in some layer, and the mixer's
# and attention's variants touch every row by construction. So the QUIET
# rows carry the comparison: the row at the lower decile is bf16's own noise
# for the right form and moves with any wrong one. The configuration states
# both limits (``roles.serve.held``); ``logits_and_margin`` raises beyond
# either. The readings they lie between: PERF.md section 6, PR 50.
# --------------------------------------------------------------------------- #
HELD_QUANTILE = 0.1
DECODE_ROWS = 96    # of a probe's tokens, the last so many enter one at a
#                     time (as many as the longest probe serves), at most
#                     half of them


def decode_rows(tokens: int) -> int:
    """How many of a probe's ``tokens`` enter one at a time."""
    return min(DECODE_ROWS, tokens // 2)


def held(got, want, decode: int) -> dict:
    """The reading of one probe whose last ``decode`` judged rows came from
    single-token calls: ``axk1.held``'s fields over all the rows, with
    ``logits_mean_abs_diff`` the row's at ``HELD_QUANTILE`` of the CHUNKED
    part's rows' mean absolute differences and
    ``decode_logits_mean_abs_diff`` the same of the decoded rows (above)."""
    rows = np.abs(np.asarray(got, np.float32) - np.asarray(want)).mean(-1)
    assert 0 < decode < len(rows), (decode, len(rows))
    at = lambda part: float(np.quantile(part, HELD_QUANTILE))
    return {**axk1.held(got, want),
            "logits_mean_abs_diff": at(rows[:-decode]),
            "decode_logits_mean_abs_diff": at(rows[-decode:]),
            "decode_rows": decode,
            "decode_median_row_mean_abs_diff": float(
                np.median(rows[-decode:])),
            "upper_quartile_row_mean_abs_diff": float(
                np.quantile(rows, 0.75))}


def disagreements(seen: dict, limits: dict) -> list:
    """Why ``held``'s reading is beyond ``limits``; empty where it is not
    (a reading that is not a number is beyond any limit)."""
    parts = (("logits_mean_abs_diff", seen["rows"] - seen["decode_rows"],
              "chunked"),
             ("decode_logits_mean_abs_diff", seen["decode_rows"], "decoded"))
    return [f"the program's {what} logits lie {seen[key]} (mean absolute "
            f"difference, the row's at {HELD_QUANTILE} of {n} judged rows) "
            f"from the reference's: the limit is {limits[key]}"
            for key, n, what in parts if not seen[key] <= limits[key]]


# the harness calls a position's routing "decided" where its margin is over
# ``closed_loop.ROUTER_MARGIN_TOL`` (0.05 of a router logit: what a bf16
# ROUTER may flip in Mixtral's), and wants a quarter of a run's positions
# decided. This router runs in float32 on bf16 rows, and a margin here is
# the least over 23 layers of eight held experts among 128: on the chip (PR
# 50, ``tools/nemotron_h_check.py``: 2 592 judged rows of 36 probes) only
# 16 % of the rows lie over 0.05 - a run's 115 served positions would fall
# under the quarter in most runs -, and of the ten rows whose served token
# lay more than 0.3 under the reference's top every one had a margin under
# 0.016. So the margins are handed over times MARGIN_SCALE, as A.X-K1's
# are: over 0.02 of a logit 48 % of the positions are decided, and none of
# those 1 248 lay more than 0.3 under the top (the harness's tolerance is
# 0.4 and it allows a run one beyond it).
MARGIN_SCALE = 2.5


# What the probes' routing says of the LOAD: of the rows this process's
# probes sent through the right form, how many chose each held expert of
# each sparse layer. ``moe_relu2_experts_roofline`` counts the bank's bytes
# by these measured shares (``readers/nemotron_h_roofline.py``), not by a
# uniform router's ``top_k / experts``: the configuration's weights are
# balanced only roughly (PERF.md section 6, PR 50: a held expert is chosen
# by 0-30 % of the rows where the uniform share is 4.7 %), and a kernel that
# skips an expert no row reached must not read over its roofline for it.
_ROUTED = {"rows": 0, "chosen": 0}


def note_routing(loads, n: int) -> None:
    """Add one probe's ``loads`` (``experts``' ``[seq, held]`` a sparse
    layer), its first ``n`` rows, to the process's count."""
    _ROUTED["rows"] += n
    _ROUTED["chosen"] = _ROUTED["chosen"] + np.stack(
        [np.asarray(x)[:n].sum(0) for x in loads])


def routed_shares():
    """``[sparse layers, held]``: the share of the probes' rows that chose
    each held expert; None before any probe."""
    if not _ROUTED["rows"]:
        return None
    return _ROUTED["chosen"] / _ROUTED["rows"]


def routing_margin(margins, n: int):
    """The least :func:`route_margin` over the sparse layers at each of the
    first ``n`` positions, in the harness's units."""
    return MARGIN_SCALE * functools.reduce(jnp.minimum, margins)[:n]


def logits_and_margin(cfg: dict, weights, tokens):
    """Logits, and each position's routing margin: how far the reference's
    choice of experts is from another that this chip's share would see, the
    least over the sparse layers (the harness holds every served token whose
    routing is decided to its flat tolerance and allows ONE beyond it a run:
    ``closed_loop.judge_probes``).

    Where the weights come with their program (the family's ``Weights`` do)
    the probe is ALSO held to the configuration's limit above: the reading
    is printed as a line of its own, and one beyond its limit raises
    ``Disagreement``."""
    margins, loads = [], []
    out = logits(cfg, weights, tokens, margins=margins, loads=loads)
    note_routing(loads, len(tokens))
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
