"""Plain reference of the Solar-Open2 decoder (``upstage/Solar-Open2-250B``
``config.json``, ``model_type`` "solar_open2"), written from the equations
and not imported from ``deepspeed_tpu``. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, no pool, no
batching: one sequence, one layer at a time, ONE expert's matrices at a
time.

The stack (published keys in brackets; ``R`` is RMSNorm with a learned weight
and eps [rms_norm_eps]; no biases; NO rotary embedding: [use_rope] false):

    x = E[token]
    for l in range([num_hidden_layers]):
        u = R(x)
        l in [gqa_layers]:  softmax attention, [num_attention_heads] query
            and [num_key_value_heads] key-value heads of [head_dim]:
            o = softmax_causal(q k^T / sqrt(head_dim)) v
            x = x + W_o (o * sigmoid(W_gate u))               [use_gqa_gate]
        else:  Kimi Delta Attention ([linear_attn_config]: H = [num_heads]
            heads, dk = dv = [head_dim], every head its own k and v):
            q, k, v = silu(conv(W_q u)), silu(conv(W_k u)), silu(conv(W_v u))
                causal depthwise, [short_conv_kernel_size] taps, no bias
            q_h = q_h / |q_h| * dk^-1/2      k_h = k_h / |k_h|
            log a_t = -exp(A_log_h) softplus(W_f2 (W_f1 u_t) + dt_bias)
                in [H, dk]: one decay a CHANNEL of the key
            beta_t = 2 sigmoid(W_b u_t)  in [H]       [kda_allow_neg_eigval]
            S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_(t-1) + beta_t k_t v_t^T
            o_t = S_t^T q_t
            x = x + W_o concat_h(R_head(o_t) * sigmoid(W_g2 (W_g1 u_t)))
        n = R(x);  s = sigmoid(W_r n) in float32 over [n_routed_experts]
        the [num_experts_per_tok] largest of s + b chosen, weights s_i / sum
        ([norm_topk_prob]) times [routed_scaling_factor]
        x = x + sum_i w_i E_i(n) + E_shared(n)     SwiGLU, [moe_intermediate_size]
    logits = R(x) W_head                            (untied)

:func:`recurrent_delta` - the recurrence a token at a time - is what
:func:`logits` computes; :func:`solved_delta` is its cross-check: the whole
sequence's pseudo-values from ONE triangular system ``(I + Diag(beta) A) R =
Diag(beta) V``, ``A_ts = sum_c k_t[c] k_s[c] exp(g_t[c] - g_s[c])`` for ``s
< t``, every difference taken before its exponential (``[s, s, dk]`` of
memory: for tests).

The chip computes ONE share of each layer's experts (``held_experts``): the
held experts' part of the routed sum and the shared expert; what the other
experts would add is left out, here as in the program.

Departures from the release's code, each because the published
``config.json`` does not carry it (the configuration's ``assumed`` says the
same): the decay's and the output gate's low-rank width (the head size);
silu after each convolution and unit-length q and k (the KDA paper's layer);
``R_head`` one learned ``[dv]`` weight shared by the heads; the router's
sigmoid with a choice-only bias and the shared expert's width (the
DeepSeek-V3 key set the file uses); the GQA gate an elementwise sigmoid of a
``[hidden, heads * head_dim]`` projection before ``W_o``; no q/k norm in the
GQA layers; [gqa_layers] trusted over [gqa_interval].
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks
from .cohere2_moe import Disagreement  # noqa: F401
from .nemotron_h import route_margin

F32 = jnp.float32
L2_EPS = 1e-6
Q_BLOCK = 256       # query rows of one attention block
HEAD_ROWS, HEAD_COLS = 512, 16384      # the head: rows x vocabulary a block


@dataclasses.dataclass(frozen=True)
class Form:
    """What the reference computes; the defaults are the model and every
    other value is one of ``solar_open2_variants``' wrong ones."""
    channel_decay: bool = True      # False: one decay a head (the channels'
    #                                 mean), a gated delta rule's scalar gate
    beta_two: bool = True           # False: beta = sigmoid, eigenvalues >= 0
    l2_norm: bool = True            # False: q and k as the convolution left
    conv: bool = True               # False: silu of the projections alone
    delta_term: bool = True         # False: S <- Diag(a) S + beta k v^T, a
    #                                 plain gated linear attention
    output_gate: bool = True        # False: no gate on a KDA layer's output
    gqa_gate: bool = True           # False: none on a GQA layer's
    rope: bool = False              # True: rotary at rope_theta in the GQA
    sigmoid_router: bool = True     # False: softmax over the experts


RIGHT = Form()


def held_experts(cfg: dict):
    """(first, count) of the experts this share of the layer holds."""
    return cfg.get("experts_first", 0), cfg["num_experts"]


def _published(cfg: dict) -> dict:
    if cfg.get("tie_word_embeddings") or cfg.get("use_rope") \
            or cfg.get("first_k_dense_replace") \
            or cfg.get("kda_use_full_proj") \
            or cfg["linear_attn_config"].get("num_kv_heads") \
            or not (cfg["use_gqa_gate"] and cfg["kda_allow_neg_eigval"]
                    and cfg["n_shared_experts"] == 1):
        raise ValueError("the configuration is not one the solar_open2 "
                         "reference computes")
    return cfg


def _freeze(cfg: dict):
    """The configuration's scalars as a hashable static argument."""
    lin = cfg["linear_attn_config"]
    return tuple(sorted(
        [(k, v) for k, v in cfg.items()
         if isinstance(v, (int, float, bool, str))]
        + [("kda_heads", lin["num_heads"]), ("kda_head_dim", lin["head_dim"]),
           ("kda_conv", lin["short_conv_kernel_size"])]))


def _mm(x, w):
    return x @ w.astype(F32)


# --------------------------------------------------------------------------- #
# the delta-rule layer
# --------------------------------------------------------------------------- #
def _conv(x, taps, form):
    """Causal depthwise convolution of ``x [s, C]`` from zeros before the
    sequence, ``taps [K, C]`` (the last tap meets the token itself), then
    silu."""
    if form.conv:
        K, s = taps.shape[0], x.shape[0]
        ext = jnp.pad(x, ((K - 1, 0), (0, 0)))
        x = sum(ext[k:k + s] * taps[k].astype(F32) for k in range(K))
    return jax.nn.silu(x)


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _delta_in(u, w, cfg, form):
    """``(q, k [s, H, dk], v [s, H, dv], log_a [s, H, dk], beta [s, H], gate
    [s, H * dv])`` of the layer's normed input ``u [s, hidden]``."""
    cfg = dict(cfg)
    s, H, d = u.shape[0], cfg["kda_heads"], cfg["kda_head_dim"]
    q, k, v = (_conv(_mm(u, w[n]), w["conv_" + n], form).reshape(s, H, d)
               for n in "qkv")
    if form.l2_norm:
        q, k = _unit(q), _unit(k)
    q = q * d ** -0.5
    step = jax.nn.softplus(_mm(_mm(u, w["f1"]), w["f2"])
                           + w["dt_bias"].astype(F32)).reshape(s, H, d)
    log_a = -jnp.exp(w["A_log"].astype(F32))[None, :, None] * step
    if not form.channel_decay:
        log_a = jnp.broadcast_to(log_a.mean(-1, keepdims=True), log_a.shape)
    beta = jax.nn.sigmoid(_mm(u, w["b"])) * (2.0 if form.beta_two else 1.0)
    gate = jax.nn.sigmoid(_mm(_mm(u, w["g1"]), w["g2"]))
    return q, k, v, log_a, beta, gate


@functools.partial(jax.jit, static_argnames=("delta_term",))
def recurrent_delta(q, k, v, log_a, beta, delta_term: bool = True):
    """The recurrence a token at a time from an empty state (``lax.scan``):
    ``o [s, H, dv]``."""
    H, dk = q.shape[1:]

    def token(S, t):
        q_t, k_t, v_t, a_t, b_t = t
        S = jnp.exp(a_t)[..., None] * S                     # Diag(a) S
        read = jnp.einsum("hkv,hk->hv", S, k_t) if delta_term else 0.0
        S = S + b_t[:, None, None] * k_t[..., None] * (v_t - read)[:, None]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    return jax.lax.scan(token, jnp.zeros((H, dk, v.shape[-1]), F32),
                        (q, k, v, log_a, beta))[1]


@jax.jit
def solved_delta(q, k, v, log_a, beta):
    """The same layer from one triangular system over the whole sequence
    (the cross-check of :func:`recurrent_delta`)."""
    g = jnp.cumsum(log_a, axis=0)                           # [s, H, dk]
    s = q.shape[0]
    seen = jnp.tril(jnp.ones((s, s), bool))
    decay = jnp.exp(jnp.where(seen[..., None, None],
                              g[:, None] - g[None, :], -jnp.inf))
    A = jnp.einsum("thc,tshc,shc->hts", k, decay, k)
    P = jnp.einsum("thc,tshc,shc->hts", q, decay, k)
    lower = jnp.eye(s) + beta.T[:, :, None] * jnp.tril(A, -1)
    R = jax.scipy.linalg.solve_triangular(
        lower, beta.T[:, :, None] * v.swapaxes(0, 1), lower=True)
    return jnp.einsum("hts,hsv->thv", P, R)


@functools.partial(jax.jit, static_argnames=("eps", "form"))
def _delta_out(x, o, gate, w, eps, form):
    o = blocks.rms_norm(o, w["o_norm"], eps).reshape(o.shape[0], -1)
    if form.output_gate:
        o = o * gate
    return x + _mm(o, w["o"])


# --------------------------------------------------------------------------- #
# the softmax layer
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _attention(x, u, w, cfg, form):
    """Causal grouped-query attention over one sequence, a KV group and a
    block of query rows at a time, its output gated before ``W_o``."""
    cfg = dict(cfg)
    s = u.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = _mm(u, w["q"]).reshape(s, nh, hd)
    k = _mm(u, w["k"]).reshape(s, nkv, hd)
    v = _mm(u, w["v"]).reshape(s, nkv, hd)
    pos = jnp.arange(s)
    if form.rope:       # a wrong variant: the model applies none
        q = blocks.rope(q, pos, cfg["rope_theta"])
        k = blocks.rope(k, pos, cfg["rope_theta"])
    rows = min(Q_BLOCK, s)
    pad = -s % rows
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        s + pad, nkv, nh // nkv, hd)

    def group(g):       # one KV head and the query heads it serves
        def block(start):
            qb = jax.lax.dynamic_slice_in_dim(q[:, g], start, rows)
            sc = jnp.einsum("qhd,kd->hqk", qb, k[:, g]) / jnp.sqrt(F32(hd))
            keep = (start + jnp.arange(rows))[:, None] >= pos[None, :]
            sc = jnp.where(keep[None], sc, -jnp.inf)
            return jnp.einsum("hqk,kd->qhd", jax.nn.softmax(sc, axis=-1),
                              v[:, g])

        return jax.lax.map(block, jnp.arange(0, s + pad, rows)).reshape(
            s + pad, nh // nkv, hd)[:s]

    mix = jnp.stack([group(g) for g in range(nkv)], axis=1).reshape(s, -1)
    if form.gqa_gate:
        mix = mix * jax.nn.sigmoid(_mm(u, w["gate"]))
    return x + _mm(mix, w["o"])


# --------------------------------------------------------------------------- #
# the sparse feed-forward
# --------------------------------------------------------------------------- #
def route(z, bias, cfg, form: Form = RIGHT):
    """``[seq, experts]`` weights from router logits ``z``: a token's chosen
    experts' scores over their sum times the route scale, zero elsewhere."""
    z = z.astype(F32)
    s = jax.nn.sigmoid(z) if form.sigmoid_router \
        else jax.nn.softmax(z, axis=-1)
    idx = jax.lax.top_k(s + bias.astype(F32), cfg["num_experts_per_tok"])[1]
    top = jnp.take_along_axis(s, idx, 1)
    if cfg["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * float(cfg["routed_scaling_factor"])
    return jnp.sum(jax.nn.one_hot(idx, s.shape[1], dtype=F32)
                   * top[..., None], axis=1)


@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _route(n, router, bias, cfg, form):
    router = router.astype(F32)
    z = n @ router
    # (the margin in logits of a unit-norm column, whatever the columns'
    # own length: ``nemotron_h.route_margin``, the same router)
    return route(z, bias, dict(cfg), form), route_margin(
        z, bias, dict(cfg), jnp.sqrt(jnp.sum(router * router, axis=0)))


@jax.jit
def _expert(n, weight, gate, up, down):
    return weight[:, None] * blocks.swiglu(n, gate, up, down)


def experts(x, w, cfg, form: Form = RIGHT, margins=None):
    """``x`` plus a layer's feed-forward: the HELD routed experts under
    their weights and the shared expert, one by one. ``w["experts"]`` are
    the held experts' ``(gate, up, down)`` in order from ``experts_first``.
    ``margins``: a list that takes the layer's ``route_margin``."""
    n = blocks.rms_norm(x, w["ffn_norm"], cfg["rms_norm_eps"])
    weights, margin = _route(n, w["router"], w["router_bias"], _freeze(cfg),
                             form)
    if margins is not None:
        margins.append(margin)
    first, count = held_experts(cfg)
    assert len(w["experts"]) == count, (len(w["experts"]), count)
    for e, matrices in enumerate(w["experts"]):
        x = x + _expert(n, weights[:, first + e], *matrices)
    return x + _expert(n, jnp.ones((n.shape[0],), F32), *w["shared"])


# --------------------------------------------------------------------------- #
# the stack
# --------------------------------------------------------------------------- #
def layer(x, w, cfg, form: Form = RIGHT, margins=None):
    """One layer over one sequence, of the kind its weights say (``kind``
    beside them: ``"attention"`` or ``"delta"``)."""
    frozen, eps = _freeze(cfg), cfg["rms_norm_eps"]
    u = blocks.rms_norm(x, w["norm"], eps)
    if w["kind"] == "attention":
        x = _attention(x, u, {k: w[k] for k in ("q", "k", "v", "gate", "o")},
                       frozen, form)
    else:
        names = ("q", "k", "v", "conv_q", "conv_k", "conv_v", "f1", "f2",
                 "dt_bias", "A_log", "b", "g1", "g2")
        *qkv, log_a, beta, gate = _delta_in(u, {k: w[k] for k in names},
                                            frozen, form)
        o = recurrent_delta(*qkv, log_a, beta, delta_term=form.delta_term)
        x = _delta_out(x, o, gate, {k: w[k] for k in ("o_norm", "o")}, eps,
                       form)
    return experts(x, w, cfg, form, margins)


def hidden(cfg: dict, weights, tokens, form: Form = RIGHT, margins=None):
    """Final hidden states ``[len(tokens), hidden]``."""
    cfg = _published(cfg)
    x = weights.embed[jnp.asarray(tokens, jnp.int32)].astype(F32)
    seen = {"attention": 0, "delta": 0}
    for l in range(cfg["num_hidden_layers"]):   # the j-th layer of its kind
        kind = "attention" if l in cfg["gqa_layers"] else "delta"
        x = layer(x, weights.layer(kind, seen[kind]), cfg, form, margins)
        seen[kind] += 1
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    return blocks.rms_norm(x, final_norm, eps) @ head.astype(F32)


def logits(cfg: dict, weights, tokens, form: Form = RIGHT, rows=None,
           margins=None):
    """Logits of one sequence as a HOST array ``[rows, vocab]`` (``rows``:
    the last so many positions; None: all of them): the head runs a block of
    rows and a slice of the vocabulary at a time. ``weights`` gives
    ``embed``, ``final_norm``, ``head [hidden, vocab]`` and ``layer(kind,
    j)``: the matrices of the ``j``-th layer of a kind, with its ``kind``."""
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
# What `correct` holds the program to BESIDE the served tokens, as the
# Nemotron-3-Nano and Brumby cells' references do and for their reason: a
# served token is the top of the served logits and the harness's flat rule
# allows it 0.4 under the reference's top, which a scalar decay, a step
# without its factor 2 or a missing gate need not move it by. ``held`` reads
# every probe - the program's ``apply_paged`` logits in the served precision
# AS THE WINDOW CALLS IT: every call the engine's mixed call over the role's
# slots, the probe in a slot and in blocks of its own draw with other
# sequences live in the other slots (``families/mixed_program.py``), its
# prompt in the cell's chunks beside their decode rows, then its last tokens
# one a tick beside another sequence's chunk - against this reference's: each
# judged row's mean absolute difference over the vocabulary, in TWO parts
# because the two halves of a probe run different code: the chunked part's
# last rows (``delta_chunk``, the bank at a chunk's rows, ``paged_prefill``)
# and the rows that entered one token a tick (``delta_decode_update`` on the
# state where it lies, ``paged_decode``). One reading over both would let
# the quiet prefill rows carry a fault that lives in the single-token segment
# alone (``tools/solar_open2_check.py`` plants one there). Of each part's
# rows TWO are held, each to a limit of its own. The one at HELD_QUANTILE,
# the LOWER DECILE, as Nemotron-3-Nano's cell has it and for its reason: the
# router runs in float32 but the rows it scores are bf16's, so here and there
# a held expert is chosen the other way than in the float32 reference - that
# row reads loud, and the rows AFTER it read loud for a while too, because
# the KDA layers' state carries what the flipped expert added; a wrong form
# moves EVERY row of the part it lives in (all but 1.4 % of the rows have a
# held expert among their eight in some layer, and the mixers' variants touch
# every row by construction), so the QUIET rows tell the forms apart with the
# most room. And the MEDIAN row: a fault that leaves a tenth of a part's rows
# clean - one that starts some tokens into the decode, after a tail wraps, or
# on rows past a tile's boundary - passes the quiet row's limit and not the
# median's (on the chip a probe's median row reads 0.020-0.061 for the right
# form and 0.154 and up for the quietest wrong one). The configuration
# states the four limits (``roles.serve.held``) with the readings they lie
# between; ``logits_and_margin`` raises beyond any. PERF.md section 6, PR 57.
# --------------------------------------------------------------------------- #
HELD_QUANTILE = 0.1
DECODE_ROWS = 96    # of a probe's tokens, the last so many enter one at a
#                     time (as many as the longest-served probe), at most
#                     half of them


def decode_rows(tokens: int) -> int:
    """How many of a probe's ``tokens`` enter one at a time."""
    return min(DECODE_ROWS, tokens // 2)


def held(got, want, decode: int) -> dict:
    """The reading of one probe whose last ``decode`` judged rows came from
    single-token calls."""
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    rows = diff.mean(-1)
    assert 0 < decode < len(rows), (decode, len(rows))
    at = lambda part: float(np.quantile(part, HELD_QUANTILE))
    return {"logits_mean_abs_diff": at(rows[:-decode]),
            "decode_logits_mean_abs_diff": at(rows[-decode:]),
            "rows": len(rows), "decode_rows": decode,
            "median_row_mean_abs_diff": float(np.median(rows[:-decode])),
            "decode_median_row_mean_abs_diff": float(
                np.median(rows[-decode:])),
            "upper_quartile_row_mean_abs_diff": float(
                np.quantile(rows, 0.75)),
            "largest_row_mean_abs_diff": float(rows.max()),
            "all_rows_mean_abs_diff": float(diff.mean()),
            "logits_max_abs_diff": float(diff.max())}


# (the reading's key, the part's name, which of the part's rows it is)
HELD = (("logits_mean_abs_diff", "chunked", f"at {HELD_QUANTILE} of"),
        ("decode_logits_mean_abs_diff", "decoded", f"at {HELD_QUANTILE} of"),
        ("median_row_mean_abs_diff", "chunked", "the median of"),
        ("decode_median_row_mean_abs_diff", "decoded", "the median of"))


def disagreements(seen: dict, limits: dict) -> list:
    """Why ``held``'s reading is beyond ``limits``; empty where it is not
    (a reading that is not a number is beyond any limit). Each part's quiet
    row AND its median row have a limit of their own: a fault that leaves a
    tenth of a part's rows clean (one that starts some tokens into the
    decode, or past a tile's boundary) passes the first and not the
    second."""
    rows = {"chunked": seen["rows"] - seen["decode_rows"],
            "decoded": seen["decode_rows"]}
    return [f"the program's {what} logits lie {seen[key]} (mean absolute "
            f"difference, the row's {which} {rows[what]} judged rows) from "
            f"the reference's: the limit is {limits[key]}"
            for key, what, which in HELD if not seen[key] <= limits[key]]


# the harness calls a position's routing "decided" where its margin is over
# ``closed_loop.ROUTER_MARGIN_TOL`` (0.05 of a router logit: what a bf16
# ROUTER may flip in Mixtral's) and wants a quarter of a run's positions
# decided. This router runs in float32 on bf16 rows, and a margin here is the
# least over every layer of 40 held experts among 320: the margins are handed
# over times MARGIN_SCALE, as A.X-K1's and Nemotron's are (the readings:
# PERF.md section 6, PR 57).
MARGIN_SCALE = 2.5


def routing_margin(margins, n: int):
    """The least ``route_margin`` over the layers at each of the first
    ``n`` positions, in the harness's units."""
    return MARGIN_SCALE * functools.reduce(jnp.minimum, margins)[:n]


def logits_and_margin(cfg: dict, weights, tokens):
    """Logits, and each position's routing margin: how far the reference's
    choice of experts is from another that this chip's share would see, the
    least over the layers (the harness holds every served token whose
    routing is decided to its flat tolerance and allows ONE beyond it a run:
    ``closed_loop.judge_probes``).

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
