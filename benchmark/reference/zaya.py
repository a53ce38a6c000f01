"""Plain reference of the ZAYA1 decoder (``Zyphra/ZAYA1-8B`` ``config.json``,
``model_type`` ``zaya``), written from that configuration and the two public
descriptions the family rests on (Compressed Convolutional Attention,
arXiv:2510.04476; the ZAYA1 report, arXiv:2511.17127), not imported from
``deepspeed_tpu``. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, no cache, no
batching: one sequence, one layer at a time, ONE expert's matrices at a time.

Published keys in brackets; ``n`` is RMSNorm with a learned weight and eps
[rms_norm_eps]; ``H`` = [num_attention_heads], ``G`` =
[num_key_value_heads], ``d`` = [head_dim], ``g(h) = h // (H / G)``; no bias
on the five projections [attention_bias]. Every layer [layer_types:
"hybrid"] is two sublayers on a residual path with a learned scale and bias
on both branches (``s``, ``b`` ``[hidden]``), applied AFTER each sublayer:

    u = n(r);  y = sublayer(u);  r <- (r + b_res) s_res + (y + b_out) s_out
    logits = n_final(r) E^T            [tie_word_embeddings], no bias

CCA sublayer over ``u [seq, hidden]``:

    1. qp = u Wq (H d), kp = u Wk (G d);  p = [qp | kp]
    2. c0_t = a0 p_(t-1) + a1 p_t + beta0      depthwise, K = [cca_time0]
    3. c1_t[j] = c0_(t-1)[j] A0[j] + c0_t[j] A1[j] + beta1[j]
       grouped: H + G blocks of d channels, K = [cca_time1]; the INPUT of
       each convolution is zero before the sequence (p_(-1) = c0_(-1) = 0)
    4. mq_t[h] = (qp_t[h] + kp_t[g(h)]) / 2
       mk_t[g] = (mean over the heads h of group g of qp_t[h] + kp_t[g]) / 2
    5. q_t[h] = c1_t[h] + mq_t[h];   k_t[g] = c1_t[H + g] + mk_t[g]
    6. q <- sqrt(d) q / |q|;   k <- tau_g sqrt(d) k / |k|
    7. v_t = [u_t Wv1 | u_(t-1) Wv2]: KV head 0 this token's value, KV
       head 1 the token before's (u_(-1) = 0)
    8. rope on the first [partial_rotary_factor] d dimensions of each head
       of q and k, half-split pairing, [rope_parameters.hybrid.rope_theta]
    9. causal GQA attention, scale 1 / sqrt(d), no window [sliding_window
       null];  y_t = o_t Wo

MoE sublayer, ``z`` the router's state of the layer before (none at layer 0):

    z_l = u Wd + bd + gamma_l z_(l-1)          [router_hidden_size]
    logits = gelu(gelu(n(z_l) W1 + b1) W2 + b2) W3       [num_experts] + 1
    P = softmax(logits);  e = argmax(P + beta) (the lower index first on
    equal scores);  w = P[e], not renormalised   [num_experts_per_tok] 1
    e < [num_experts]: y = w (silu(u Wg_e) * (u Wu_e)) Wd_e
    e = [num_experts] (skip): y = 0, no expert   [moe_intermediate_size]

ASSUMED, each because no key of the catalog's configuration settles it (the
configuration file's ``assumed`` says the same; each has a deliberately wrong
variant in ``zaya_variants``): that the first convolution is depthwise and
the second grouped by head, both with a bias; the q-k mean and the value
shift as written; the L2 norm before the rope; ``repeat_interleave`` grouping
of the query heads; the 17th "skip" output and its zero; the carried router
state entering before the norm; exact (erf) GELU in the router;
scale-and-bias on both residual branches; half-split rope pairing; text ids.

Everything runs in blocks so that a probe fits beside a serving engine that
holds 95 % of the chip: attention one KV head's group of query heads and one
block of query rows at a time, ONE expert upcast to float32 at a time, the
head a slice of the vocabulary at a time.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks
from .cohere2_moe import Disagreement
from . import solar_open2
from .solar_open2 import decode_rows, held  # noqa: F401

F32 = blocks.F32
Q_BLOCK = 256       # query rows of one attention block
HEAD_ROWS, HEAD_COLS = 512, 16384      # the head: rows x vocabulary a block
L2_EPS = 1e-6       # under the root of a head's squared norm


@dataclasses.dataclass(frozen=True)
class Form:
    """What the reference computes; the defaults are the model. Each other
    value is one deliberately wrong variant (``zaya_variants``)."""
    conv: bool = True               # (False: q and k from p and the mean)
    conv1_grouped: bool = True      # (False: conv 1 depthwise, its diagonals)
    qk_mean: bool = True            # (False: q, k the convolution's alone)
    value_shift: bool = True        # (False: KV head 1 is u_t Wv2)
    l2_norm: bool = True            # (False: q and k as they come)
    tau: bool = True                # (False: the keys' scale left out)
    rope_whole_head: bool = False   # rope over all d dimensions
    carry_router: bool = True       # (False: z_l = u Wd + bd alone)
    router_bf16: bool = False       # the router's weights and rows in bf16
    renorm_gate: bool = False       # the gate renormalised to 1
    skip_to_expert0: bool = False   # a skipped row through expert 0
    residual_scale: bool = True     # (False: s_res left out)
    # what a row at the START of a call reads of the tokens before it (its
    # sequence's tail), at the positions ``logits(starts=)`` names: "kept"
    # as computed, "dropped" zeros, or rounded to a narrower type's name
    tail: str = "kept"


RIGHT = Form()


def _freeze(cfg: dict):
    """The configuration's scalars as a hashable static argument."""
    return tuple(sorted(
        [(k, v) for k, v in cfg.items()
         if isinstance(v, (int, float, bool, str))]
        + [("rope_theta", cfg["rope_parameters"]["hybrid"]["rope_theta"])]))


def _published(cfg: dict) -> dict:
    if cfg.get("attention_bias") or cfg.get("lm_head_bias") \
            or not cfg.get("tie_word_embeddings") \
            or cfg.get("sliding_window") is not None \
            or cfg["hidden_act"] != "silu" \
            or cfg["cca_time0"] != 2 or cfg["cca_time1"] != 2 \
            or cfg["num_experts_per_tok"] != 1 \
            or cfg["num_key_value_heads"] != 2 \
            or set(cfg["layer_types"][:cfg["num_hidden_layers"]]) \
            != {"hybrid"} \
            or cfg["rope_parameters"]["hybrid"]["rope_type"] != "default" \
            or cfg.get("num_local_experts", cfg["num_experts"]) \
            != cfg["num_experts"]:
        raise ValueError("the configuration is not one the zaya reference "
                         "computes")
    return cfg


def _mm(x, w):
    return x @ w.astype(F32)


def _as(x, dtype: str):
    """``x`` rounded to the floating type ``dtype`` names and back, by
    ``lax.reduce_precision`` (a pair of converts is what XLA on the chip
    takes out again: it allows excess precision)."""
    info = jnp.finfo(jnp.dtype(dtype))
    return jax.lax.reduce_precision(x.astype(F32), info.nexp, info.nmant)


def _rounded(x, tail: str):
    """A tail as a call's first row reads it (``Form.tail``)."""
    if tail == "kept":
        return x
    if tail == "dropped":
        return jnp.zeros_like(x)
    return _as(x, tail)


def _before(x, starts, tail: str):
    """``x_(t-1)`` for every row ``t`` of ``x [seq, C]`` (zeros before the
    sequence); at a row where ``starts [seq]`` holds it is the tail's."""
    prev = jnp.pad(x, ((1, 0), (0, 0)))[:-1]
    if tail == "kept":
        return prev
    return jnp.where(starts[:, None], _rounded(prev, tail), prev)


def rope(x, positions, theta: float, rotary: int):
    """The first ``rotary`` dimensions of each head of ``x [seq, heads, d]``
    turned by ``positions * theta ** (-2 j / rotary)``, dimension ``j``
    paired with ``j + rotary / 2``; the rest pass."""
    return jnp.concatenate([blocks.rope(x[..., :rotary], positions, theta),
                            x[..., rotary:]], axis=-1)


def _unit(x, d):
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)
                * d ** 0.5)


@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _mix(u, w, starts, cfg, form):
    """Steps 1-8: ``(q [seq, H, d], k, v [seq, G, d])``."""
    cfg = dict(cfg)
    H, G, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    s, per = u.shape[0], H // G
    qp, kp = _mm(u, w["wq"]), _mm(u, w["wk"])
    p = jnp.concatenate([qp, kp], axis=-1)
    qp, kp = qp.reshape(s, H, d), kp.reshape(s, G, d)
    if form.conv:
        a, beta0 = w["conv0_w"].astype(F32), w["conv0_b"].astype(F32)
        p1 = _before(p, starts, form.tail)                  # p_(t-1)
        c0 = a[0] * p1 + a[1] * p + beta0
        # c0_(t-1): the row before's own, or, at a call's first row, conv 0
        # over the tail's two rows; zero before the sequence
        c0_before = jnp.pad(c0, ((1, 0), (0, 0)))[:-1]
        if form.tail != "kept":
            p2 = _rounded(jnp.pad(p, ((2, 0), (0, 0)))[:-2], form.tail)
            from_tail = a[0] * p2 + a[1] * p1 + beta0
            c0_before = jnp.where((starts & (jnp.arange(s) > 0))[:, None],
                                  from_tail, c0_before)
        A = w["conv1_w"].astype(F32)                        # [2, H + G, d, d]
        if not form.conv1_grouped:      # the blocks' diagonals alone
            A = A * jnp.eye(d, dtype=F32)
        c1 = jnp.einsum("sjc,jcd->sjd", c0_before.reshape(s, H + G, d), A[0]) \
            + jnp.einsum("sjc,jcd->sjd", c0.reshape(s, H + G, d), A[1]) \
            + w["conv1_b"].astype(F32).reshape(H + G, d)
    else:
        c1 = p.reshape(s, H + G, d)
    q, k = c1[:, :H], c1[:, H:]
    if form.qk_mean:
        q = q + 0.5 * (qp + jnp.repeat(kp, per, axis=1))
        k = k + 0.5 * (jnp.mean(qp.reshape(s, G, per, d), axis=2) + kp)
    if form.l2_norm:
        q, k = _unit(q, d), _unit(k, d)
    if form.tau:
        k = k * w["tau"].astype(F32)[:, None]
    v_now, v_next = _mm(u, w["wv1"]), _mm(u, w["wv2"])
    v = jnp.stack([v_now, _before(v_next, starts, form.tail)
                   if form.value_shift else v_next], axis=1)
    pos = jnp.arange(s)
    theta = float(cfg["rope_theta"])
    rotary = d if form.rope_whole_head \
        else int(d * cfg["partial_rotary_factor"])
    return rope(q, pos, theta, rotary), rope(k, pos, theta, rotary), v


@jax.jit
def _attention_group(q, k, v, wo):
    """One KV head and its group of query heads over the whole sequence:
    ``q [seq, g, d]``, ``k``, ``v`` ``[seq, d]``, ``wo [g * d, hidden]``."""
    s, g, d = q.shape
    pos = jnp.arange(s)
    rows = min(Q_BLOCK, s)

    def block(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows)
        scores = jnp.einsum("qgd,kd->gqk", qb, k) * d ** -0.5
        keep = (start + jnp.arange(rows))[:, None] >= pos[None, :]
        scores = jnp.where(keep[None], scores, -jnp.inf)
        mix = jnp.einsum("gqk,kd->qgd", jax.nn.softmax(scores, axis=-1), v)
        return mix.reshape(rows, g * d)

    mix = jax.lax.map(block, jnp.arange(0, s, rows)).reshape(s, g * d)
    return mix @ wo.astype(F32)


def attention(u, w, cfg, form: Form = RIGHT, starts=None):
    """The CCA sublayer over one whole sequence ``u [seq, hidden]`` (``seq``
    a multiple of the query block, or shorter than one)."""
    H, G, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
               cfg["head_dim"])
    if starts is None:
        starts = jnp.zeros(u.shape[0], bool)
    q, k, v = _mix(u, {n: w[n] for n in (
        "wq", "wk", "wv1", "wv2", "conv0_w", "conv0_b", "conv1_w",
        "conv1_b", "tau")}, starts, _freeze(cfg), form)
    per, wo = H // G, w["wo"]
    out = jnp.zeros_like(u)
    for n in range(G):
        out = out + _attention_group(
            q[:, n * per:(n + 1) * per], k[:, n], v[:, n],
            wo[n * per * d:(n + 1) * per * d])
    return out


@functools.partial(jax.jit, static_argnames=("eps", "form"))
def probabilities(u, z, r, eps, form=RIGHT):
    """``(P [seq, E + 1], z_l)``: the router MLP over the carried state and
    the softmax over its outputs (what the choice bias is added to)."""
    cast = (lambda a: _as(a, "bfloat16")) if form.router_bf16 \
        else (lambda a: a.astype(F32))
    z_l = cast(u) @ cast(r["down"]) + cast(r["down_bias"])
    if form.carry_router and z is not None:
        z_l = z_l + cast(r["carry"]) * z
    hid = blocks.rms_norm(cast(z_l), cast(r["norm"]), eps)
    for m, b in (("w1", "b1"), ("w2", "b2")):
        hid = cast(jax.nn.gelu(cast(hid) @ cast(r[m]) + cast(r[b]),
                               approximate=False))
    return jax.nn.softmax(hid @ cast(r["out"]), axis=-1), z_l


@functools.partial(jax.jit, static_argnames=("eps", "form"))
def _route(u, z, r, eps, form):
    """``(weights [seq, E + 1], z_l, each row's top-1 margin)``: each row's
    ONE output under its gate."""
    P, z_l = probabilities(u, z, r, eps, form)
    score = P + r["bias"].astype(F32)
    top, idx = jax.lax.top_k(score, 2)
    gate = jnp.take_along_axis(P, idx[:, :1], axis=1)
    if form.renorm_gate:
        gate = jnp.ones_like(gate)
    weights = jax.nn.one_hot(idx[:, 0], P.shape[-1], dtype=F32) * gate
    # how far the choice is from another: the two best scores' gap over the
    # larger of their probabilities - their logits' gap where the choice
    # bias is zero and the gap small (what a rounded row moves is a logit)
    both = jnp.take_along_axis(P, idx, axis=1)
    margin = (top[:, 0] - top[:, 1]) / jnp.max(both, axis=1)
    return weights, z_l, margin


@jax.jit
def _expert(u, weight, gate, up, down):
    return weight[:, None] * blocks.swiglu(u, gate, up, down)


def experts(u, z, w, cfg, form: Form = RIGHT, margins=None, counts=None):
    """``(the MoE sublayer's output for the normed input u, z_l)``: every
    expert under its gate, one by one; a row whose top-1 is the skip gets
    nothing. ``w["experts"]`` are the experts' ``(gate, up, down)`` in
    order. ``margins``: a list that takes the layer's top-1 margins;
    ``counts``: one that takes the rows each output was chosen by ``[E +
    1]``."""
    dense, z, margin = _route(u, z, w["router"], cfg["rms_norm_eps"], form)
    if margins is not None:
        margins.append(margin)
    if counts is not None:
        counts.append(np.asarray(jnp.sum(dense > 0, axis=0)))
    E = cfg["num_experts"]
    assert len(w["experts"]) == E and dense.shape[1] == E + 1, dense.shape
    out = jnp.zeros_like(u)
    for e, bank in enumerate(w["experts"]):
        weight = dense[:, e]
        if form.skip_to_expert0 and e == 0:
            weight = weight + dense[:, E]
        out = out + _expert(u, weight, *bank)
    return out, z


@functools.partial(jax.jit, static_argnames=("eps",))
def norm(x, weight, eps):
    """RMSNorm with a learned weight."""
    return blocks.rms_norm(x, weight, eps)


@functools.partial(jax.jit, static_argnames=("scaled",))
def merge(r, y, path, scaled: bool = True):
    f = lambda a: a.astype(F32)
    r = r + f(path["b_res"])
    if scaled:
        r = r * f(path["s_res"])
    return r + (y + f(path["b_out"])) * f(path["s_out"])


def layer(r, z, w, cfg, form: Form = RIGHT, starts=None, margins=None,
          counts=None):
    """One layer over one sequence: ``(r, z)``."""
    eps = cfg["rms_norm_eps"]
    y = attention(norm(r, w["attn_norm"], eps), w, cfg, form, starts)
    r = merge(r, y, dict(w["attn_path"]), form.residual_scale)
    y, z = experts(norm(r, w["mlp_norm"], eps), z, w, cfg, form, margins,
                   counts)
    return merge(r, y, dict(w["mlp_path"])), z


def hidden(cfg: dict, weights, tokens, form: Form = RIGHT, starts=None,
           margins=None, counts=None):
    """Final stream ``[len(tokens), hidden]``. The sequence is padded to
    whole query blocks with token 0 at its END: causal rows never read what
    follows them. ``starts``: the positions at which a call of the served
    program began (``Form.tail`` says what their rows read)."""
    cfg = _published(cfg)
    n = len(tokens)
    pad = (-n) % Q_BLOCK if n > Q_BLOCK else 0
    tokens = jnp.concatenate([jnp.asarray(tokens, jnp.int32),
                              jnp.zeros((pad,), jnp.int32)])
    at = np.zeros(n + pad, bool)
    if starts is not None:
        at[np.asarray(starts, np.int64)] = True
    r, z = weights.embed[tokens].astype(F32), None
    for i in range(cfg["num_hidden_layers"]):
        r, z = layer(r, z, weights.layer(i), cfg, form, jnp.asarray(at),
                     margins, counts)
    if margins is not None:     # (the padding's rows are no token's)
        margins[:] = [m[:n] for m in margins]
    return r[:n]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, table, eps):
    return blocks.rms_norm(x, final_norm, eps) @ table.astype(F32).T


def logits(cfg: dict, weights, tokens, form: Form = RIGHT, rows=None,
           margins=None, starts=None, counts=None):
    """Logits of one sequence as a HOST array ``[rows, vocab]`` (``rows``:
    the last so many positions; None: all of them): the head runs a block of
    rows and a slice of the vocabulary at a time. ``weights`` gives ``embed
    [vocab, hidden]`` (the head too: tied), ``final_norm`` and
    ``layer(i)``."""
    with jax.default_matmul_precision("highest"):
        x = hidden(cfg, weights, tokens, form, starts, margins, counts)
        if rows is not None:
            x = x[-rows:]
        vocab = weights.embed.shape[0]
        out = np.empty((x.shape[0], vocab), np.float32)
        for a in range(0, x.shape[0], HEAD_ROWS):
            for c in range(0, vocab, HEAD_COLS):
                out[a:a + HEAD_ROWS, c:c + HEAD_COLS] = np.asarray(_head(
                    x[a:a + HEAD_ROWS], weights.final_norm,
                    weights.embed[c:c + HEAD_COLS], cfg["rms_norm_eps"]))
    return out


def loss(cfg: dict, weights, rows):
    """Mean next-token loss over ``rows`` of ``seq + 1`` tokens each."""
    each = []
    for row in rows:
        row = jnp.asarray(row, jnp.int32)
        each.append(blocks.next_token_loss(
            jnp.asarray(logits(cfg, weights, row[:-1])), row))
    return sum(each) / len(each)


def call_starts(tokens: int, decode: int, chunk: int) -> np.ndarray:
    """The positions at which the served program's calls of one probe begin
    (``families/mixed_program.py``): a chunk every ``chunk`` tokens of the
    first ``tokens - decode``, then every token."""
    cut = tokens - decode
    return np.concatenate([np.arange(0, cut, chunk), np.arange(cut, tokens)])


# --------------------------------------------------------------------------- #
# What `correct` holds the program to BESIDE the served tokens, as Solar-
# Open2's and Mellum 2's cells do and by their functions
# (``reference/solar_open2.py`` ``held``, ``disagreements``,
# ``decode_rows``): every probe's LOGITS - the program's ``apply_paged`` in
# the served precision AS THE WINDOW CALLS IT (``families/mixed_program.py``:
# every call the engine's mixed call over the role's slots, the probe in a
# slot, a tail row and blocks of its own draw, other sequences live in the
# other slots) - against this reference's: each judged row's mean absolute
# difference over the vocabulary, the chunked part's last rows and the rows
# that entered one token a tick apart (the tail comes from the pool at every
# one of those), of each part the LOWER DECILE row and the MEDIAN row, each
# under a limit of its own. The quiet row: where a layer's two best router
# scores lie closer than the bf16 rows resolve, a row's ONE expert is another
# and that row reads loud, while a wrong form moves every row. The
# configuration states the four limits (``roles.serve.held``) with the
# readings they lie between; ``logits_and_margin`` raises beyond any.
# --------------------------------------------------------------------------- #
# ``solar_open2.HELD`` and one reading more: the UPPER QUARTILE of every judged
# row. A row whose top-1 flipped in some layer reads loud, and the right form
# has such rows too (a float32 router on bf16 rows): the quiet row and the
# median are blind to them by design, so a form that only ADDS flips (a router
# in bf16) is told by the share of rows that read loud, which is what the
# upper quartile sees.
HELD = solar_open2.HELD + (
    ("upper_quartile_row_mean_abs_diff", "judged", "the upper quartile of"),)


def disagreements(seen: dict, limits: dict) -> list:
    """Why ``held``'s reading is beyond ``limits``; empty where it is not (a
    reading that is not a number is beyond any limit)."""
    rows = {"chunked": seen["rows"] - seen["decode_rows"],
            "decoded": seen["decode_rows"], "judged": seen["rows"]}
    return [f"the program's {what} logits lie {seen[key]} (mean absolute "
            f"difference, the row's {which} {rows[what]} judged rows) from "
            f"the reference's: the limit is {limits[key]}"
            for key, what, which in HELD if not seen[key] <= limits[key]]


# the harness calls a position's routing "decided" where its margin is over
# ``closed_loop.ROUTER_MARGIN_TOL`` (0.05 of a router logit) and wants a
# quarter of a run's positions decided. A margin here is the least over
# every layer of a top-1 of 17 under a float32 router on bf16 rows, handed
# over times MARGIN_SCALE, as A.X-K1's, Nemotron's, Solar's and Mellum 2's
# are. The readings that chose it (my chip runs, PR 64: 1 150 served
# positions of seven engines): the served token lay more than 0.4 under the
# reference's top at 13 of them, whose margins were 0.001-0.017 - the head
# reads the LAST sublayer's branch, so a top-1 that bf16 rows order the other
# way there is another token altogether. At 2.5 a position is decided from
# 0.020 on: 45 % of them, none of the 13.
MARGIN_SCALE = 2.5


def routing_margin(margins, n: int):
    """The least top-1 margin over the layers at each of the first ``n``
    positions, in the harness's units."""
    return MARGIN_SCALE * functools.reduce(jnp.minimum, margins)[:n]


def logits_and_margin(cfg: dict, weights, tokens):
    """Logits, and each position's routing margin: how far the reference's
    choice of ONE expert (or of the skip) is from another, the least over
    the layers. Mixtral's rule (``closed_loop.judge_probes``): a served
    token is held to the flat tolerance where its routing is decided - a
    row whose top-1 flipped in some layer is another model's there.

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
