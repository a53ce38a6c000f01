"""Plain reference of the Brumby decoder (``manifestai/Brumby-14B-Base``
``config.json``, ``model_type`` "brumby"): Qwen3's dense decoder with every
attention layer replaced by POWER RETENTION (Manifest AI, "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239; the ``retention`` package's
``power_retention``), written from the equations and not imported from
``deepspeed_tpu``. Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no kernel, no state pool, no
batching: one sequence, one layer at a time.

The stack (published keys in brackets; ``R`` is RMSNorm with a learned
weight and eps [rms_norm_eps]; no biases):

    x = E[token]
    for each of [num_hidden_layers] layers:
        u = R(x)
        key-value head j of [num_key_value_heads], each query head i of its
        [num_attention_heads] / [num_key_value_heads], d = [head_dim]:
            q_t = rope(R_head(W_q^i u_t))    k_t = rope(R_head(W_k^j u_t))
            v_t = W_v^j u_t                  log g_t = logsigmoid(w_g^j . u_t)
            a_ts = exp(sum_{r=s+1..t} log g_r) (q_t . k_s)^p     s <= t, p = 2
            o_t  = sum_s a_ts v_s / (sum_s a_ts + eps)
        x = x + W_o concat_i(o^i)
        x = x + W_down(silu(W_gate n) * W_up n),   n = R(x)
    logits = R(x) W_head                       (untied)

``rope`` is the half-split rotary embedding at [rope_theta]; ``R_head`` is
Qwen3's RMSNorm over each head's ``d`` numbers with a learned ``[d]``
weight. The QUADRATIC form above is what :func:`logits` computes, one
key-value head and one block of query rows at a time so that a 2 k-token
probe fits beside a serving engine that holds 12 of 16 GB.
:func:`recurrent_retention` is its cross-check: the same layer as a
recurrence over the fixed state ``S_t = g_t S_{t-1} + v_t phi(k_t)^T``,
``z_t = g_t z_{t-1} + phi(k_t)``, ``o_t = S_t phi(q_t) / (z_t . phi(q_t) +
eps)`` with ``phi`` the SMALLEST map for which ``phi(a) . phi(b) = (a .
b)^2``: ``a_m^2`` and ``sqrt(2) a_m a_n`` for ``m < n``, ``d (d + 1) / 2``
entries (the program's layout is another, 8704 where this has 8256; the
outputs are what is compared).

Departures from the paper and package, each because the published
``config.json`` carries none of the retention's own keys (the
configuration's ``assumed`` says the same): degree 2 (the release's); the
gate ONE scalar a key-value head a token from a bias-less ``[hidden, kv
heads]`` projection through ``logsigmoid``; the normaliser (the package's
``sum_of_keys``) with ``eps`` 1e-6 added to it; no short convolution and no
output gate; every sequence lives in its state from its first token (the
package's switch-over - keys and values kept and attended quadratically
until a context passes a length - computes the same numbers and is how it is
computed, not what). A scale on ``q . k`` cancels in the quotient and is not
applied.
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

F32 = jnp.float32
EPS = 1e-6
QUERY_ROWS = 512       # query rows a block of the quadratic form
HEAD_ROWS, HEAD_COLS = 1024, 16384     # the head: rows x vocabulary a block


@dataclasses.dataclass(frozen=True)
class Form:
    """What a retention layer computes; the defaults are the right form and
    every other value is one of ``brumby_variants``' wrong ones."""
    degree: int = 2                 # p
    gate: bool = True               # False: g = 1, nothing is forgotten
    normaliser: bool = True         # False: the sum without its quotient
    gate_per_head: bool = True      # False: head 0's gate for every head
    softmax: bool = False           # True: Qwen3's own softmax attention
    qk_norm: bool = True            # False: no per-head RMSNorm of q and k
    rope: bool = True               # False: no rotary embedding
    state_dtype: str = "float32"    # bfloat16: the recurrence, its state
    #                                 rounded after every token


RIGHT = Form()


def _published(cfg: dict) -> dict:
    return {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, str))}


def _freeze(cfg: dict):
    return tuple(sorted(_published(cfg).items()))


def phi(a):
    """``[.., d] -> [.., d (d + 1) / 2]``: ``a_m^2``, then ``sqrt(2) a_m
    a_n`` for ``m < n``."""
    d = a.shape[-1]
    m, n = np.triu_indices(d, 1)
    return jnp.concatenate([a * a, np.sqrt(2.0) * a[..., m] * a[..., n]], -1)


def _projections(y, w, cfg, form):
    """``(q [s, nh, d], k [s, nkv, d], v [s, nkv, d], log g [s, nkv])``."""
    s = y.shape[0]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    pos = jnp.arange(s)
    q = (y @ w["q"].astype(F32)).reshape(s, nh, d)
    k = (y @ w["k"].astype(F32)).reshape(s, nkv, d)
    v = (y @ w["v"].astype(F32)).reshape(s, nkv, d)
    if form.qk_norm:
        q = blocks.rms_norm(q, w["q_norm"], eps)
        k = blocks.rms_norm(k, w["k_norm"], eps)
    if form.rope:
        q = blocks.rope(q, pos, cfg["rope_theta"])
        k = blocks.rope(k, pos, cfg["rope_theta"])
    log_g = jax.nn.log_sigmoid(y @ w["g"].astype(F32))          # [s, nkv]
    if not form.gate_per_head:
        log_g = jnp.broadcast_to(log_g[:, :1], log_g.shape)
    if not form.gate:
        log_g = jnp.zeros_like(log_g)
    return q, k, v, log_g


@functools.partial(jax.jit, static_argnames=("form",))
def _quadratic_rows(q, k, v, cum_q, cum_k, first, form):
    """One key-value head, one block of query rows ``q [rows, g, d]`` at
    positions ``first + arange(rows)`` against every key ``k [s, d]``."""
    rows, s = q.shape[0], k.shape[0]
    seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(s)[None, :]
    qk = jnp.einsum("tid,sd->its", q, k)
    if form.softmax:
        p = jax.nn.softmax(jnp.where(seen[None], qk / jnp.sqrt(
            F32(q.shape[-1])), -jnp.inf), axis=-1)
        return jnp.einsum("its,sd->tid", p, v)
    decay = jnp.exp(jnp.where(seen, cum_q[:, None] - cum_k[None, :],
                              -jnp.inf))
    a = decay[None] * qk ** form.degree
    num = jnp.einsum("its,sd->tid", a, v)
    if not form.normaliser:
        return num
    return num / (jnp.sum(a, axis=-1).T[..., None] + EPS)


def quadratic_retention(q, k, v, log_g, form: Form = RIGHT):
    """The equations: ``o [s, nh, d]``. One key-value head and
    ``QUERY_ROWS`` query rows at a time."""
    s, nh, d = q.shape
    nkv = k.shape[1]
    cum = jnp.cumsum(log_g, axis=0)                             # [s, nkv]
    heads = []
    for j in range(nkv):
        mine = q[:, j * (nh // nkv):(j + 1) * (nh // nkv)]
        heads.append(jnp.concatenate([
            _quadratic_rows(mine[a:a + QUERY_ROWS], k[:, j], v[:, j],
                            cum[a:a + QUERY_ROWS, j], cum[:, j], a, form)
            for a in range(0, s, QUERY_ROWS)]))
    return jnp.concatenate(heads, axis=1)


@functools.partial(jax.jit, static_argnames=("state_dtype",))
def recurrent_retention(q, k, v, log_g, state_dtype: str = "float32"):
    """The same layer a token at a time over the fixed state (``lax.scan``):
    the cross-check of :func:`quadratic_retention`, and - with
    ``state_dtype`` bfloat16, ``S`` and ``z`` rounded after every token -
    the wrong form a narrow state is."""
    s, nh, d = q.shape
    nkv = k.shape[1]
    keep = lambda x: x.astype(jnp.dtype(state_dtype)).astype(F32)

    def token(carry, t):
        S, z = carry
        q_t, k_t, v_t, g_t = t
        fk = phi(k_t)                                           # [nkv, D]
        g = jnp.exp(g_t)
        S = keep(g[:, None, None] * S + fk[:, :, None] * v_t[:, None, :])
        z = keep(g[:, None] * z + fk)
        fq = phi(q_t.reshape(nkv, nh // nkv, d))                # [nkv, G, D]
        num = jnp.einsum("jir,jrd->jid", fq, S)
        den = jnp.einsum("jir,jr->ji", fq, z)[..., None] + EPS
        return (S, z), (num / den).reshape(nh, d)

    D = d * (d + 1) // 2
    _, o = jax.lax.scan(token, (jnp.zeros((nkv, D, d), F32),
                                jnp.zeros((nkv, D), F32)),
                        (q, k, v, log_g))
    return o


@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _mixer_in(x, w, cfg, form):
    cfg = dict(cfg)
    return _projections(blocks.rms_norm(x, w["attn_norm"],
                                        cfg["rms_norm_eps"]), w, cfg, form)


@functools.partial(jax.jit, static_argnames=("eps",))
def _mixer_out(x, o, wo, eps):
    del eps
    return x + o.reshape(o.shape[0], -1) @ wo.astype(F32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ffn(x, w, eps):
    y = blocks.rms_norm(x, w["ffn_norm"], eps)
    return x + blocks.swiglu(y, w["gate"], w["up"], w["down"])


def layer(x, w, cfg: dict, form: Form = RIGHT):
    """One layer over ``x [seq, hidden]``; ``w`` the layer's matrices
    (``q``, ``k``, ``v``, ``o``, ``g``, ``q_norm``, ``k_norm``,
    ``attn_norm``, ``ffn_norm``, ``gate``, ``up``, ``down``), in whatever
    type the program serves them in, each widened by the step that reads
    it."""
    q, k, v, log_g = _mixer_in(x, w, _freeze(cfg), form)
    if form.state_dtype != "float32":
        o = recurrent_retention(q, k, v, log_g, form.state_dtype)
    else:
        o = quadratic_retention(q, k, v, log_g, form)
    x = _mixer_out(x, o, w["o"], cfg["rms_norm_eps"])
    return _ffn(x, {n: w[n] for n in ("ffn_norm", "gate", "up", "down")},
                cfg["rms_norm_eps"])


def hidden(cfg: dict, weights, tokens, form: Form = RIGHT):
    x = weights.embed[jnp.asarray(tokens, jnp.int32)].astype(F32)
    for i in range(cfg["num_hidden_layers"]):
        x = layer(x, weights.layer(i), cfg, form)
    return x


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, eps):
    return blocks.rms_norm(x, norm, eps) @ head.astype(F32)


def logits(cfg: dict, weights, tokens, form: Form = RIGHT, rows=None):
    """Logits of one sequence as a HOST array ``[rows, vocab]`` (``rows``:
    the last so many positions; None: all of them): the head runs a block of
    rows and a slice of the vocabulary at a time. ``weights`` gives
    ``embed``, ``final_norm``, ``head [hidden, vocab]`` and ``layer(i)``."""
    with jax.default_matmul_precision("highest"):
        x = hidden(cfg, weights, tokens, form)
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
# Nemotron-3-Nano cell's reference does and for its reason: a served token
# is the top of the served logits and the harness's flat rule allows it 0.4
# under the reference's top, which one gate for every head or a missing
# rotary embedding need not move it by. ``held`` reads every probe - the
# program's ``apply_paged`` logits in the served precision, prefill in the
# cell's chunks then decode through the state (``families/brumby.py``
# ``Program``) - against this reference's: each judged row's mean absolute
# difference over the vocabulary, in TWO readings with a limit each, because
# the two halves of a probe run different code: ``logits_mean_abs_diff`` over
# the chunked part's last rows (``retention_chunk``) and
# ``decode_logits_mean_abs_diff`` over the rows that entered one token a call
# (``retention_decode_update`` on the state where it lies). One reading over
# both would let the quiet prefill rows carry a fault that lives in the
# single-token call alone (``tools/brumby_check.py`` plants one there). Of
# each part's rows the MEDIAN: this model is dense and makes no discrete
# choice, so no row is an outlier by a flipped expert and a wrong form moves
# every row of the part it lives in. The configuration states both limits
# (``roles.serve.held``); ``logits_and_margin`` raises beyond either. The
# readings they lie between: PERF.md section 6, PR 55.
# --------------------------------------------------------------------------- #
DECODE_ROWS = 96    # of a probe's tokens, the last so many enter one at a
#                     time (as many as the longest probe serves), at most
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
    return {"logits_mean_abs_diff": float(np.median(rows[:-decode])),
            "decode_logits_mean_abs_diff": float(np.median(rows[-decode:])),
            "rows": len(rows), "decode_rows": decode,
            "largest_row_mean_abs_diff": float(rows.max()),
            "all_rows_mean_abs_diff": float(diff.mean()),
            "logits_max_abs_diff": float(diff.max())}


def disagreements(seen: dict, limits: dict) -> list:
    """Why ``held``'s reading is beyond ``limits``; empty where it is not
    (a reading that is not a number is beyond any limit)."""
    parts = (("logits_mean_abs_diff", seen["rows"] - seen["decode_rows"],
              "chunked"),
             ("decode_logits_mean_abs_diff", seen["decode_rows"], "decoded"))
    return [f"the program's {what} logits lie {seen[key]} (mean absolute "
            f"difference, the median of {n} judged rows) from the "
            f"reference's: the limit is {limits[key]}"
            for key, n, what in parts if not seen[key] <= limits[key]]


def logits_and_margin(cfg: dict, weights, tokens):
    """Logits, and each position's distance from another discrete choice: a
    dense model makes none, so infinitely far.

    Where the weights come with their program (the family's ``Weights`` do)
    the probe is ALSO held to the configuration's limits above: the reading
    is printed as a line of its own, and one beyond its limit raises
    ``Disagreement``."""
    out = logits(cfg, weights, tokens)
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
    return out, jnp.full(out.shape[0], jnp.inf)
