"""Plain reference of the Command A+ language model's block
(``CohereLabs/command-a-plus-05-2026`` ``config.json``, ``model_type``
``cohere2_moe``), written from that configuration and the catalog's
description of it, not imported from ``deepspeed_tpu/models``.

One layer ``l`` over ``x [seq, hidden]`` (published keys in brackets):

    h = LayerNorm(x)            mean and variance over hidden, eps
                                [layer_norm_eps], a learned weight, NO bias;
                                ONE norm a layer [use_parallel_block]
    q = h Wq (128 heads of 128), k = h Wk, v = h Wv (8 heads of 128), no
        bias, no QK-norm, scale 128 ** -0.5
    [layer_types][l] == "sliding_attention": rope on q and k over the whole
        head [rotary_pct 1], ADJACENT pairs rotated [rope_gptj], theta
        [rope_theta]; causal AND q_pos - k_pos < [sliding_window]
    "full_attention": NO positional embedding, plain causal
    a = softmax(q k^T * scale) v, heads joined, @ Wo
    s = sigmoid(h Wr) over all the experts [expert_selection_fn], float32
    T = the [num_experts_per_tok] largest; w_e = s_e / sum_T s
        [norm_topk_prob]
    routed = sum_{e in T, e held} w_e E_e(h), E(h) = (silu(h Wg) * (h Wu)) Wd
    shared = 1/n sum_{j<n} S_j(h)   [num_shared_experts],
                                    [shared_expert_combination_strategy]
    x' = x + a + routed + shared

After the last layer ``LayerNorm``, then ``logits = logit_scale * h E^T``
with the embedding table ``E`` [tie_word_embeddings].

Departures and assumptions (each also under the configuration file's
``assumed``): the averaged shared output is ADDED to the routed sum; no
score-correction bias and no route scale (the config has no key for
either); the window counts the current token; text ids only (the vision
tower is not in the catalog's config); ``prefix_dense_*`` name layers that
do not exist here (``first_k_dense_replace`` 0); ties of equal scores go to
the lower expert index (``lax.top_k``).

One chip's share of the expert bank. ``num_experts`` is the experts HELD
(``experts_first`` .. + ``num_experts``, the first 0 where the key is
absent); the router runs over all ``num_local_experts`` - a key this
benchmark ADDS for the router's width, the published file has one key for
both - and normalises over the token's top 8 wherever they live; only held
experts add their term. The shared experts are whole on every chip.

Everything runs in blocks so that a 12 k-token probe fits in the ~2 GB a
serving engine at 82 % of the chip leaves: attention one KV head's group of
query heads and one block of query rows at a time (scores ``[group, rows,
seq]``), a matrix or ONE expert upcast to float32 at a time (a layer's
float32 weights are 4.6 GB), the head a slice of the vocabulary at a time.
The four shared experts are computed one by one.
"""

from __future__ import annotations

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks

F32 = blocks.F32
Q_BLOCK = 256       # query rows of one attention block
HEAD_ROWS = 512     # rows of one block of the head's matmul
HEAD_COLS = 16384   # vocabulary entries of one block of it


@dataclasses.dataclass(frozen=True)
class Form:
    """What the reference computes; the defaults are the model. Each other
    value is one deliberately wrong variant
    (``cohere2_moe_variants``)."""
    window_on_full: bool = False    # the window on the full layers too
    window_on_window: bool = True   # (False: the window layers read it all)
    rope_on_full: bool = False      # rope on the full layers too
    interleaved_rope: bool = True   # (False: half-split, "rotate_half")
    sigmoid_router: bool = True     # (False: softmax over the experts)
    shared_averaged: bool = True    # (False: the shared experts summed)
    parallel_block: bool = True     # (False: attention, then the experts)
    layer_norm: bool = True         # (False: an RMSNorm)


RIGHT = Form()


def held_experts(cfg: dict):
    """(first, count) of the experts this share of the layer holds."""
    return cfg.get("experts_first", 0), cfg["num_experts"]


def layer_types(cfg: dict):
    """The type of each of the ``num_hidden_layers`` that run: the published
    list's first so many (a depth cut keeps whole periods)."""
    return tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])


def norm(x, weight, eps, form: Form = RIGHT):
    """The family's LayerNorm: mean and variance, a weight, no bias."""
    if not form.layer_norm:
        return blocks.rms_norm(x, weight, eps)
    x = x.astype(F32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * weight.astype(F32)


def rope_adjacent(x, positions, theta):
    """Rotary embedding in the GPT-J convention (``rope_gptj``): dimension
    ``2i`` pairs with ``2i + 1``. ``x`` is ``[seq, heads, d]``."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("hd", "theta", "window", "rope",
                                             "interleaved"))
def _attention_group(h, wq, wk, wv, wo, *, hd, theta, window, rope,
                     interleaved):
    """One KV head and its group of query heads over the whole sequence:
    ``wq [hidden, g * hd]``, ``wk`` / ``wv`` ``[hidden, hd]``, ``wo
    [g * hd, hidden]``; returns the group's part of the block's attention
    output ``[seq, hidden]``."""
    s = h.shape[0]
    g = wq.shape[1] // hd
    pos = jnp.arange(s)
    q = (h @ wq.astype(F32)).reshape(s, g, hd)
    k = (h @ wk.astype(F32)).reshape(s, 1, hd)
    v = h @ wv.astype(F32)
    if rope:
        turn = rope_adjacent if interleaved else blocks.rope
        q, k = turn(q, pos, theta), turn(k, pos, theta)
    k = k[:, 0]
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


def attention(h, w, cfg, layer_type: str, form: Form = RIGHT):
    """Grouped-query self-attention of one layer over one whole sequence
    ``h [seq, hidden]`` (``seq`` a multiple of the query block, or shorter
    than one), a KV head's group at a time."""
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    g = nh // nkv
    sliding = layer_type == "sliding_attention"
    windowed = form.window_on_window if sliding else form.window_on_full
    out = jnp.zeros_like(h)
    for n in range(nkv):
        heads = slice(n * g * hd, (n + 1) * g * hd)
        one = slice(n * hd, (n + 1) * hd)
        out = out + _attention_group(
            h, w["q"][:, heads], w["k"][:, one], w["v"][:, one],
            w["o"][heads], hd=hd, theta=float(cfg["rope_theta"]),
            window=cfg["sliding_window"] if windowed else None,
            rope=sliding or form.rope_on_full,
            interleaved=form.interleaved_rope)
    return out


def route(router_logits, cfg, form: Form = RIGHT):
    """``[seq, experts]`` weights: a token's top ``num_experts_per_tok``
    scores, over their sum where ``norm_topk_prob``, zero elsewhere."""
    logits = router_logits.astype(F32)
    s = jax.nn.sigmoid(logits) if form.sigmoid_router \
        else jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, s.shape[-1], dtype=F32)
                   * top[..., None], axis=1)


@functools.partial(jax.jit, static_argnames=("eps", "form"))
def _norm(x, weight, eps, form):
    return norm(x, weight, eps, form)


@functools.partial(jax.jit, static_argnames=("cfg", "form"))
def _route(h, router, cfg, form):
    return route(h @ router.astype(F32), dict(cfg), form)


@jax.jit
def _expert(h, weight, gate, up, down):
    return weight[:, None] * blocks.swiglu(h, gate, up, down)


def experts(h, w, cfg, frozen, form: Form = RIGHT):
    """The expert layer's output for the normed input ``h``: the HELD routed
    experts under their gates and the shared experts, one by one.
    ``w["experts"]`` are the held experts' matrices in order from
    ``experts_first``, ``w["shared"]`` the shared experts'."""
    dense = _route(h, w["router"], frozen, form)
    first, count = held_experts(cfg)
    assert len(w["experts"]) == count, (len(w["experts"]), count)
    out = jnp.zeros_like(h)
    for e, bank in enumerate(w["experts"]):
        out = out + _expert(h, dense[:, first + e], *bank)
    share = jnp.full((h.shape[0],), 1.0 / len(w["shared"])
                     if form.shared_averaged else 1.0, F32)
    for bank in w["shared"]:
        out = out + _expert(h, share, *bank)
    return out


def layer(x, w, cfg, layer_type: str, form: Form = RIGHT):
    """One block over one sequence."""
    eps = cfg["layer_norm_eps"]
    frozen = _freeze(cfg)
    h = _norm(x, w["norm"], eps, form)
    a = attention(h, w, cfg, layer_type, form)
    if not form.parallel_block:     # the experts read what attention left
        h = _norm(x + a, w["norm"], eps, form)
    return x + a + experts(h, w, cfg, frozen, form)


def _freeze(cfg: dict):
    """The configuration's scalars as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str))))


def _published(cfg: dict) -> dict:
    for key in ("attention_bias", "use_qk_norm", "first_k_dense_replace"):
        if cfg.get(key):
            raise ValueError(f"the cohere2_moe reference has no {key}")
    if not (cfg["use_parallel_block"] and cfg["tie_word_embeddings"]
            and cfg["use_gated_activation"] and cfg["hidden_act"] == "silu"
            and cfg["expert_selection_fn"] == "sigmoid"
            and cfg["shared_expert_combination_strategy"] == "average"
            and cfg["position_embedding_type"] == "rope_gptj"
            and cfg["rotary_pct"] == 1):
        raise ValueError("the configuration is not one the cohere2_moe "
                         "reference computes")
    return cfg


def hidden(cfg: dict, weights, tokens, form: Form = RIGHT):
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
        x = layer(x, weights.layer(i), cfg, layer_type, form)
    return x[:n]


@functools.partial(jax.jit, static_argnames=("eps", "scale", "form"))
def _head(x, final_norm, table, eps, scale, form):
    """Logits over one slice ``table [entries, hidden]`` of the tied
    embedding table."""
    return scale * (norm(x, final_norm, eps, form) @ table.astype(F32).T)


def logits(cfg: dict, weights, tokens, form: Form = RIGHT, rows=None):
    """Logits of one sequence as a HOST array ``[rows, vocab]`` (``rows``:
    the last so many positions; None: all of them - 13 GB for 12 k tokens
    of this vocabulary, which a host holds and a chip beside an engine does
    not: the head runs a block of rows and a slice of the vocabulary at a
    time)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(cfg, weights, tokens, form)
        if rows is not None:
            x = x[-rows:]
        vocab = weights.embed.shape[0]
        out = np.empty((x.shape[0], vocab), np.float32)
        for a in range(0, x.shape[0], HEAD_ROWS):
            for c in range(0, vocab, HEAD_COLS):
                out[a:a + HEAD_ROWS, c:c + HEAD_COLS] = np.asarray(_head(
                    x[a:a + HEAD_ROWS], weights.final_norm,
                    weights.embed[c:c + HEAD_COLS], cfg["layer_norm_eps"],
                    float(cfg["logit_scale"]), form))
    return out


# --------------------------------------------------------------------------- #
# What `correct` holds the program to BESIDE the served tokens. A served token
# is the top of the served logits, and the harness's flat rule allows it 0.4
# under the reference's top (``closed_loop.SERVED_TOKEN_GAP_TOL``): a window
# on the wrong kind of layer, a rope in the wrong convention or a router with
# the wrong score moves logits by less than that at these widths, so the
# token check alone cannot see them. One reading of every probe can
# (``held``), the configuration states its limit (``roles.serve.held``; the
# rehearsal's widths have their own), and ``logits_and_margin`` raises where
# it is beyond it:
#
# logits_mean_abs_diff: between the program's logits - ``apply_paged`` in the
#   served precision over the cell's chunks and blocks, its window kind's
#   blocks given back by a ``StateManager`` of its own as the engine's gives
#   them back (``families/cohere2_moe.py`` ``Program``) - and this
#   reference's: each judged row's mean absolute difference over the
#   vocabulary, and of the rows the MEDIAN. A wrong form is wrong in every
#   row; what bf16 does to the right form is not: where the 8th and the 9th
#   router score lie closer than bf16 resolves, a row's eight experts differ
#   by one and that row alone reads ten times its neighbours (one or two of a
#   probe's nine rows, about every second probe). The mean over the rows
#   would need a limit above what the quietest wrong forms read.
#
# The readings the limit lies between: PERF.md section 6, PR 42.
# --------------------------------------------------------------------------- #
HELD_DECODE = 8     # of a probe's tokens, the last so many enter one at a time


class Disagreement(RuntimeError):
    """The program's logits lie beyond the limit from this reference's on a
    probe. Raised, as the harness raises for a probe whose streamed tokens
    are not ``finish()``'s: ``closed_loop`` judges served tokens alone and
    has no place for another reason (PERF.md section 7)."""


def held(got, want) -> dict:
    """The reading of one probe: ``got`` the program's logits
    (``weights.program.logits``), ``want`` a reference's at the same rows.
    ``logits_mean_abs_diff`` is the MEDIAN row's (above); every row's and the
    mean over all of them are beside it."""
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    rows = diff.mean(axis=-1)
    return {"logits_mean_abs_diff": float(np.median(rows)),
            "rows_mean_abs_diff": [round(float(r), 5) for r in rows],
            "all_rows_mean_abs_diff": float(diff.mean()),
            "logits_max_abs_diff": float(diff.max())}


def disagreements(seen: dict, limits: dict) -> list:
    """Why ``held``'s reading is beyond ``limits``; empty where it is not
    (a reading that is not a number is beyond any limit)."""
    if seen["logits_mean_abs_diff"] <= limits["logits_mean_abs_diff"]:
        return []
    return [f"the program's logits lie {seen['logits_mean_abs_diff']} (mean "
            f"absolute difference, the median row's) from the reference's: "
            f"the limit is {limits['logits_mean_abs_diff']}"]


def logits_and_margin(cfg: dict, weights, tokens):
    """Logits, and NO routing margin - OLMoE's flat rule
    (``reference/olmoe.py``), for OLMoE's reason at the top 8 of 128: the gap
    between the 8th and the 9th router score is under the margin tolerance
    about every second time in every layer, so no run could reach the
    decided share; and a flip there exchanges one expert of eight whose
    normalised weight is near the smallest. Every served token is held to
    the flat tolerance with none allowed beyond.

    Where the weights come with their program (the family's ``Weights`` do)
    the probe is ALSO held to the configuration's limit above: the reading
    is printed as a line of its own, and one beyond its limit raises
    ``Disagreement``."""
    out = logits(cfg, weights, tokens)
    program = getattr(weights, "program", None)
    if program is not None:
        decode = min(HELD_DECODE, len(tokens) - 1)
        seen = held(program.logits(cfg, tokens, decode),
                    out[-(decode + 1):])
        limits = {k: v for k, v in program.limits.items() if k != "why"}
        why = disagreements(seen, limits)
        print(json.dumps({"phase": "held", "tokens": len(tokens), **seen,
                          "limits": limits, "why_not": why}), flush=True)
        if why:
            raise Disagreement(f"a probe of {len(tokens)} tokens: "
                               + "; ".join(why))
    return out, jnp.full(out.shape[0], jnp.inf)
