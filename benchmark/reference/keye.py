"""Plain reference of the Keye-VL-2.0-30B-A3B language model's block
(``Kwai-Keye/Keye-VL-2.0-30B-A3B`` ``config.json``, ``model_type``
``KeyeVL2``), written from that configuration and from the description of
DeepSeek-Sparse-Attention's indexer (the "lightning indexer" of DeepSeek-V3.2:
a few small index heads score every cached token, and attention reads the
``topk`` best alone), here on grouped-query attention.

One pre-norm residual block, ``x`` the normed input of token ``t`` and
``s <= t`` a cached token:

    q_t, k_s, v_s   GQA projections (32 query / 4 KV heads of head_dim 128,
                    which is NOT hidden / heads), an RMSNorm over each head
                    of q and k, rope at theta 1e7
    qI[t, j] = rope(WqI x_t)[j]  in R^64, j = 1..16
    kI[s]    = rope(WkI x_s)     in R^64: ONE index key head
    w[t, j]  = (Ww x_t)[j]
    I[t, s]  = sum_j w[t, j] . relu(qI[t, j] . kI[s])         the index score
    S_t      = the topk tokens s <= t of largest I[t, s]: all of them while
               t < topk; equal scores: the lower s first
    attn_t   = Wo . concat_h softmax_{s in S_t}(q[t, h] . k[s, g(h)]
                                                / sqrt(128)) v[s, g(h)]
    p = softmax(RMSNorm(h) Wr) over num_local_experts
    out = h + sum_{i in top8(p)} p_i / (sum_{top8} p) . E_i(y)

Departures and assumptions (each also under the configuration file's
``assumed``): text tokens only - the vision tower is not in the catalog's
``config``, and for text ids the three M-RoPE sections carry one position and
reduce to plain rope; the per-head QK-norm is the family's convention (the
config is silent); the indexer reads the attention block's normed input,
ropes all 64 index dims at the model's theta, puts no norm on ``kI``;
``q_chunk_size`` / ``kv_chunk_size`` are the tiling of the score computation
and change neither ``I`` nor ``S_t``; positive scalings of ``w`` (a softmax
scale, ``1/sqrt(heads)``) do not move ``S_t`` and are left out; -0.0 and 0.0
are one score.

One chip's share of the expert bank. ``num_experts`` is the experts HELD
(``experts_first`` .. + ``num_experts``, the first 0 where the key is
absent); the router runs over all ``num_local_experts`` and normalises over
the token's top 8 wherever they live; only held experts add their term. What
the absent experts would add is left out, here as in the program, and that
partial sum goes on to the next layer.

Attention runs in query blocks, so that 16 k tokens fit beside a serving
engine: each block's scores are ``[heads, block, seq]``, never ``[seq, seq]``
a head at once for the whole sequence.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks

F32 = blocks.F32
Q_BLOCK = 256       # query rows of one attention block
LOGIT_ROWS = 512    # rows of one block of the head's matmul


def held_experts(cfg: dict):
    """(first, count) of the experts this share of the layer holds."""
    return cfg.get("experts_first", 0), cfg["num_experts"]


def index_vectors(y, w, cfg, rope_index=True):
    """The indexer's queries ``[seq, H, d]``, key ``[seq, d]`` and head
    weights ``[seq, H]`` from the block's normed input."""
    s = y.shape[0]
    sa = cfg["sa_config"]
    heads, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    pos = jnp.arange(s)
    q_idx = (y @ w["q_idx"].astype(F32)).reshape(s, heads, d)
    k_idx = (y @ w["k_idx"].astype(F32)).reshape(s, 1, d)
    if rope_index:
        q_idx = blocks.rope(q_idx, pos, cfg["rope_theta"])
        k_idx = blocks.rope(k_idx, pos, cfg["rope_theta"])
    return q_idx, k_idx[:, 0], y @ w["w_idx"].astype(F32)


def index_scores(q_idx, k_idx, w_idx):
    """``I [rows, seq]`` of a block of rows against every key."""
    s = jnp.einsum("qjd,kd->qjk", q_idx, k_idx)
    s = jnp.sum(jax.nn.relu(s) * w_idx[:, :, None], axis=1)
    return jnp.where(s == 0.0, 0.0, s)        # -0.0 and 0.0: one score


def learned_selection(scores, q_pos, k_pos, topk):
    """``[rows, seq]`` bool: the ``topk`` causal keys of largest score of
    each row (``lax.top_k``: of equal scores the lower position first)."""
    causal = k_pos[None, :] <= q_pos[:, None]
    if scores.shape[1] <= topk:
        return causal
    _, at = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    chosen = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], at].set(True)
    return jnp.logical_and(chosen, causal)


def attention(x, w, cfg, select=learned_selection, rope_index=True):
    """Sparse causal self-attention over one whole sequence ``x [seq,
    hidden]`` (``seq`` a multiple of the query block, or shorter than one).
    ``select`` and ``rope_index`` are hooks for the deliberately wrong
    variants (``keye_variants``)."""
    s = x.shape[0]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, pos = cfg["rms_norm_eps"], jnp.arange(s)
    topk = cfg["sa_config"]["topk"]
    q = blocks.rms_norm((x @ w["q"].astype(F32)).reshape(s, nh, hd),
                        w["q_norm"], eps)
    k = blocks.rms_norm((x @ w["k"].astype(F32)).reshape(s, nkv, hd),
                        w["k_norm"], eps)
    q = blocks.rope(q, pos, cfg["rope_theta"])
    k = blocks.rope(k, pos, cfg["rope_theta"])
    v = (x @ w["v"].astype(F32)).reshape(s, nkv, hd)
    q_idx, k_idx, w_idx = index_vectors(x, w, cfg, rope_index)
    g = nh // nkv

    def block(start):
        rows = min(Q_BLOCK, s)
        at = lambda a: jax.lax.dynamic_slice_in_dim(a, start, rows)
        q_pos = start + jnp.arange(rows)
        keep = select(index_scores(at(q_idx), k_idx, at(w_idx)), q_pos, pos,
                      topk)
        qb = at(q).reshape(rows, nkv, g, hd)
        scores = jnp.einsum("qngd,knd->ngqk", qb, k) / jnp.sqrt(F32(hd))
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        mix = jnp.einsum("ngqk,knd->qngd", jax.nn.softmax(scores, axis=-1), v)
        return mix.reshape(rows, nh * hd)

    starts = jnp.arange(0, s, min(Q_BLOCK, s))
    mix = jax.lax.map(block, starts).reshape(s, nh * hd)
    return mix @ w["o"].astype(F32)


def route(router_logits, cfg):
    """``[seq, num_local_experts]`` weights: a token's top
    ``num_experts_per_tok`` softmax probabilities, normalised over those
    eight where ``norm_topk_prob`` (Keye's does), zero elsewhere."""
    p = jax.nn.softmax(router_logits.astype(F32), axis=-1)
    top, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return jnp.sum(jax.nn.one_hot(idx, p.shape[-1], dtype=F32)
                   * top[..., None], axis=1)


@functools.partial(jax.jit, static_argnames=("cfg", "select", "rope_index"))
def _attention_and_route(x, w, cfg, select, rope_index):
    cfg = _thaw(cfg)
    eps = cfg["rms_norm_eps"]
    x = x + attention(blocks.rms_norm(x, w["attn_norm"], eps), w, cfg,
                      select, rope_index)
    y = blocks.rms_norm(x, w["ffn_norm"], eps)
    return x, y, route(y @ w["router"].astype(F32), cfg)


@jax.jit
def _expert(y, weight, gate, up, down):
    return weight[:, None] * blocks.swiglu(y, gate, up, down)


def layer(x, w, cfg, select=learned_selection, rope_index=True):
    """One block over one sequence. ``w["experts"]`` are the HELD experts'
    matrices, in order from ``experts_first``."""
    x, y, dense = _attention_and_route(
        x, {k: v for k, v in w.items() if k != "experts"}, cfg, select,
        rope_index)
    first, count = held_experts(_thaw(cfg))
    assert len(w["experts"]) == count, (len(w["experts"]), count)
    for e, (gate, up, down) in enumerate(w["experts"]):
        x = x + _expert(y, dense[:, first + e], gate, up, down)
    return x


def _freeze(cfg: dict):
    """The configuration as a hashable static argument, its ``sa_config``
    included."""
    flat = {k: v for k, v in cfg.items()
            if isinstance(v, (int, float, bool, str))}
    flat["sa_config"] = tuple(sorted(cfg["sa_config"].items()))
    return tuple(sorted(flat.items()))


def _thaw(frozen) -> dict:
    cfg = dict(frozen)
    cfg["sa_config"] = dict(cfg["sa_config"])
    return cfg


def _published(cfg: dict) -> dict:
    for key in ("attention_bias", "use_sliding_window", "sliding_window",
                "tie_word_embeddings", "mlp_only_layers"):
        if cfg.get(key):
            raise ValueError(f"the Keye reference has no {key}")
    if cfg["decoder_sparse_step"] != 1 or cfg["hidden_act"] != "silu" \
            or cfg["sa_config"]["indexer_num_kv_heads"] != 1:
        raise ValueError("the configuration is not one the Keye reference "
                         "computes")
    return cfg


def hidden(cfg: dict, weights, tokens, layer_fn=layer, layers=None,
           keep=None):
    """Final hidden states ``[len(tokens), hidden]`` (of the first ``layers``
    layers, if given). The sequence is padded to whole query blocks with
    token 0 at its END: causal rows never read what follows them. ``keep``
    ``{layer: None}`` is filled with those layers' INPUTS."""
    cfg = _published(cfg)
    n = len(tokens)
    pad = (-n) % Q_BLOCK if n > Q_BLOCK else 0
    tokens = jnp.concatenate([jnp.asarray(tokens, jnp.int32),
                              jnp.zeros((pad,), jnp.int32)])
    frozen = _freeze(cfg)
    x = weights.embed[tokens].astype(F32)
    count = cfg["num_hidden_layers"] if layers is None else layers
    for i in range(count):
        if keep is not None and i in keep:
            keep[i] = x[:n]
        x = layer_fn(x, weights.layer(i), frozen)
    return x[:n]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm, head, eps):
    return blocks.rms_norm(x, norm, eps) @ head.astype(F32)


def logits(cfg: dict, weights, tokens, layer_fn=layer, rows=None, keep=None):
    """Logits of one sequence as a HOST array ``[rows, vocab]`` (``rows``:
    the last so many positions; None: all of them, ``[seq, vocab]`` - 10 GB
    for 16 k tokens of this vocabulary, which a host holds and a chip beside
    an engine does not: the head runs a block of rows at a time)."""
    with jax.default_matmul_precision("highest"):
        x = hidden(cfg, weights, tokens, layer_fn, keep=keep)
        if rows is not None:
            x = x[-rows:]
        out = np.empty((x.shape[0], weights.head.shape[1]), np.float32)
        for a in range(0, x.shape[0], LOGIT_ROWS):
            out[a:a + LOGIT_ROWS] = np.asarray(_head(
                x[a:a + LOGIT_ROWS], weights.final_norm, weights.head,
                cfg["rms_norm_eps"]))
    return out


# --------------------------------------------------------------------------- #
# What `correct` holds the program to BESIDE the served tokens. A served token
# is the top of the served logits, and the harness's flat rule allows it 0.4
# under the reference's top (``closed_loop.SERVED_TOKEN_GAP_TOL``): at unit
# QK gain no wrong selection moves a logit that far (dense attention in the
# place of the learned 2048 moves them by 0.07-0.17 in the mean), so the
# token check cannot see WHICH tokens attention read. Two readings of every
# probe can (``held``), the configuration states a limit for each
# (``roles.serve.held``; the rehearsal's widths have their own), and
# ``logits_and_margin`` raises where one is beyond it:
#
# logits_mean_abs_diff: between the program's logits (``apply_paged``, bf16,
#   the cell's chunks and blocks) and this reference's, over the rows the
#   probe is judged at.
# selected_share: of this reference's ``S_t`` (float32) the share that the
#   program's bf16 indexer and its selection also take from the same normed
#   input, the mean over the sequence's last ``HELD_ROWS`` rows, at the first
#   and the last layer; and each row takes exactly as many tokens as the
#   reference.
#
# The readings the limits lie between: PERF.md section 6, PR 38.
# --------------------------------------------------------------------------- #
HELD_DECODE = 8     # of a probe's tokens, the last so many enter one at a time
HELD_ROWS = 16      # rows of the sequence's end whose selected sets are held


class Disagreement(RuntimeError):
    """The program's logits or its selected sets lie beyond a limit from this
    reference's on a probe. Raised, as the harness raises for a probe whose
    streamed tokens are not ``finish()``'s: ``closed_loop`` judges served
    tokens alone and has no place for another reason (PERF.md section 7)."""


def selected_sets(cfg: dict, w, x, rows: int, select=learned_selection,
                  rope_index=True):
    """The normed input ``y [seq, hidden]`` of one block's attention from the
    block's input ``x``, and ``[rows, seq]`` bool: what the last ``rows``
    rows may read."""
    n = x.shape[0]
    with jax.default_matmul_precision("highest"):
        y = blocks.rms_norm(x, w["attn_norm"], cfg["rms_norm_eps"])
        q_idx, k_idx, w_idx = index_vectors(y, w, cfg, rope_index)
        last = slice(n - rows, n)
        chosen = select(index_scores(q_idx[last], k_idx, w_idx[last]),
                        jnp.arange(n - rows, n), jnp.arange(n),
                        cfg["sa_config"]["topk"])
    return y, np.asarray(chosen)


def held(cfg: dict, weights, got, want, inputs, form=None,
         keys=None) -> dict:
    """The two readings of one probe. ``got`` are the program's logits
    (``weights.program.logits``) and ``want`` a reference's at the same rows;
    ``inputs {layer: x}`` that reference's inputs of the layers whose
    selected sets are held (``hidden(keep=)``). ``form`` ``(cfg, select,
    rope_index)`` is the reference's form where it is a deliberately wrong
    one (``keye_variants.form``); the program is always ``cfg``'s, with its
    index keys rounded to the type ``keys`` names where one is given."""
    ref_cfg, select, rope_index = form or (cfg, learned_selection, True)
    diff = np.abs(np.asarray(got) - np.asarray(want))
    seen = {"logits_mean_abs_diff": float(diff.mean()),
            "logits_max_abs_diff": float(diff.max()), "selected": []}
    for i, x in sorted(inputs.items()):
        rows = min(HELD_ROWS, x.shape[0])
        y, chosen = selected_sets(ref_cfg, weights.layer(i), x, rows, select,
                                  rope_index)
        mine = weights.program.selected(cfg, i, y, rows, keys)
        share = (mine & chosen).sum(1) / chosen.sum(1)
        seen["selected"].append({
            "layer": i, "share": float(share.mean()),
            "least_share": float(share.min()),
            "counts_equal": bool((mine.sum(1) == chosen.sum(1)).all())})
    return seen


def disagreements(seen: dict, limits: dict) -> list:
    """Why ``held``'s readings are beyond ``limits``; empty where none is."""
    why = []
    if not seen["logits_mean_abs_diff"] <= limits["logits_mean_abs_diff"]:
        why.append(f"the program's logits lie {seen['logits_mean_abs_diff']} "
                   f"(mean absolute difference) from the reference's: the "
                   f"limit is {limits['logits_mean_abs_diff']}")
    for s in seen["selected"]:
        if not s["share"] >= limits["selected_share"] \
                or not s["counts_equal"]:
            why.append(f"layer {s['layer']}: the program selects "
                       f"{s['share']} of the reference's tokens (the limit "
                       f"is {limits['selected_share']}) and "
                       f"{'as many' if s['counts_equal'] else 'ANOTHER COUNT'}"
                       f" a row")
    return why


def logits_and_margin(cfg: dict, weights, tokens):
    """Logits, and NO routing margin - OLMoE's flat rule
    (``reference/olmoe.py``), for OLMoE's reason at the top 8 of 128: the gap
    between the 8th and the 9th router logit is under the margin tolerance
    about every second time in every layer, so no run could reach the
    decided share; and a flip there exchanges one expert of eight whose
    normalised weight is near the smallest. Every served token is held to
    the flat tolerance with none allowed beyond. The same holds of the
    selection: a token within bf16 of the threshold may rightly fall either
    side, and it is one of ``topk``.

    Where the weights come with their program (the family's ``Weights`` do)
    the probe is ALSO held to the configuration's two limits above: the
    readings are printed as a line of their own, and one beyond its limit
    raises ``Disagreement``."""
    program = getattr(weights, "program", None)
    if program is None:
        out = logits(cfg, weights, tokens)
        return out, jnp.full(out.shape[0], jnp.inf)
    keep = {0: None, cfg["num_hidden_layers"] - 1: None}
    out = logits(cfg, weights, tokens, keep=keep)
    decode = min(HELD_DECODE, len(tokens) - 1)
    seen = held(cfg, weights, program.logits(cfg, tokens, decode),
                out[-(decode + 1):], keep)
    limits = {k: v for k, v in program.limits.items() if k != "why"}
    why = disagreements(seen, limits)
    print(json.dumps({"phase": "held", "tokens": len(tokens), **seen,
                      "limits": limits, "why_not": why}), flush=True)
    if why:
        raise Disagreement(f"a probe of {len(tokens)} tokens: "
                           + "; ".join(why))
    return out, jnp.full(out.shape[0], jnp.inf)
