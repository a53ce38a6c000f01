"""Token sampling — greedy / temperature / top-k / top-p, jit-safe.

Reference parity: the sampling the reference delegates to HF ``generate``;
v2 exposes logits and lets the client sample. Here sampling is a pure function
so it fuses into the decode step.

``filter_logits`` / ``filter_logits_batch`` expose the temperature/top-k/top-p
filtering WITHOUT the final draw — the speculative-decoding verifier
(:func:`accept_drafts`, the sampler of ``engine_v2``'s verify program) needs
the filtered distribution itself to accept/reject draft tokens by exact
rejection sampling.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np


class SamplingParams(NamedTuple):
    temperature: float = 1.0
    top_k: int = 0          # 0 = disabled
    top_p: float = 1.0      # 1.0 = disabled
    greedy: bool = False


def filter_logits(logits: jnp.ndarray,
                  params: SamplingParams) -> jnp.ndarray:
    """Temperature/top-k/top-p filtered logits (static params), ready for
    ``jax.random.categorical``. ONE shared descending sort serves both the
    top-k cutoff and the top-p cumulative scan — the filters used to sort the
    logits twice per decode step. The top-p stage runs over the top-k-FILTERED
    order: masking the sorted array below the k-th value is exactly the sort
    of the filtered logits (ties at the cutoff stay kept, matching the
    historical `logits < kth` semantics)."""
    logits = logits / jnp.maximum(params.temperature, 1e-6)
    srt = None
    if params.top_k > 0 or params.top_p < 1.0:
        srt = jnp.sort(logits, axis=-1)[..., ::-1]        # descending, once
    if params.top_k > 0:
        k = min(params.top_k, logits.shape[-1])
        kth = srt[..., k - 1][..., None]                  # k-th largest
        logits = jnp.where(logits < kth, -jnp.inf, logits)
        srt = jnp.where(srt < kth, -jnp.inf, srt)
    if params.top_p < 1.0:
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep the smallest set with cumulative prob >= top_p (always keep #1);
        # the cutoff is the SMALLEST kept logit
        keep = cum - probs < params.top_p
        cutoff = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return logits


@jax.named_scope("sample")
def sample(rng: jax.Array, logits: jnp.ndarray,
           params: SamplingParams = SamplingParams()) -> jnp.ndarray:
    """logits [..., vocab] → token ids [...]. Static sampling params."""
    if params.greedy or params.temperature == 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(rng, filter_logits(logits, params), axis=-1)


def filter_logits_batch(logits: jnp.ndarray, temperature: jnp.ndarray,
                        top_k: jnp.ndarray, top_p: jnp.ndarray) -> jnp.ndarray:
    """Per-ROW filtered logits, all params traced: logits [B, V];
    temperature/top_p f32 [B]; top_k int32 [B] (0 = disabled). The traced
    counterpart of :func:`filter_logits` — one compiled program serves any
    mix of client sampling configs. Greedy rows are the caller's concern
    (``sample_batch`` overlays argmax)."""
    B, V = logits.shape
    t = jnp.maximum(temperature, 1e-6)[:, None]
    scaled = logits / t
    srt = jnp.sort(scaled, axis=-1)[:, ::-1]              # descending
    # top-k cutoff: the k-th largest per row (k=0 → keep all)
    k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, V), V)
    kth = jnp.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
    filt = jnp.where(scaled < kth, -jnp.inf, scaled)
    # top-p AFTER top-k with renormalization, matching `sample`'s sequential
    # filtering (cutoff on the raw distribution would make a request's
    # distribution depend on its batch neighbors)
    col = jax.lax.broadcasted_iota(jnp.int32, (B, V), 1)
    srt_k = jnp.where(col < k_eff[:, None], srt, -jnp.inf)
    probs = jax.nn.softmax(srt_k, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = cum - probs < jnp.minimum(top_p, 1.0)[:, None]  # always keeps #1
    # top_p >= 1.0 means DISABLED and must be exactly a no-op (as in the
    # static `sample` path, which skips the filter entirely): a cumsum that
    # rounds up could otherwise drop a valid tail column for those rows
    keep = jnp.logical_or(keep, (top_p >= 1.0)[:, None])
    cutoff = jnp.min(jnp.where(keep, srt_k, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(scaled < cutoff, -jnp.inf, filt)


@jax.named_scope("sample")
def sample_batch(rng: jax.Array, logits: jnp.ndarray,
                 temperature: jnp.ndarray, top_k: jnp.ndarray,
                 top_p: jnp.ndarray, greedy: jnp.ndarray) -> jnp.ndarray:
    """Per-ROW sampling params, all traced: logits [B, V]; temperature/top_p
    f32 [B]; top_k int32 [B] (0 = disabled); greedy bool [B]. One compiled
    program serves any mix of client sampling configs (the reference's v2
    engine carries per-request sampling the same way). Rows with greedy or
    temperature 0 take the argmax; the rest sample through their own
    temperature/top-k/top-p filter."""
    argmax = jnp.argmax(logits, axis=-1)
    filt = filter_logits_batch(logits, temperature, top_k, top_p)
    sampled = jax.random.categorical(rng, filt, axis=-1)
    pick_greedy = jnp.logical_or(greedy, temperature <= 0.0)
    return jnp.where(pick_greedy, argmax, sampled)


def accept_drafts(rng: jax.Array, logits: jnp.ndarray, drafts: jnp.ndarray,
                  nvalid: jnp.ndarray, uids: jnp.ndarray, temp: jnp.ndarray,
                  topk: jnp.ndarray, topp: jnp.ndarray, greedy: jnp.ndarray):
    """Speculative verification's sampler, all traced: ``logits``
    [B, k+1, V] score ``drafts`` [B, k] (zero-padded past each row's
    ``nvalid - 1`` real drafts) plus the bonus position; per-row keys fold
    ``uids`` into ``rng``; sampling params per row as in
    :func:`sample_batch`.

    Greedy rows accept draft j while it equals the argmax of the logits
    that precede it; stochastic rows accept with probability
    ``p(draft_j)`` under their own temperature/top-k/top-p-filtered
    distribution — exact rejection sampling for a DETERMINISTIC drafter
    (q = δ), so on rejection the correction is drawn from p with the
    rejected token removed and renormalized, and the emitted stream is
    distributed exactly as plain decode. When every draft is accepted the
    bonus position (scored in the same pass) supplies one extra token.
    Returns (accepted_len [B], next_token [B])."""
    B, kp1 = logits.shape[:2]
    k = kp1 - 1
    amax = jnp.argmax(logits, axis=-1)                         # [B, kp1]
    filt = filter_logits_batch(
        logits.reshape(B * kp1, -1),
        jnp.repeat(temp, kp1), jnp.repeat(topk, kp1),
        jnp.repeat(topp, kp1)).reshape(B, kp1, -1)
    probs = jax.nn.softmax(filt, axis=-1)
    draft_len = nvalid - 1
    keys = jax.vmap(lambda u: jax.random.fold_in(rng, u))(uids)
    accept_u = jax.vmap(lambda kk: jax.random.uniform(kk, (k,)))(keys)
    p_draft = jnp.take_along_axis(
        probs[:, :k, :], drafts[..., None], axis=-1)[..., 0]
    is_greedy = jnp.logical_or(greedy, temp <= 0.0)
    ok = jnp.where(is_greedy[:, None], drafts == amax[:, :k],
                   accept_u < p_draft)
    ok = ok & (jnp.arange(k)[None, :] < draft_len[:, None])
    # longest agreeing prefix: cumprod zeroes everything after the first
    # rejection
    m = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)   # [B]
    lm = jnp.take_along_axis(filt, m[:, None, None], axis=1)[:, 0]   # [B, V]
    la = jnp.take_along_axis(amax, m[:, None], axis=1)[:, 0]
    rejected = m < draft_len
    d_m = jnp.take_along_axis(
        drafts, jnp.minimum(m, k - 1)[:, None], axis=1)[:, 0]
    vocab = jax.lax.broadcasted_iota(jnp.int32, lm.shape, 1)
    residual = jnp.where(rejected[:, None] & (vocab == d_m[:, None]),
                         -jnp.inf, lm)
    keys2 = jax.vmap(lambda kk: jax.random.fold_in(kk, kp1))(keys)
    sampled = jax.vmap(jax.random.categorical)(keys2, residual)
    nxt = jnp.where(is_greedy, la, sampled)
    return m, nxt


def sp_arrays(sps) -> tuple:
    """Pack a list of SamplingParams into the (temperature, top_k, top_p,
    greedy) arrays ``sample_batch`` consumes."""
    return (np.asarray([s.temperature for s in sps], np.float32),
            np.asarray([s.top_k for s in sps], np.int32),
            np.asarray([s.top_p for s in sps], np.float32),
            np.asarray([s.greedy for s in sps], bool))
