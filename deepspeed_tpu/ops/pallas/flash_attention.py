"""Blockwise (flash) attention Pallas kernel, forward + backward.

Reference parity: the reference leans on external flash-attention CUDA kernels
for its long-sequence paths (``deepspeed/sequence/fpdt_layer.py`` imports
``flash_attn_func``; inference v2 ragged attention wraps blocked flash
attention kernels). This is the TPU-native equivalent: an online-softmax
blockwise attention kernel that never materializes the [Sq, Skv] score matrix
in HBM, tiled for the MXU (128-lane blocks), with a flash-style backward pass
(recompute scores per block from the saved logsumexp).

Layout is [batch, seq, heads, head_dim] at the API boundary (matching
``ops.attention``); kernels run on [batch*heads, seq, head_dim].

Grid design (forward): (BH, num_q_blocks, num_kv_blocks) with the kv loop as
the innermost (sequential on TPU) dimension; running max / sum / accumulator
live in VMEM scratch that persists across kv steps. Backward uses two kernels:
one accumulating dQ over kv blocks, one accumulating dK/dV over q blocks.

Native GQA mode (``attention.gqa_native``; docs/performance.md "Native GQA
attention"): the same three kernels run on a KV-HEAD grid —
q ``[B*nkv, g, Sq, d]``, K/V ``[B*nkv, Skv, d]`` — with the query-head group
``g = nh/nkv`` folded into the kernel's ROW axis, so every score matmul is
``[g*bq, d] x [d, bkv]`` against ONE narrow K/V tile in VMEM. K/V are never
materialized at query width: fwd and bwd HBM traffic for K/V drops by g×
(up to 8× for Llama-3/Mistral shapes), and the dK/dV kernel accumulates the
query-head group's contributions onto the NARROW grads for free (the group
rides the contracted row axis). Enabled per-process via
``ops.attention.configure_gqa_native``; default OFF keeps every program
byte-identical to the widening path.

Sliding window (static ``window=``): causal attention additionally masks kv
positions older than ``q_pos - window + 1``; blocks entirely outside the
window skip their compute AND their DMA (the fold maps clamp dead block
indices onto the live band from BOTH sides, matching the paged decode
kernel's dead-step fold).
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import dim_semantics as _dim_semantics
from ._common import interpret as _interpret
from ._common import mxu_dot as _mxu_dot

NEG_INF = -1e30

# lse/delta are stored lane-replicated as [..., 128] fp32 — the Mosaic-friendly
# layout (matches the official JAX TPU flash-attention kernels); costs 128x the
# minimal HBM for these small per-row stats in exchange for layout-change-free
# VMEM reads in the backward kernels.


def _mask_split(qi, ki, *, causal, bq, bkv, kv_len, q_offset, nkv,
                window=None):
    """Disjoint (no_mask, masked) block predicates for the causal/pad mask.

    Only diagonal-band blocks and the ragged last KV block need the
    [bq, bkv] iota/compare/where mask; interior blocks are fully visible
    and skip that VPU work entirely (at bq=bkv=512 the mask build costs
    about as much VPU time as the block's two MXU matmuls take — the
    official TPU flash kernels specialize the same way). Returns None when
    NO block ever needs a mask (non-causal, no KV padding). With a sliding
    ``window`` the band has a LOWER edge too: blocks entirely older than
    the oldest q row's window are dead, and blocks straddling that edge
    are masked."""
    has_pad = (nkv * bkv) != kv_len
    if not causal and not has_pad:
        return None
    if causal:
        participates = ki * bkv <= qi * bq + (bq - 1) + q_offset
        fully_visible = ki * bkv + (bkv - 1) <= qi * bq + q_offset
        if window is not None:
            # newest kv in block must be inside the OLDEST q row's window;
            # fully visible additionally needs the oldest kv inside the
            # NEWEST q row's window
            participates = jnp.logical_and(
                participates,
                ki * bkv + (bkv - 1) > qi * bq + q_offset - window)
            fully_visible = jnp.logical_and(
                fully_visible,
                ki * bkv > qi * bq + (bq - 1) + q_offset - window)
    else:
        participates = jnp.bool_(True)
        fully_visible = jnp.bool_(True)
    pad_blk = (ki == nkv - 1) if has_pad else jnp.bool_(False)
    no_mask = jnp.logical_and(
        participates, jnp.logical_and(fully_visible,
                                      jnp.logical_not(pad_blk)))
    masked = jnp.logical_and(
        participates, jnp.logical_or(jnp.logical_not(fully_visible),
                                     pad_blk))
    return no_mask, masked


def _block_mask(qi, ki, *, causal, bq, bkv, kv_len, q_offset, g=1,
                window=None):
    """The [g*bq, bkv] validity mask for a masked block — ONE definition
    shared by fwd/dq/dkv so the three kernels cannot drift. ``g`` is the
    native-GQA query-head group folded into the row axis: all g groups
    share the same bq query positions, so the [bq, bkv] pattern tiles."""
    q_idx = qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 0) + q_offset
    kv_idx = ki * bkv + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bkv), 1)
    mask = kv_idx < kv_len
    if causal:
        mask = jnp.logical_and(mask, kv_idx <= q_idx)
        if window is not None:
            mask = jnp.logical_and(mask, q_idx - kv_idx < window)
    if g > 1:
        mask = jnp.broadcast_to(mask[None], (g, bq, bkv)) \
            .reshape(g * bq, bkv)
    return mask


def _fold_kv(qi, ki, *, bq, bkv, q_offset, window=None):
    """Clamp a causal-dead kv block index onto the diagonal band: blocks
    strictly above the diagonal compute nothing, so their BlockSpec index
    folds to the last participating block — consecutive grid steps then
    map to the same block and Pallas elides the DMA. Halves causal K/V
    HBM traffic (same trick as the paged kernel's dead-step fold). With a
    sliding ``window`` the clamp is two-sided: blocks entirely older than
    the window fold onto the first live one."""
    j_max = jnp.maximum((qi * bq + (bq - 1) + q_offset) // bkv, 0)
    if window is None:
        return jnp.minimum(ki, j_max)
    j_min = jnp.maximum((qi * bq + q_offset - window + 1) // bkv, 0)
    return jnp.clip(ki, jnp.minimum(j_min, j_max), j_max)


def _fold_q(qi, ki, *, bq, bkv, q_offset, nq, window=None):
    """dkv-kernel counterpart: clamp a dead Q block index up to the first
    participating one for kv block ki (qi*bq+bq-1+q_offset >= ki*bkv).
    Upper clamp to nq-1: with kv_len > sq (legal — trailing keys are fully
    masked) a kv block past the last q row has NO participant and the
    unclamped first-participant index would run off the q array. With a
    sliding ``window`` q blocks entirely NEWER than the block's window
    (qi*bq+q_offset > ki*bkv+bkv-1+window-1) are dead too — clamp down."""
    q_min = jnp.maximum((ki * bkv - q_offset) // bq, 0)
    q_hi = nq - 1
    if window is not None:
        q_hi = jnp.minimum(
            q_hi, jnp.maximum(
                (ki * bkv + (bkv - 1) + window - 1 - q_offset) // bq, 0))
        q_min = jnp.minimum(q_min, q_hi)
    return jnp.minimum(jnp.maximum(qi, q_min), q_hi)


def _fold_maps(*, causal, bq, bkv, q_offset, window=None):
    """(kvmap, biasmap) for the q-major grids (b, qi, ki) — ONE builder
    shared by _flash_fwd and the dq backward so the fold cannot drift."""
    if not causal:
        return (lambda b, i, j: (b, j, 0)), (lambda b, i, j: (b, i, j))

    def kvmap(b, i, j):
        return (b, _fold_kv(i, j, bq=bq, bkv=bkv, q_offset=q_offset,
                            window=window), 0)

    def biasmap(b, i, j):
        return (b, i, _fold_kv(i, j, bq=bq, bkv=bkv, q_offset=q_offset,
                               window=window))

    return kvmap, biasmap


def _fold_maps_dkv(*, causal, bq, bkv, q_offset, nq, window=None):
    """(qmap, biasmap) for the kv-major dkv grid (b, ki, qi); qmap also
    serves the do/lse/delta specs."""
    if not causal:
        return (lambda b, j, i: (b, i, 0)), (lambda b, j, i: (b, i, j))

    def qmap(b, j, i):
        return (b, _fold_q(i, j, bq=bq, bkv=bkv, q_offset=q_offset, nq=nq,
                           window=window),
                0)

    def biasmap(b, j, i):
        return (b, _fold_q(i, j, bq=bq, bkv=bkv, q_offset=q_offset, nq=nq,
                           window=window),
                j)

    return qmap, biasmap


_TUNED_CACHE: dict = {}


def _tuned_json() -> dict:
    """`.dstpu_tuned.json` at the repo root (resolved by
    ``tuning/persist.py``, same file the online tuner persists to), read
    ONCE. Keys: ``flash_block`` (the MHA q/kv block), plus optional
    per-GQA-group q blocks ``flash_block_g<g>`` written by
    ``scripts/attn_sweep.py``'s kv_heads sweep dimension."""
    if "tuned" not in _TUNED_CACHE:
        _TUNED_CACHE["tuned"] = {}
        try:
            from ...tuning.persist import load_tuned

            _TUNED_CACHE["tuned"] = load_tuned()
        except Exception:
            pass  # no sweep artifact — compiled-in defaults
    return _TUNED_CACHE["tuned"]


def _tuned_default() -> int:
    """Best measured block size, if `scripts/attn_sweep.py` has run on this
    machine. Falls back to 512 — large enough to amortize MXU issue + VPU
    overhead; VMEM at bq=bkv=512, d<=128 stays well under budget.
    Env/`pref` still override."""
    if "flash_block" not in _TUNED_CACHE:
        _TUNED_CACHE["flash_block"] = 512
        try:
            v = int(_tuned_json().get("flash_block", 512))
            if v > 0 and v % 8 == 0:
                _TUNED_CACHE["flash_block"] = v
        except Exception:
            pass
    return _TUNED_CACHE["flash_block"]


def _block(n: int, pref: Optional[int] = None) -> int:
    """Block size preference order: explicit ``pref`` > ``DSTPU_FLASH_BLOCK``
    env (on-chip sweeps) > measured `.dstpu_tuned.json` > 512."""
    if pref is None:
        raw = os.environ.get("DSTPU_FLASH_BLOCK")
        if raw is None:
            pref = _tuned_default()
        else:
            try:
                pref = int(raw)
            except ValueError:
                raise ValueError(
                    f"DSTPU_FLASH_BLOCK={raw!r} is not an integer") from None
            if pref <= 0 or pref % 8:
                raise ValueError(f"DSTPU_FLASH_BLOCK={pref} must be a "
                                 f"positive multiple of 8 (Mosaic tiling)")
    return min(pref, max(8, 1 << (n - 1).bit_length())) if n < pref else pref


def _block_gqa(n: int, g: int) -> int:
    """Per-GROUP q block for the native-GQA kernels: the kernel's row axis
    carries g*bq rows, so the default scales the tuned/env block down by g
    (total rows ≈ the MHA block → same VMEM/score-tile budget). A measured
    ``flash_block_g<g>`` in `.dstpu_tuned.json` overrides directly (it IS
    the per-group bq — the autotune key gained the kv_heads dimension)."""
    raw = os.environ.get("DSTPU_FLASH_BLOCK")
    if raw is None:
        try:
            v = int(_tuned_json().get(f"flash_block_g{g}", 0))
        except Exception:
            v = 0
        if v > 0 and v % 8 == 0:
            return _block(n, v)
        base = _tuned_default()
    else:
        base = _block(max(n * g, 8))  # env names TOTAL kernel rows
    return _block(n, max(8, (base // g) // 8 * 8))


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #
def _fwd_kernel(*refs, scale, causal, bq, bkv, kv_len, q_offset, nkv,
                has_bias, g=1, window=None):
    if has_bias:
        (q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        bias_ref = None
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    qi = pl.program_id(1)
    # causal: kv blocks strictly above the diagonal band contribute nothing —
    # skip their compute entirely (the reference's flash kernels do the same);
    # interior (fully visible) blocks additionally skip the mask build.
    def _compute(masked):
        # keep q/k in input dtype (bf16): the MXU runs bf16xbf16->fp32 at full
        # rate; casting inputs to fp32 first would drop to ~1/8 peak.
        if g > 1:
            q = q_ref[0].reshape(g * bq, q_ref.shape[-1])  # [g*bq, d]
        else:
            q = q_ref[0]                          # [bq, d]
        k = k_ref[0]                              # [bkv, d]
        v = v_ref[0]                              # [bkv, d]
        s = _mxu_dot(q, k, (((1,), (1,)), ((), ())),
                     preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)

        if masked:
            s = jnp.where(_block_mask(qi, ki, causal=causal, bq=bq, bkv=bkv,
                                      kv_len=kv_len, q_offset=q_offset,
                                      g=g, window=window),
                          s, NEG_INF)

        m_prev = m_scr[...]                  # [g*bq, 128] (lane-replicated)
        l_prev = l_scr[...]
        m_curr = jnp.max(s, axis=1, keepdims=True)            # [g*bq, 1]
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_curr, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)                        # [g*bq, 128]
        p = jnp.exp(s - m_new[:, :1])                          # [g*bq, bkv]
        l_new = l_prev * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + _mxu_dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new

    split = _mask_split(qi, ki, causal=causal, bq=bq, bkv=bkv, kv_len=kv_len,
                        q_offset=q_offset, nkv=nkv, window=window)
    if split is None:
        _compute(masked=False)
    else:
        no_mask, masked = split
        pl.when(no_mask)(lambda: _compute(masked=False))
        pl.when(masked)(lambda: _compute(masked=True))

    @pl.when(ki == nkv - 1)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        if g > 1:
            d = o_ref.shape[-1]
            o_ref[0] = (acc_scr[...] / l_safe[:, :1]) \
                .reshape(g, bq, d).astype(o_ref.dtype)
            lse_ref[0] = (m_scr[...] + jnp.log(l_safe)).reshape(g, bq, 128)
        else:
            o_ref[0] = (acc_scr[...] / l_safe[:, :1]).astype(o_ref.dtype)
            lse_ref[0] = m_scr[...] + jnp.log(l_safe)


def _flash_fwd(q, k, v, bias=None, *, causal, scale, q_offset, g=1,
               window=None):
    """MHA/widened layout (g == 1): q/k/v [BH, S, d] (+ optional bias
    [BH, Sq, Skv]) → (o [BH, Sq, d], lse [BH, Sq, 128]).

    Native-GQA layout (g > 1): q [B*nkv, g, Sq, d], k/v [B*nkv, Skv, d]
    (narrow — never widened) → (o [B*nkv, g, Sq, d],
    lse [B*nkv, g, Sq, 128]); bias unsupported there."""
    if g > 1:
        assert bias is None, "native-GQA kernel does not take a bias"
        bh, _, sq, d = q.shape
        q_axis = 2
    else:
        bh, sq, d = q.shape
        q_axis = 1
    kv_len = k.shape[1]
    bq = _block_gqa(sq, g) if g > 1 else _block(sq)
    bkv = _block(kv_len)
    qp = _pad_to(q, q_axis, bq)
    kp = _pad_to(k, 1, bkv)
    vp = _pad_to(v, 1, bkv)
    nq = qp.shape[q_axis] // bq
    nkv = kp.shape[1] // bkv

    kvmap, biasmap = _fold_maps(causal=causal, bq=bq, bkv=bkv,
                                q_offset=q_offset, window=window)
    if g > 1:
        qspec = pl.BlockSpec((1, g, bq, d), lambda b, i, j: (b, 0, i, 0))
        in_specs = [
            qspec,
            pl.BlockSpec((1, bkv, d), kvmap),
            pl.BlockSpec((1, bkv, d), kvmap),
        ]
        out_specs = [
            qspec,
            pl.BlockSpec((1, g, bq, 128), lambda b, i, j: (b, 0, i, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((bh, g, qp.shape[2], d), q.dtype),
            jax.ShapeDtypeStruct((bh, g, qp.shape[2], 128), jnp.float32),
        ]
    else:
        in_specs = [
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), kvmap),
            pl.BlockSpec((1, bkv, d), kvmap),
        ]
        out_specs = [
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
        ]
        out_shape = [
            jax.ShapeDtypeStruct((bh, qp.shape[1], d), q.dtype),
            jax.ShapeDtypeStruct((bh, qp.shape[1], 128), jnp.float32),
        ]
    args = [qp, kp, vp]
    if bias is not None:
        bp = _pad_to(_pad_to(bias, 1, bq), 2, bkv)
        in_specs.append(pl.BlockSpec((1, bq, bkv), biasmap))
        args.append(bp)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, bq=bq, bkv=bkv,
        kv_len=kv_len, q_offset=q_offset, nkv=nkv, has_bias=bias is not None,
        g=g, window=window)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nkv),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((g * bq, 128), jnp.float32),
            pltpu.VMEM((g * bq, 128), jnp.float32),
            pltpu.VMEM((g * bq, d), jnp.float32),
        ],
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="flash_fwd",
    )(*args)
    if g > 1:
        return o[:, :, :sq], lse[:, :, :sq]
    return o[:, :sq], lse[:, :sq]


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #
def _bwd_dq_kernel(*refs, scale, causal, bq, bkv, kv_len, q_offset, nkv,
                   has_bias, g=1, window=None):
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
         dq_ref, dbias_ref, dq_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dq_ref, dq_scr) = refs
        bias_ref = dbias_ref = None
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    qi = pl.program_id(1)
    def _compute(masked):
        if g > 1:
            d = q_ref.shape[-1]
            q = q_ref[0].reshape(g * bq, d)
            do = do_ref[0].reshape(g * bq, d)
            lse = lse_ref[0].reshape(g * bq, 128)[:, :1]   # [g*bq, 1]
            delta = delta_ref[0].reshape(g * bq, 128)[:, :1]
        else:
            q = q_ref[0]
            do = do_ref[0]
            lse = lse_ref[0][:, :1]                   # [bq, 1]
            delta = delta_ref[0][:, :1]
        k = k_ref[0]
        v = v_ref[0]

        s = _mxu_dot(q, k, (((1,), (1,)), ((), ())),
                     preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)
        if masked:
            p = jnp.where(_block_mask(qi, ki, causal=causal, bq=bq, bkv=bkv,
                                      kv_len=kv_len, q_offset=q_offset,
                                      g=g, window=window),
                          jnp.exp(s - lse), 0.0)              # [g*bq, bkv]
        else:
            p = jnp.exp(s - lse)
        dp = _mxu_dot(do, v, (((1,), (1,)), ((), ())),
                      preferred_element_type=jnp.float32)
        ds_raw = p * (dp - delta)   # dL/d(logits) — the bias gradient
        if dbias_ref is not None:
            dbias_ref[0] = ds_raw.astype(dbias_ref.dtype)
        ds = (ds_raw * scale).astype(k.dtype)
        dq_scr[...] += _mxu_dot(ds, k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    split = _mask_split(qi, ki, causal=causal, bq=bq, bkv=bkv, kv_len=kv_len,
                        q_offset=q_offset, nkv=nkv, window=window)
    if split is None:
        _compute(masked=False)
    else:
        no_mask, masked = split
        pl.when(no_mask)(lambda: _compute(masked=False))
        pl.when(masked)(lambda: _compute(masked=True))
        if causal and dbias_ref is not None:
            # skipped above-diagonal blocks must still zero their dbias
            # block — exactly the complement of the two branches above
            @pl.when(jnp.logical_not(jnp.logical_or(no_mask, masked)))
            def _zero_dbias():
                dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    @pl.when(ki == nkv - 1)
    def _finish():
        if g > 1:
            d = dq_ref.shape[-1]
            dq_ref[0] = dq_scr[...].reshape(g, bq, d).astype(dq_ref.dtype)
        else:
            dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, bq, bkv, kv_len, q_offset, nq,
                    nkv, has_bias, g=1, window=None):
    if has_bias:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        bias_ref = None
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    ki = pl.program_id(1)
    def _compute(masked):
        if g > 1:
            d = q_ref.shape[-1]
            q = q_ref[0].reshape(g * bq, d)
            do = do_ref[0].reshape(g * bq, d)
            lse = lse_ref[0].reshape(g * bq, 128)[:, :1]
            delta = delta_ref[0].reshape(g * bq, 128)[:, :1]
        else:
            q = q_ref[0]
            do = do_ref[0]
            lse = lse_ref[0][:, :1]
            delta = delta_ref[0][:, :1]
        k = k_ref[0]
        v = v_ref[0]

        s = _mxu_dot(q, k, (((1,), (1,)), ((), ())),
                     preferred_element_type=jnp.float32) * scale
        if bias_ref is not None:
            s = s + bias_ref[0].astype(jnp.float32)
        if masked:
            p = jnp.where(_block_mask(qi, ki, causal=causal, bq=bq, bkv=bkv,
                                      kv_len=kv_len, q_offset=q_offset,
                                      g=g, window=window),
                          jnp.exp(s - lse), 0.0)
        else:
            p = jnp.exp(s - lse)
        dp = _mxu_dot(do, v, (((1,), (1,)), ((), ())),
                      preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        # contraction over the ROW axis (g*bq): the query-head group's
        # contributions accumulate onto the NARROW dk/dv tile for free
        dv_scr[...] += _mxu_dot(p.astype(do.dtype), do,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        dk_scr[...] += _mxu_dot(ds, q, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    split = _mask_split(qi, ki, causal=causal, bq=bq, bkv=bkv, kv_len=kv_len,
                        q_offset=q_offset, nkv=nkv, window=window)
    if split is None:
        _compute(masked=False)
    else:
        no_mask, masked = split
        pl.when(no_mask)(lambda: _compute(masked=False))
        pl.when(masked)(lambda: _compute(masked=True))

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, bias=None, *, causal, scale, q_offset,
               g=1, window=None):
    if g > 1:
        assert bias is None, "native-GQA kernel does not take a bias"
        bh, _, sq, d = q.shape
        q_axis = 2
    else:
        bh, sq, d = q.shape
        q_axis = 1
    kv_len = k.shape[1]
    bq = _block_gqa(sq, g) if g > 1 else _block(sq)
    bkv = _block(kv_len)
    qp = _pad_to(q, q_axis, bq)
    kp = _pad_to(k, 1, bkv)
    vp = _pad_to(v, 1, bkv)
    dop = _pad_to(do, q_axis, bq)
    nq = qp.shape[q_axis] // bq
    nkv = kp.shape[1] // bkv
    has_bias = bias is not None

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (128,))
    delta = _pad_to(delta, q_axis, bq)
    lsep = _pad_to(lse, q_axis, bq)

    # causal: fold dead (above-diagonal) steps' INPUT fetches onto the
    # diagonal band so their DMA is elided; output specs never fold (dead
    # dbias blocks must still write their zeros to the right slot)
    kvmap_dq, biasmap_dq = _fold_maps(causal=causal, bq=bq, bkv=bkv,
                                      q_offset=q_offset, window=window)
    if g > 1:
        def qmap4(b, i, j):
            return (b, 0, i, 0)

        dq_in_specs = [
            pl.BlockSpec((1, g, bq, d), qmap4),
            pl.BlockSpec((1, bkv, d), kvmap_dq),
            pl.BlockSpec((1, bkv, d), kvmap_dq),
            pl.BlockSpec((1, g, bq, d), qmap4),
            pl.BlockSpec((1, g, bq, 128), qmap4),
            pl.BlockSpec((1, g, bq, 128), qmap4),
        ]
        dq_out_specs = pl.BlockSpec((1, g, bq, d), qmap4)
        dq_out_shape = jax.ShapeDtypeStruct((bh, g, qp.shape[2], d), q.dtype)
    else:
        dq_in_specs = [
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, d), kvmap_dq),
            pl.BlockSpec((1, bkv, d), kvmap_dq),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 128), lambda b, i, j: (b, i, 0)),
        ]
        dq_out_specs = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
        dq_out_shape = jax.ShapeDtypeStruct((bh, qp.shape[1], d), q.dtype)
    dq_args = [qp, kp, vp, dop, lsep, delta]
    if has_bias:
        bp = _pad_to(_pad_to(bias, 1, bq), 2, bkv)
        dq_in_specs.append(pl.BlockSpec((1, bq, bkv), biasmap_dq))
        dq_args.append(bp)
        dq_out_specs = [dq_out_specs,
                        pl.BlockSpec((1, bq, bkv), lambda b, i, j: (b, i, j))]
        dq_out_shape = [dq_out_shape,
                        jax.ShapeDtypeStruct(bp.shape, jnp.float32)]

    dq_out = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal, bq=bq,
                          bkv=bkv, kv_len=kv_len, q_offset=q_offset, nkv=nkv,
                          has_bias=has_bias, g=g, window=window),
        grid=(bh, nq, nkv),
        in_specs=dq_in_specs,
        out_specs=dq_out_specs,
        out_shape=dq_out_shape,
        scratch_shapes=[pltpu.VMEM((g * bq, d), jnp.float32)],
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="flash_bwd_dq",
    )(*dq_args)
    if has_bias:
        dq, dbias = dq_out
        dbias = dbias[:, :sq, :kv_len]
    else:
        dq, dbias = dq_out, None

    # dkv mirror: dead steps are q blocks ABOVE kv block j's band — clamp
    # the q-side fetches (q/do/lse/delta/bias) up to the first participant
    qmap_dkv, biasmap_dkv = _fold_maps_dkv(causal=causal, bq=bq, bkv=bkv,
                                           q_offset=q_offset, nq=nq,
                                           window=window)
    if g > 1:
        def qmap4_dkv(b, j, i):
            return (b, 0) + qmap_dkv(b, j, i)[1:]

        dkv_in_specs = [
            pl.BlockSpec((1, g, bq, d), qmap4_dkv),
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, g, bq, d), qmap4_dkv),
            pl.BlockSpec((1, g, bq, 128), qmap4_dkv),
            pl.BlockSpec((1, g, bq, 128), qmap4_dkv),
        ]
    else:
        dkv_in_specs = [
            pl.BlockSpec((1, bq, d), qmap_dkv),
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, d), qmap_dkv),
            pl.BlockSpec((1, bq, 128), qmap_dkv),
            pl.BlockSpec((1, bq, 128), qmap_dkv),
        ]
    dkv_args = [qp, kp, vp, dop, lsep, delta]
    if has_bias:
        dkv_in_specs.append(pl.BlockSpec((1, bq, bkv), biasmap_dkv))
        dkv_args.append(bp)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal, bq=bq,
                          bkv=bkv, kv_len=kv_len, q_offset=q_offset, nq=nq,
                          nkv=nkv, has_bias=has_bias, g=g, window=window),
        grid=(bh, nkv, nq),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bkv, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, kp.shape[1], d), k.dtype),
            jax.ShapeDtypeStruct((bh, kp.shape[1], d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bkv, d), jnp.float32),
            pltpu.VMEM((bkv, d), jnp.float32),
        ],
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="flash_bwd_dkv",
    )(*dkv_args)
    if g > 1:
        return dq[:, :, :sq], dk[:, :kv_len], dv[:, :kv_len], dbias
    return dq[:, :sq], dk[:, :kv_len], dv[:, :kv_len], dbias


# --------------------------------------------------------------------------- #
# differentiable wrappers ([BH, S, d] widened layout, and the native-GQA
# [B*nkv, g, S, d] / narrow [B*nkv, S, d] layout)
# --------------------------------------------------------------------------- #
def _named(o, lse):
    """The forward rules' own residuals under a checkpoint name: a remat
    policy that saves ``attn_flash`` (``save_big_matmuls`` does) spares the
    backward the kernel's forward replay - the output in the kernel's layout
    and the log-sum-exp are all ``_flash_bwd`` needs beside q, k and v.
    Identity outside such a policy."""
    return checkpoint_name(o, "attn_flash"), checkpoint_name(lse, "attn_flash")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, q_offset, window=None):
    o, _ = _flash_fwd(q, k, v, causal=causal, scale=scale, q_offset=q_offset,
                      window=window)
    return o


def _flash_vjp_fwd(q, k, v, causal, scale, q_offset, window=None):
    o, lse = _named(*_flash_fwd(q, k, v, causal=causal, scale=scale,
                                q_offset=q_offset, window=window))
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, q_offset, window, res, do):
    q, k, v, o, lse = res
    dq, dk, dv, _ = _flash_bwd(q, k, v, o, lse, do, causal=causal,
                               scale=scale, q_offset=q_offset, window=window)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_gqa(q, k, v, causal, scale, q_offset, window=None):
    """Native-GQA flash: q [B*nkv, g, Sq, d]; k/v NARROW [B*nkv, Skv, d].
    dK/dV come back narrow — the dkv kernel contracts the query-head group
    on its row axis, so no widen/sum-back pair ever exists."""
    o, _ = _flash_fwd(q, k, v, causal=causal, scale=scale, q_offset=q_offset,
                      g=q.shape[1], window=window)
    return o


def _flash_gqa_vjp_fwd(q, k, v, causal, scale, q_offset, window=None):
    o, lse = _named(*_flash_fwd(q, k, v, causal=causal, scale=scale,
                                q_offset=q_offset, g=q.shape[1],
                                window=window))
    return o, (q, k, v, o, lse)


def _flash_gqa_vjp_bwd(causal, scale, q_offset, window, res, do):
    q, k, v, o, lse = res
    dq, dk, dv, _ = _flash_bwd(q, k, v, o, lse, do, causal=causal,
                               scale=scale, q_offset=q_offset,
                               g=q.shape[1], window=window)
    return dq, dk, dv


_flash_gqa.defvjp(_flash_gqa_vjp_fwd, _flash_gqa_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_b(q, k, v, bias, causal, scale, q_offset):
    o, _ = _flash_fwd(q, k, v, bias, causal=causal, scale=scale,
                      q_offset=q_offset)
    return o


def _flash_b_vjp_fwd(q, k, v, bias, causal, scale, q_offset):
    o, lse = _named(*_flash_fwd(q, k, v, bias, causal=causal, scale=scale,
                                q_offset=q_offset))
    return o, (q, k, v, bias, o, lse)


def _flash_b_vjp_bwd(causal, scale, q_offset, res, do):
    q, k, v, bias, o, lse = res
    dq, dk, dv, dbias = _flash_bwd(q, k, v, o, lse, do, bias, causal=causal,
                                   scale=scale, q_offset=q_offset)
    return dq, dk, dv, dbias.astype(bias.dtype)


_flash_b.defvjp(_flash_b_vjp_fwd, _flash_b_vjp_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, scale: Optional[float] = None,
                    mask: Optional[jnp.ndarray] = None,
                    bias: Optional[jnp.ndarray] = None,
                    q_offset: int = 0,
                    window: Optional[int] = None) -> jnp.ndarray:
    """Drop-in for ``ops.attention.attention_xla``: [B, S, H, D] layout, GQA
    K/V broadcast (or native-narrow under ``attention.gqa_native``), fp32
    accumulation. Supports an ADDITIVE bias (broadcastable to
    [B, H, Sq, Skv]; differentiable — dbias flows through the backward
    kernel; the evoformer pair-bias path) and a STATIC causal sliding
    ``window`` (blocks outside the window skip compute and DMA). Boolean
    masks — and the window+bias combination — fall back to the XLA
    implementation (the kernel handles causal + length masking natively)."""
    if mask is not None or (window is not None and bias is not None):
        from ..attention import attention_xla

        return attention_xla(q, k, v, causal=causal, scale=scale, mask=mask,
                             bias=bias, q_offset=q_offset, window=window)
    from ..attention import gqa_native_active, widen_kv

    b, sq, h, d = q.shape
    kvh = k.shape[2]
    scale = scale if scale is not None else d ** -0.5
    if window is not None:
        assert causal, "window requires causal attention"
        assert window >= 1, f"sliding window must be >= 1, got {window}"

    if gqa_native_active() and kvh != h and bias is None:
        # native-GQA path: K/V stay narrow; query head h = kv*g + gi rides
        # the kernel's row axis with its kv head's tile
        g = h // kvh
        kv_len = k.shape[1]
        q4 = q.reshape(b, sq, kvh, g, d).transpose(0, 2, 3, 1, 4) \
            .reshape(b * kvh, g, sq, d)
        k3 = k.transpose(0, 2, 1, 3).reshape(b * kvh, kv_len, d)
        v3 = v.transpose(0, 2, 1, 3).reshape(b * kvh, kv_len, d)
        o = _flash_gqa(q4, k3, v3, causal, float(scale), int(q_offset),
                       None if window is None else int(window))
        return o.reshape(b, kvh, g, sq, d).transpose(0, 3, 1, 2, 4) \
            .reshape(b, sq, h, d)

    k, v = widen_kv(k, v, h)
    kv_len = k.shape[1]

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    if bias is not None:
        bias = jnp.broadcast_to(bias, (b, h, sq, kv_len)) \
            .reshape(b * h, sq, kv_len)
        o = _flash_b(to_bh(q), to_bh(k), to_bh(v), bias, causal,
                     float(scale), int(q_offset))
    elif window is not None:
        o = _flash(to_bh(q), to_bh(k), to_bh(v), causal, float(scale),
                   int(q_offset), int(window))
    else:
        o = _flash(to_bh(q), to_bh(k), to_bh(v), causal, float(scale),
                   int(q_offset))
    return o.reshape(b, h, sq, d).transpose(0, 2, 1, 3)


from ..registry import register  # noqa: E402

# q, k and v carry the batch on their leading dim
register("attention", backend="pallas", rows=3)(flash_attention)
