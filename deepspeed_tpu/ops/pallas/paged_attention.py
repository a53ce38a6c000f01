"""Paged (blocked-KV) flash-decode attention Pallas kernel.

Reference parity: the inference v2 ragged decode kernels
(``inference/v2/kernels/ragged_ops/`` — blocked flash attention over the
``BlockedKVCache``, ``inference/v2/ragged/kv_cache.py``). Round-1 shipped a
gather-based XLA path (``models/llama.py apply_paged``) that materializes a
dense [B, max_blocks*bs, ...] KV view per layer; this kernel reads KV blocks
straight out of the shared pool via a block-table-indexed ``BlockSpec``
(scalar-prefetch), online-softmax accumulating — no dense copy, HBM traffic =
exactly the live context.

Decode layout: one query token per sequence.
  q            [B, nh, hd]
  k/v pool     [num_blocks, nkv, bs, hd]   (block 0 = trash block; kv-head
               axis ahead of the token axis so the per-block tile is
               (bs, hd) — a squeezed dim in the last two positions is
               rejected by the Mosaic TPU lowering's tiling check)
  block_tables [B, max_blocks] int32
  context_lens [B] int32 — tokens ALREADY cached; the current token's K/V
               must be written to the pool before calling (so the effective
               length is context_lens + 1).
Grid: (B, nkv, max_blocks), KV-block loop innermost/sequential; the GQA query
group (g = nh/nkv rows) rides the MXU sublanes.

Quantized KV mode (``inference.kv_quant``, docs/serving.md "Quantized KV
cache"): ``k_pool``/``v_pool`` hold int8 codes and ``k_scale``/``v_scale``
``[num_blocks, nkv, bs, ngroups]`` fp32 per-block-per-group scales ride the
same block-table-indexed BlockSpecs. The kernel loads the int8 tile plus its
scale tile and dequantizes IN-REGISTER (a lane broadcast at ngroups == 1 —
the default ``group_size >= hd`` config — or a grouped reshape-multiply
otherwise) immediately before the bf16 MXU dots. No standalone XLA
int8→bf16 convert pass over the pool ever runs: QUANT_TPU_LIVE.json pins
that path at 1.02–1.21× SLOWER than bf16, so the entire win is int8 HBM
traffic + residency with the convert hidden inside the flash loop.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import dim_semantics as _dim_semantics
from ._common import interpret as _interpret
from ._common import mxu_dot as _mxu_dot

NEG_INF = -1e30


def _dequant_tile(codes_ref, scale_ref, dtype):
    """In-register dequant of one [bs, hd] int8 KV tile with its [bs, ng]
    fp32 scale tile, emitted right before the MXU dot. ng == 1 (the default
    ``group_size >= hd`` config) is a pure lane broadcast; ng > 1 groups the
    lanes (blocked layout, matching ``ops.quantization.kv_quantize_int8``)."""
    x = codes_ref[...].astype(jnp.float32)
    s = scale_ref[...]
    ng = s.shape[1]
    if ng == 1:
        x = x * s
    else:
        bs_, hd_ = x.shape
        x = (x.reshape(bs_, ng, hd_ // ng) * s[:, :, None]).reshape(bs_, hd_)
    return x.astype(dtype)


def _decode_kernel(*refs, bs, scale, nblk, gpad, has_window, quant=False):
    if quant:
        if has_window:
            (tables_ref, ctx_ref, wnd_ref, q_ref, k_ref, v_ref, ks_ref,
             vs_ref, o_ref, m_scr, l_scr, acc_scr) = refs
        else:
            (tables_ref, ctx_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
             o_ref, m_scr, l_scr, acc_scr) = refs
            wnd_ref = None
    elif has_window:
        (tables_ref, ctx_ref, wnd_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (tables_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
        wnd_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = ctx_ref[b] + 1  # current token attends to itself too
    # sliding window: only positions in (ctx-1-w, ctx-1] are visible; blocks
    # entirely older than the window skip their compute AND their DMA —
    # kvmap folds dead grid steps onto the nearest live block index, and
    # Pallas elides the copy when consecutive steps map to the same block
    if has_window:
        lo = ctx_ref[b] - wnd_ref[0]
        live = jnp.logical_and(j * bs < ctx, j * bs + bs - 1 > lo)
    else:
        live = j * bs < ctx

    @pl.when(live)
    def _compute():
        q = q_ref[...]                     # [gpad, hd]
        if quant:                          # int8 tile → q.dtype, in-register
            k = _dequant_tile(k_ref, ks_ref, q_ref.dtype)
            v = _dequant_tile(v_ref, vs_ref, q_ref.dtype)
        else:
            k = k_ref[...]                 # [bs, hd]
            v = v_ref[...]                 # [bs, hd]
        s = _mxu_dot(q, k, (((1,), (1,)), ((), ())),
                     preferred_element_type=jnp.float32) * scale
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < ctx
        if has_window:
            valid = jnp.logical_and(valid, pos > lo)
        s = jnp.where(valid, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_curr = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_curr, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_scr[...] = l_prev * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + _mxu_dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nblk - 1)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe[:, :1]).astype(o_ref.dtype)


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                           context_lens: jnp.ndarray, *,
                           scale: float = None,
                           window=None, k_scale=None,
                           v_scale=None) -> jnp.ndarray:
    """See module docstring. Returns [B, nh, hd]. ``window``: optional
    sliding-window length (int or traced scalar — exaone4 scans per-layer
    windows): only the last ``window`` positions are attended; blocks
    entirely outside the window skip their compute. ``k_scale``/``v_scale``:
    per-block-per-group fp32 scale pools ``[num_blocks, nkv, bs, ngroups]``
    for int8 code pools — the quantized-KV mode with dequant fused into the
    flash loop (both or neither must be given)."""
    B, nh, hd = q.shape
    nblocks, nkv, bs, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    g = nh // nkv
    gpad = max(8, 1 << (g - 1).bit_length())  # sublane-pad the query group
    scale = hd ** -0.5 if scale is None else scale
    has_window = window is not None
    quant = k_scale is not None
    assert quant == (v_scale is not None), \
        "k_scale and v_scale must be given together"
    if has_window:
        # window <= 0 is nonsensical: every score masks to NEG_INF and the
        # all-masked softmax degenerates to a uniform average over a garbage
        # block (ADVICE r5). Reject static values outright; clamp traced ones.
        if isinstance(window, (int, np.integer)):
            assert window >= 1, f"sliding window must be >= 1, got {window}"
        window = jnp.maximum(jnp.asarray(window, jnp.int32), 1)

    # [B, nkv, gpad, hd] query groups
    qg = q.reshape(B, nkv, g, hd)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, gpad - g), (0, 0)))

    kernel = functools.partial(_decode_kernel, bs=bs, scale=float(scale),
                               nblk=max_blocks, gpad=gpad,
                               has_window=has_window, quant=quant)

    # index maps are called positionally with one trailing arg per
    # prefetched scalar array — varargs serves both arities. Dead grid
    # steps (past the context, or older than the window) FOLD onto the
    # nearest live block index: Pallas elides the DMA when consecutive
    # steps map to the same block, so HBM traffic stays "exactly the live
    # context" with or without a window.
    def qmap(b, h, j, *_):
        return (b, h, 0, 0)

    def kvmap(b, h, j, tables, ctx, *rest):
        hi_blk = ctx[b] // bs              # block holding the current token
        lo_blk = (jnp.maximum(ctx[b] - rest[0][0] + 1, 0) // bs
                  if rest else 0)
        j_eff = jnp.clip(j, lo_blk, hi_blk)
        return (jnp.clip(tables[b, j_eff], 0, nblocks - 1), h, 0, 0)

    in_specs = [
        pl.BlockSpec((None, None, gpad, hd), qmap),
        # the paged read: pool block chosen by the table
        pl.BlockSpec((None, None, bs, hd), kvmap),
        pl.BlockSpec((None, None, bs, hd), kvmap),
    ]
    operands = [qg, k_pool, v_pool]
    if quant:
        # scale tiles ride the SAME block-table-indexed map as their code
        # tiles, so a dead grid step elides both DMAs together
        ng = k_scale.shape[-1]
        in_specs += [pl.BlockSpec((None, None, bs, ng), kvmap),
                     pl.BlockSpec((None, None, bs, ng), kvmap)]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + int(has_window),
        grid=(B, nkv, max_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, gpad, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((gpad, 128), jnp.float32),
            pltpu.VMEM((gpad, 128), jnp.float32),
            pltpu.VMEM((gpad, hd), jnp.float32),
        ],
    )
    prefetch = [block_tables.astype(jnp.int32),
                context_lens.astype(jnp.int32)]
    if has_window:
        prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, gpad, hd), q.dtype),
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="paged_decode",
    )(*prefetch, *operands)
    return out[:, :, :g].reshape(B, nh, hd)


def paged_decode_attention_xla(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                               context_lens: jnp.ndarray, *,
                               scale: float = None,
                               window=None, k_scale=None,
                               v_scale=None) -> jnp.ndarray:
    """Dense-gather fallback with identical semantics (compiled XLA — the
    right choice off-TPU, where the Pallas path runs interpreted).
    ``k_scale``/``v_scale``: the quantized-KV reference path — int8 code
    pools dequantize on the gathered view (the convert rides the gather
    consumer, matching the fused-kernel semantics bit-for-bit in fp32)."""
    from ..attention import attention_xla

    B, nh, hd = q.shape
    _, nkv, bs, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    S = max_blocks * bs
    kg = k_pool[block_tables].swapaxes(2, 3).reshape(B, S, nkv, hd)
    vg = v_pool[block_tables].swapaxes(2, 3).reshape(B, S, nkv, hd)
    if window is not None:
        # same window >= 1 contract as the Pallas kernel
        if isinstance(window, (int, np.integer)):
            assert window >= 1, f"sliding window must be >= 1, got {window}"
        window = jnp.maximum(jnp.asarray(window, jnp.int32), 1)
    if k_scale is not None and k_scale.shape[-1] == 1:
        # one scale per (block, head, token) — the default group_size >= hd
        # config. Fold the scales into SCORE space instead of dequantizing
        # the [B, S, nkv, hd] gathered views: s_pos = (q · codes_pos) ·
        # k_scale_pos and out = (p · v_scale) @ v_codes, so the per-step
        # dequant work drops from O(S · hd) multiplies per head to O(S)
        sc = hd ** -0.5 if scale is None else scale
        g = nh // nkv
        qg = q.reshape(B, nkv, g, hd).astype(jnp.float32)
        ksg = k_scale[block_tables].swapaxes(2, 3).reshape(B, S, nkv)
        vsg = v_scale[block_tables].swapaxes(2, 3).reshape(B, S, nkv)
        s = jnp.einsum("bngh,bsnh->bngs", qg, kg.astype(jnp.float32)) * sc
        s = s * ksg.transpose(0, 2, 1)[:, :, None, :]       # [B, nkv, g, S]
        kv_pos = jnp.arange(S)[None, None, None, :]
        cl = context_lens[:, None, None, None]
        mask = kv_pos <= cl
        if window is not None:
            mask = mask & (kv_pos > cl - window)
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        p = p * vsg.transpose(0, 2, 1)[:, :, None, :]
        out = jnp.einsum("bngs,bsnh->bngh", p, vg.astype(jnp.float32))
        return out.reshape(B, nh, hd).astype(q.dtype)
    if k_scale is not None:
        from ..quantization import kv_dequantize_int8

        ng = k_scale.shape[-1]
        ksg = k_scale[block_tables].swapaxes(2, 3).reshape(B, S, nkv, ng)
        vsg = v_scale[block_tables].swapaxes(2, 3).reshape(B, S, nkv, ng)
        kg = kv_dequantize_int8(kg, ksg, q.dtype)
        vg = kv_dequantize_int8(vg, vsg, q.dtype)
    kv_pos = jnp.arange(S)[None, None, None, :]
    cl = context_lens[:, None, None, None]
    mask = kv_pos <= cl
    if window is not None:
        mask = mask & (kv_pos > cl - window)
    out = attention_xla(q[:, None], kg, vg, causal=False, mask=mask,
                        scale=scale)
    return out[:, 0]


# --------------------------------------------------------------------------- #
# fused speculative verification (inference.speculative.fused_verify;
# docs/serving.md "Fused verification"): score the [last_token, draft_1..k]
# rows of every sequence against the SAME block-table-indexed KV pools the
# decode kernel walks — t query rows per (sequence, kv-head) grid cell
# instead of one, row ti attending positions <= ctx + ti. Replaces the
# prefill-shaped ctx-offset dispatch (`engine_v2._verify_fn`), which
# re-materialized a dense [B, max_blocks*bs, ...] KV view of the WHOLE
# context at prefill width for every verify step. Composes with the int8
# dequant-in-register path exactly like the decode kernel.
# --------------------------------------------------------------------------- #
def _spec_verify_kernel(*refs, bs, scale, nblk, t, rpad, has_window,
                        quant=False):
    if quant:
        if has_window:
            (tables_ref, ctx_ref, wnd_ref, q_ref, k_ref, v_ref, ks_ref,
             vs_ref, o_ref, m_scr, l_scr, acc_scr) = refs
        else:
            (tables_ref, ctx_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
             o_ref, m_scr, l_scr, acc_scr) = refs
            wnd_ref = None
    elif has_window:
        (tables_ref, ctx_ref, wnd_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
    else:
        (tables_ref, ctx_ref, q_ref, k_ref, v_ref, o_ref,
         m_scr, l_scr, acc_scr) = refs
        wnd_ref = None
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    ctx = ctx_ref[b]
    # block j is live if ANY of the t rows can see it: the newest row
    # attends up to ctx + t - 1, the oldest row's window reaches back to
    # ctx - window + 1 (rows are g-major/t-minor: row r verifies draft
    # position r % t)
    if has_window:
        lo = ctx - wnd_ref[0]
        live = jnp.logical_and(j * bs < ctx + t, j * bs + bs - 1 > lo)
    else:
        live = j * bs < ctx + t

    @pl.when(live)
    def _compute():
        q = q_ref[...]                     # [rpad, hd]
        if quant:                          # int8 tile → q.dtype, in-register
            k = _dequant_tile(k_ref, ks_ref, q_ref.dtype)
            v = _dequant_tile(v_ref, vs_ref, q_ref.dtype)
        else:
            k = k_ref[...]                 # [bs, hd]
            v = v_ref[...]                 # [bs, hd]
        s = _mxu_dot(q, k, (((1,), (1,)), ((), ())),
                     preferred_element_type=jnp.float32) * scale
        pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ti = jax.lax.rem(jax.lax.broadcasted_iota(jnp.int32, s.shape, 0),
                         t)
        valid = pos <= ctx + ti            # row ti attends itself too
        if has_window:
            valid = jnp.logical_and(valid, pos > ctx + ti - wnd_ref[0])
        s = jnp.where(valid, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_curr = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_curr, m_prev.shape))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, :1])
        l_scr[...] = l_prev * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
        acc_scr[...] = acc_scr[...] * alpha[:, :1] + _mxu_dot(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == nblk - 1)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe[:, :1]).astype(o_ref.dtype)


def paged_spec_verify_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                                v_pool: jnp.ndarray,
                                block_tables: jnp.ndarray,
                                context_lens: jnp.ndarray, *,
                                scale: float = None,
                                window=None, k_scale=None,
                                v_scale=None) -> jnp.ndarray:
    """Fused speculative-verification attention over the paged pools.

    q ``[B, t, nh, hd]`` — row ti of sequence b sits at absolute position
    ``context_lens[b] + ti`` (the verify window ``[last_token,
    draft_1..t-1]``; its K/V must already be scattered into the pool, like
    the decode kernel's current token). Returns ``[B, t, nh, hd]``.
    ``window``/``k_scale``/``v_scale`` as in :func:`paged_decode_attention`.
    HBM traffic is exactly the live context per kv head — never a dense
    [B, max_blocks*bs, ...] gather."""
    B, t, nh, hd = q.shape
    nblocks, nkv, bs, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    g = nh // nkv
    # rows are g-major/t-minor, sublane-padded: row r = gi*t + ti
    rpad = max(8, -(-(g * t) // 8) * 8)
    scale = hd ** -0.5 if scale is None else scale
    has_window = window is not None
    quant = k_scale is not None
    assert quant == (v_scale is not None), \
        "k_scale and v_scale must be given together"
    if has_window:
        # same window >= 1 contract as the decode kernel
        if isinstance(window, (int, np.integer)):
            assert window >= 1, f"sliding window must be >= 1, got {window}"
        window = jnp.maximum(jnp.asarray(window, jnp.int32), 1)

    # [B, nkv, rpad, hd] row-folded query groups (head h = kv*g + gi)
    qg = q.reshape(B, t, nkv, g, hd).transpose(0, 2, 3, 1, 4) \
        .reshape(B, nkv, g * t, hd)
    qg = jnp.pad(qg, ((0, 0), (0, 0), (0, rpad - g * t), (0, 0)))

    kernel = functools.partial(_spec_verify_kernel, bs=bs,
                               scale=float(scale), nblk=max_blocks, t=t,
                               rpad=rpad, has_window=has_window, quant=quant)

    def qmap(b, h, j, *_):
        return (b, h, 0, 0)

    def kvmap(b, h, j, tables, ctx, *rest):
        # the newest verify row writes/reads position ctx + t - 1
        hi_blk = (ctx[b] + t - 1) // bs
        lo_blk = (jnp.maximum(ctx[b] - rest[0][0] + 1, 0) // bs
                  if rest else 0)
        j_eff = jnp.clip(j, lo_blk, hi_blk)
        return (jnp.clip(tables[b, j_eff], 0, nblocks - 1), h, 0, 0)

    in_specs = [
        pl.BlockSpec((None, None, rpad, hd), qmap),
        pl.BlockSpec((None, None, bs, hd), kvmap),
        pl.BlockSpec((None, None, bs, hd), kvmap),
    ]
    operands = [qg, k_pool, v_pool]
    if quant:
        ng = k_scale.shape[-1]
        in_specs += [pl.BlockSpec((None, None, bs, ng), kvmap),
                     pl.BlockSpec((None, None, bs, ng), kvmap)]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2 + int(has_window),
        grid=(B, nkv, max_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, rpad, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((rpad, 128), jnp.float32),
            pltpu.VMEM((rpad, 128), jnp.float32),
            pltpu.VMEM((rpad, hd), jnp.float32),
        ],
    )
    prefetch = [block_tables.astype(jnp.int32),
                context_lens.astype(jnp.int32)]
    if has_window:
        prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, rpad, hd), q.dtype),
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="paged_spec_verify",
    )(*prefetch, *operands)
    return out[:, :, :g * t].reshape(B, nkv, g, t, hd) \
        .transpose(0, 3, 1, 2, 4).reshape(B, t, nh, hd)


def paged_spec_verify_attention_xla(q: jnp.ndarray, k_pool: jnp.ndarray,
                                    v_pool: jnp.ndarray,
                                    block_tables: jnp.ndarray,
                                    context_lens: jnp.ndarray, *,
                                    scale: float = None,
                                    window=None, k_scale=None,
                                    v_scale=None) -> jnp.ndarray:
    """Dense-gather fallback with identical semantics — deliberately the
    SAME expressions as the multi-token prefill read path
    (``models/_paged.paged_attention_step``), so on CPU the fused-verify
    programs match the unfused ones and greedy streams stay
    token-identical."""
    from ..attention import attention_xla
    from ..quantization import kv_dequantize_int8

    B, t, nh, hd = q.shape
    _, nkv, bs, _ = k_pool.shape
    max_blocks = block_tables.shape[1]
    S = max_blocks * bs
    kg = k_pool[block_tables].swapaxes(2, 3).reshape(B, S, nkv, hd)
    vg = v_pool[block_tables].swapaxes(2, 3).reshape(B, S, nkv, hd)
    if k_scale is not None:
        ng = k_scale.shape[-1]
        ksg = k_scale[block_tables].swapaxes(2, 3).reshape(B, S, nkv, ng)
        vsg = v_scale[block_tables].swapaxes(2, 3).reshape(B, S, nkv, ng)
        kg = kv_dequantize_int8(kg, ksg, q.dtype)
        vg = kv_dequantize_int8(vg, vsg, q.dtype)
    positions = context_lens[:, None] + jnp.arange(t)[None, :]
    kv_pos = jnp.arange(S)[None, None, None, :]
    q_abs = positions[:, None, :, None]
    mask = kv_pos <= q_abs
    if window is not None:
        if isinstance(window, (int, np.integer)):
            assert window >= 1, f"sliding window must be >= 1, got {window}"
        window = jnp.maximum(jnp.asarray(window, jnp.int32), 1)
        mask = mask & (q_abs - kv_pos < window)
    return attention_xla(q, kg, vg, causal=False, mask=mask, scale=scale)


from ..registry import register  # noqa: E402

register("paged_decode_attention", backend="pallas")(paged_decode_attention)
register("paged_decode_attention", backend="xla")(paged_decode_attention_xla)
register("paged_spec_verify_attention",
         backend="pallas")(paged_spec_verify_attention)
register("paged_spec_verify_attention",
         backend="xla")(paged_spec_verify_attention_xla)
