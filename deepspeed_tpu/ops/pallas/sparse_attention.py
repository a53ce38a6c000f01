"""Block-sparse flash attention Pallas kernel.

Reference parity: ``deepspeed/ops/sparse_attention`` (triton block-sparse
attention over fixed/bigbird/sliding-window layouts; ``csrc/sparse_attention``
utils). The layout ([q_blocks, kv_blocks] bool) is scalar-prefetched and the
kernel SKIPS inactive kv blocks outright — compute and HBM traffic scale with
layout density, not seq², which is the whole point of block sparsity (the
dense-masked XLA path still pays O(s²)).

Forward AND backward run skipping kernels (round 5): the backward streams
the same compacted active-block lists — dq over each q-row's list, dk/dv
over each kv-COLUMN's transposed list — recomputing p from the forward's
saved logsumexp exactly like the dense flash backward, so sparse TRAINING
is O(density·S²) in both compute and memory (the previous dense-masked
backward paid full O(S²) regardless of layout).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import dim_semantics as _dim_semantics
from ._common import interpret as _interpret
from ._common import mxu_dot as _mxu_dot

NEG_INF = -1e30


def _sparse_fwd_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                       m_scr, l_scr, acc_scr, *, scale, causal, bs, max_a):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # j indexes the COMPACTED active-block list for this q row; padded slots
    # (j >= count) repeat the last active block id, so their DMA is a cache
    # hit and their compute is skipped
    @pl.when(j < cnt_ref[qi])
    def _compute():
        ki = idx_ref[qi, j]
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = _mxu_dot(q, k, (((1,), (1,)), ((), ())),
                     preferred_element_type=jnp.float32) * scale
        if causal:
            # intra-block causal masking on the diagonal block
            q_idx = qi * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
            kv_idx = ki * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
            s = jnp.where(kv_idx <= q_idx, s, NEG_INF)

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

    @pl.when(j == max_a - 1)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe[:, :1]).astype(o_ref.dtype)
        lse_ref[0] = m_scr[...] + jnp.log(l_safe)


def _sparse_dq_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dq_scr, *, scale, causal, bs, max_a):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(j < cnt_ref[qi])
    def _compute():
        ki = idx_ref[qi, j]
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = _mxu_dot(q, k, (((1,), (1,)), ((), ())),
                     preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        if causal:
            # only the diagonal block needs intra-block masking (off-diagonal
            # active blocks are fully below the diagonal — compact_layout
            # culled everything above it)
            q_idx = qi * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
            kv_idx = ki * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
            p = jnp.where(kv_idx <= q_idx, p, 0.0)
        dp = _mxu_dot(do, v, (((1,), (1,)), ((), ())),
                      preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        dq_scr[...] += _mxu_dot(ds, k, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    @pl.when(j == max_a - 1)
    def _finish():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _sparse_dkv_kernel(idx_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                       scale, causal, bs, max_a):
    """Transposed stream: for kv block ki (grid dim 1), iterate the q blocks
    attending to it (idx_ref row ki holds that transposed list)."""
    ki = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(i < cnt_ref[ki])
    def _compute():
        qi = idx_ref[ki, i]
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, :1]
        delta = delta_ref[0][:, :1]
        s = _mxu_dot(q, k, (((1,), (1,)), ((), ())),
                     preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        if causal:
            q_idx = qi * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 0)
            kv_idx = ki * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, bs), 1)
            p = jnp.where(kv_idx <= q_idx, p, 0.0)
        dp = _mxu_dot(do, v, (((1,), (1,)), ((), ())),
                      preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dv_scr[...] += _mxu_dot(p.astype(do.dtype), do,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        dk_scr[...] += _mxu_dot(ds, q, (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)

    @pl.when(i == max_a - 1)
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def compact_layout(layout: np.ndarray, causal: bool) -> tuple:
    """[nb, nb] bool → (indices [nb, max_active], counts [nb]). Every q row
    must keep ≥1 active block (an empty row has no well-defined softmax)."""
    lay = np.asarray(layout, bool).copy()
    nb = lay.shape[0]
    if causal:
        lay &= np.tril(np.ones((nb, nb), bool))
    counts = lay.sum(axis=1)
    if (counts == 0).any():
        bad = np.nonzero(counts == 0)[0]
        raise ValueError(
            f"layout rows {bad.tolist()} attend to no kv block"
            f"{' after causal masking' if causal else ''} — softmax over an "
            f"empty row is undefined; give every q block at least one target")
    max_a = int(counts.max())
    idx = np.zeros((nb, max_a), np.int32)
    for i in range(nb):
        act = np.nonzero(lay[i])[0]
        idx[i, :len(act)] = act
        idx[i, len(act):] = act[-1]  # repeat → DMA reuse, compute skipped
    return idx, counts.astype(np.int32)


def compact_layout_t(layout: np.ndarray, causal: bool) -> tuple:
    """Transposed compaction for the dk/dv stream: row j lists the Q blocks
    attending to kv block j. Empty COLUMNS are legal (a kv block nobody
    attends to gets zero grads); padded slots repeat the last entry (or 0
    for empty columns — DMA'd but compute-skipped)."""
    lay = np.asarray(layout, bool).copy()
    nb = lay.shape[0]
    if causal:
        lay &= np.tril(np.ones((nb, nb), bool))
    counts = lay.sum(axis=0)
    max_a = max(1, int(counts.max()))
    idx = np.zeros((nb, max_a), np.int32)
    for j in range(nb):
        act = np.nonzero(lay[:, j])[0]
        if len(act):
            idx[j, :len(act)] = act
            idx[j, len(act):] = act[-1]
    return idx, counts.astype(np.int32)


def _to_bh(x, b, h, s, d):
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x, b, h, s, d):
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _sparse_fwd_lse(q, k, v, layout, block_size, *, causal, scale):
    """[B,S,H,D] widened inputs → (o [B,S,H,D], lse [B*H, S, 128])."""
    b, s, h, d = q.shape
    nb = s // block_size
    idx, counts = compact_layout(layout, causal)
    max_a = idx.shape[1]
    kernel = functools.partial(_sparse_fwd_kernel, scale=float(scale),
                               causal=causal, bs=block_size, max_a=max_a)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * h, nb, max_a),
        in_specs=[
            pl.BlockSpec((1, block_size, d),
                         lambda bh, i, j, idx, cnt: (bh, i, 0)),
            pl.BlockSpec((1, block_size, d),
                         lambda bh, i, j, idx, cnt: (bh, idx[i, j], 0)),
            pl.BlockSpec((1, block_size, d),
                         lambda bh, i, j, idx, cnt: (bh, idx[i, j], 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_size, d),
                         lambda bh, i, j, idx, cnt: (bh, i, 0)),
            pl.BlockSpec((1, block_size, 128),
                         lambda bh, i, j, idx, cnt: (bh, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_size, 128), jnp.float32),
            pltpu.VMEM((block_size, 128), jnp.float32),
            pltpu.VMEM((block_size, d), jnp.float32),
        ],
    )
    o, lse = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, s, 128), jnp.float32)],
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="sparse_flash_fwd",
    )(jnp.asarray(idx), jnp.asarray(counts), _to_bh(q, b, h, s, d),
      _to_bh(k, b, h, s, d), _to_bh(v, b, h, s, d))
    return _from_bh(o, b, h, s, d), lse


def sparse_flash_attention_fwd(q: jnp.ndarray, k: jnp.ndarray,
                               v: jnp.ndarray, layout: np.ndarray,
                               block_size: int, *, causal: bool = True,
                               scale: Optional[float] = None) -> jnp.ndarray:
    """q/k/v [B, S, H, D]; layout [S/bs, S/bs] (static bool). Returns o.
    Grid runs over the compacted active-block lists, so BOTH compute and
    DMA scale with layout density."""
    from ..attention import widen_kv

    b, s, h, d = q.shape
    k, v = widen_kv(k, v, h)
    scale = d ** -0.5 if scale is None else scale
    o, _ = _sparse_fwd_lse(q, k, v, layout, block_size, causal=causal,
                           scale=scale)
    return o


def sparse_flash_attention_bwd(q, k, v, o, lse, do, layout, block_size, *,
                               causal, scale):
    """Skipping backward: dq streams each q row's active list; dk/dv stream
    each kv COLUMN's transposed list. Inputs are head-widened [B,S,H,D]
    (+ lse [B*H,S,128]); returns (dq, dk_wide, dv_wide) — GQA narrowing is
    the caller's sum over query-head groups."""
    b, s, h, d = q.shape
    nb = s // block_size
    q_bh = _to_bh(q, b, h, s, d)
    k_bh = _to_bh(k, b, h, s, d)
    v_bh = _to_bh(v, b, h, s, d)
    do_bh = _to_bh(do, b, h, s, d)
    o_bh = _to_bh(o, b, h, s, d)
    delta = jnp.sum(do_bh.astype(jnp.float32) * o_bh.astype(jnp.float32),
                    axis=-1)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (128,))

    idx, counts = compact_layout(layout, causal)
    max_a = idx.shape[1]
    dq_kernel = functools.partial(_sparse_dq_kernel, scale=float(scale),
                                  causal=causal, bs=block_size, max_a=max_a)
    row_spec = pl.BlockSpec((1, block_size, d),
                            lambda bh, i, j, idx, cnt: (bh, i, 0))
    tbl_spec = pl.BlockSpec((1, block_size, d),
                            lambda bh, i, j, idx, cnt: (bh, idx[i, j], 0))
    stat_spec = pl.BlockSpec((1, block_size, 128),
                             lambda bh, i, j, idx, cnt: (bh, i, 0))
    dq = pl.pallas_call(
        dq_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * h, nb, max_a),
            in_specs=[row_spec, tbl_spec, tbl_spec, row_spec, stat_spec,
                      stat_spec],
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((block_size, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="sparse_flash_bwd_dq",
    )(jnp.asarray(idx), jnp.asarray(counts), q_bh, k_bh, v_bh, do_bh, lse,
      delta)

    idx_t, counts_t = compact_layout_t(layout, causal)
    max_t = idx_t.shape[1]
    dkv_kernel = functools.partial(_sparse_dkv_kernel, scale=float(scale),
                                   causal=causal, bs=block_size, max_a=max_t)
    col_spec = pl.BlockSpec((1, block_size, d),
                            lambda bh, j, i, idx, cnt: (bh, j, 0))
    tblq_spec = pl.BlockSpec((1, block_size, d),
                             lambda bh, j, i, idx, cnt: (bh, idx[j, i], 0))
    statq_spec = pl.BlockSpec((1, block_size, 128),
                              lambda bh, j, i, idx, cnt: (bh, idx[j, i], 0))
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b * h, nb, max_t),
            in_specs=[tblq_spec, col_spec, col_spec, tblq_spec, statq_spec,
                      statq_spec],
            out_specs=[col_spec, col_spec],
            scratch_shapes=[pltpu.VMEM((block_size, d), jnp.float32),
                            pltpu.VMEM((block_size, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), k.dtype),
                   jax.ShapeDtypeStruct((b * h, s, d), v.dtype)],
        compiler_params=_dim_semantics("parallel", "parallel", "arbitrary"),
        interpret=_interpret(),
        name="sparse_flash_bwd_dkv",
    )(jnp.asarray(idx_t), jnp.asarray(counts_t), q_bh, k_bh, v_bh, do_bh,
      lse, delta)
    return (_from_bh(dq, b, h, s, d), _from_bh(dk, b, h, s, d),
            _from_bh(dv, b, h, s, d))


from ..registry import register  # noqa: E402

register("sparse_attention_fwd", backend="pallas")(sparse_flash_attention_fwd)
