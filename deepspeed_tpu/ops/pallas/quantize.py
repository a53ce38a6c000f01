"""Blockwise int8 quantize / dequantize Pallas kernels.

Reference parity: ``csrc/quantization/{quantize.cu,swizzled_quantize.cu,
quant_reduce.cu}`` (symmetric per-group int8 quantization used by ZeRO++
quantized-weight all-gather / quantized-gradient reduce) and the
``deepspeed/ops/quantizer`` binding. TPU-native version: per-group symmetric
int8 with fp32 scales, one row-block per grid step. XLA fallbacks for the same
op names are registered unconditionally in ``deepspeed_tpu/ops/quantization``;
the quantized-collective compositions (qwZ gather / qgZ all-to-all reduce)
build on these ops from the comm layer.

Group layout: the input is viewed as [n_groups, group_size]; each group gets
one fp32 scale = max(|x|)/127.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..registry import register
from ._common import dim_semantics as _dim_semantics
from ._common import (interpret as _interpret, pad_rows as _pad_rows,
                      row_block as _row_block)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = jnp.broadcast_to(scale, s_ref.shape)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = (q_ref[...].astype(jnp.float32)
                  * s_ref[...][:, :1]).astype(o_ref.dtype)


@register("quantize_int8", backend="pallas", rows=1)
def quantize_int8_pallas(x: jnp.ndarray, group_size: int = 2048):
    """x: any shape with size % group_size == 0 →
    (int8 values same shape, fp32 scales [n_groups])."""
    shape = x.shape
    x2, n = _pad_rows(x.reshape(-1, group_size))
    np_ = x2.shape[0]
    bn = _row_block(np_)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(np_ // bn,),
        in_specs=[pl.BlockSpec((bn, group_size), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bn, group_size), lambda i: (i, 0)),
                   pl.BlockSpec((bn, 128), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((np_, group_size), jnp.int8),
                   jax.ShapeDtypeStruct((np_, 128), jnp.float32)],
        compiler_params=_dim_semantics("parallel"),
        interpret=_interpret(),
        name="quantize_int8",
    )(x2)
    return q[:n].reshape(shape), s[:n, 0]


@register("dequantize_int8", backend="pallas", rows=2)
def dequantize_int8_pallas(q: jnp.ndarray, scales: jnp.ndarray,
                           group_size: int = 2048, dtype=jnp.float32):
    shape = q.shape
    q2, n = _pad_rows(q.reshape(-1, group_size))
    np_ = q2.shape[0]
    bn = _row_block(np_)
    s2, _ = _pad_rows(jnp.broadcast_to(scales[:, None], (n, 128)))
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(np_ // bn,),
        in_specs=[pl.BlockSpec((bn, group_size), lambda i: (i, 0)),
                  pl.BlockSpec((bn, 128), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, group_size), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, group_size), dtype),
        compiler_params=_dim_semantics("parallel"),
        interpret=_interpret(),
        name="dequantize_int8",
    )(q2, s2)
    return out[:n].reshape(shape)
