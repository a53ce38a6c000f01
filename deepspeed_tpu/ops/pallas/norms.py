"""Fused normalization Pallas kernels (rms_norm / layer_norm).

Reference parity: ``csrc/transformer/inference/csrc/rms_norm.cu`` and
``layer_norm.cu`` (bound via ``ops/transformer/inference/op_binding``). One
row-block per grid step, fp32 accumulation in VMEM, cast back to the input
dtype. Forward runs in Pallas; the backward is a hand-derived VJP evaluated
in XLA (a pure elementwise+reduce expression XLA fuses into one pass).
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


# --------------------------------------------------------------------------- #
# rms_norm
# --------------------------------------------------------------------------- #
def _rms_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rms_fwd_pallas(x2, w, eps):
    x2, n = _pad_rows(x2)
    np_, d = x2.shape
    bn = _row_block(np_)
    out = pl.pallas_call(
        functools.partial(_rms_kernel, eps=eps),
        grid=(np_ // bn,),
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, d), x2.dtype),
        compiler_params=_dim_semantics("parallel"),
        interpret=_interpret(),
        name="rms_norm_fwd",
    )(x2, w)
    return out[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rms(x2, w, eps):
    return _rms_fwd_pallas(x2, w, eps)


def _rms_vjp_fwd(x2, w, eps):
    return _rms_fwd_pallas(x2, w, eps), (x2, w)


def _rms_vjp_bwd(eps, res, dy):
    x2, w = res
    xf = x2.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    d = xf.shape[-1]
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    wdy = dyf * wf
    dx = r * wdy - xf * (r ** 3) * jnp.sum(wdy * xf, axis=-1, keepdims=True) / d
    dw = jnp.sum(dyf * xf * r, axis=0)
    return dx.astype(x2.dtype), dw.astype(w.dtype)


_rms.defvjp(_rms_vjp_fwd, _rms_vjp_bwd)


@register("rms_norm", backend="pallas", rows=1)
def rms_norm_pallas(x: jnp.ndarray, weight: jnp.ndarray,
                    eps: float = 1e-6) -> jnp.ndarray:
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    return _rms(x2, weight, float(eps)).reshape(x.shape)


# --------------------------------------------------------------------------- #
# layer_norm
# --------------------------------------------------------------------------- #
def _ln_kernel(x_ref, w_ref, b_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mean
    var = jnp.mean(jnp.square(xc), axis=-1, keepdims=True)
    y = xc * jax.lax.rsqrt(var + eps)
    y = y * w_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def _ln_fwd_pallas(x2, w, b, eps):
    x2, n = _pad_rows(x2)
    np_, d = x2.shape
    bn = _row_block(np_)
    out = pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(np_ // bn,),
        in_specs=[pl.BlockSpec((bn, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, d), x2.dtype),
        compiler_params=_dim_semantics("parallel"),
        interpret=_interpret(),
        name="layer_norm_fwd",
    )(x2, w, b)
    return out[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _ln(x2, w, b, eps):
    return _ln_fwd_pallas(x2, w, b, eps)


def _ln_vjp_fwd(x2, w, b, eps):
    # b itself is a residual only for its dtype (bias may differ from weight
    # in mixed-precision param trees); it is [d]-sized, so this is free.
    return _ln_fwd_pallas(x2, w, b, eps), (x2, w, b)


def _ln_vjp_bwd(eps, res, dy):
    x2, w, b = res
    b_dtype = b.dtype
    xf = x2.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    d = xf.shape[-1]
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    xc = xf - mean
    r = jax.lax.rsqrt(jnp.mean(jnp.square(xc), axis=-1, keepdims=True) + eps)
    xhat = xc * r
    wdy = dyf * wf
    dx = r * (wdy - jnp.mean(wdy, axis=-1, keepdims=True)
              - xhat * jnp.mean(wdy * xhat, axis=-1, keepdims=True))
    dw = jnp.sum(dyf * xhat, axis=0)
    db = jnp.sum(dyf, axis=0)
    return dx.astype(x2.dtype), dw.astype(w.dtype), db.astype(b_dtype)


_ln.defvjp(_ln_vjp_fwd, _ln_vjp_bwd)


@register("layer_norm", backend="pallas", rows=1)
def layer_norm_pallas(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray,
                      eps: float = 1e-5) -> jnp.ndarray:
    if bias is None:
        bias = jnp.zeros_like(weight)
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    return _ln(x2, weight, bias, float(eps)).reshape(x.shape)
