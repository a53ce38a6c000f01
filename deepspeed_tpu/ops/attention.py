"""Attention ops.

Reference parity: the fused softmax/attention CUDA kernels in
``csrc/transformer`` and the flash-attention integrations used by
``deepspeed/sequence`` / inference v2 ragged attention. Here:

- ``xla`` backend: straightforward softmax attention (fp32 accumulation,
  causal masking, GQA) — XLA fuses this well at moderate sequence lengths.
- ``pallas`` backend (``ops/pallas/flash_attention.py``): blockwise
  flash attention for long sequences, registered lazily on import.

All shapes are [batch, seq, heads, head_dim]; K/V may have fewer heads (GQA).
By default they are broadcast to the query head count (``repeat_kv`` — the
reference semantics). With ``attention.gqa_native`` enabled
(:func:`configure_gqa_native`; docs/performance.md "Native GQA attention")
K/V stay NARROW end to end: the Pallas flash kernels grow a kv-head grid
axis with the query-head group riding the MXU sublanes against ONE K/V tile
in VMEM, and the XLA path computes grouped einsums — up to nq/nkv× less KV
traffic through HBM in forward AND backward. ``repeat_kv`` survives only as
the XLA-fallback reference (gate off) and the Ulysses head-sharding
alignment widener (:func:`kv_alignment_heads`).
"""

from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax.numpy as jnp

from .registry import op, register

NEG_INF = -1e30

# --------------------------------------------------------------------------- #
# native-GQA gate (attention.gqa_native; docs/performance.md). Default OFF →
# every attention program is byte-identical to the widening implementation.
# Published process-wide by the runtime engine (latest engine wins, like
# activation_checkpointing.configure); DSTPU_GQA_NATIVE=1 arms it for
# engine-less probes (bench.py detail.attn_probe, scripts/attn_sweep.py).
# --------------------------------------------------------------------------- #
_GQA_NATIVE = {"on": False}


def configure_gqa_native(enabled: bool) -> bool:
    """Arm/disarm the native-GQA kernels process-wide; returns the previous
    setting so callers can restore it exactly."""
    prev = _GQA_NATIVE["on"]
    _GQA_NATIVE["on"] = bool(enabled)
    return prev


def gqa_native_active() -> bool:
    return _GQA_NATIVE["on"] or \
        os.environ.get("DSTPU_GQA_NATIVE", "") == "1"


def repeat_kv(k: jnp.ndarray, num_q_heads: int) -> jnp.ndarray:
    kv_heads = k.shape[-2]
    if kv_heads == num_q_heads:
        return k
    assert num_q_heads % kv_heads == 0
    return jnp.repeat(k, num_q_heads // kv_heads, axis=-2)


def widen_kv(k: jnp.ndarray, v: jnp.ndarray,
             num_q_heads: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """THE K/V head-widening helper — every call site that still broadcasts
    narrow K/V to the query head count routes through here (the one place
    the gqa-native lint has to watch)."""
    return repeat_kv(k, num_q_heads), repeat_kv(v, num_q_heads)


def kv_alignment_heads(num_kv_heads: int, num_q_heads: int,
                       group: int) -> int:
    """Smallest head count GQA-narrow K/V must widen to so it can shard
    over a ``group``-device head group: lcm(num_kv_heads, group). Falls
    back to full query width only when the lcm cannot tile the query heads
    (never the case when both divide num_q_heads) — with the native kernel
    active that fallback would throw away the narrow-KV win for no
    correctness gain, so it is the degenerate branch, not the default."""
    t = num_kv_heads * group // math.gcd(num_kv_heads, group)
    if t > num_q_heads or num_q_heads % t:
        return num_q_heads
    return t


def _causal_window_mask(q_len: int, kv_len: int, q_offset,
                        window: Optional[int]):
    """[q_len, kv_len] boolean visibility (True = attend) for the causal /
    sliding-window pattern — ONE definition shared by the plain and
    grouped XLA paths."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    m = q_pos >= kv_pos
    if window is not None:
        m = m & (q_pos - kv_pos < window)
    return m


def _attention_xla_grouped(q, k, v, *, causal, scale, mask, bias, q_offset,
                           window):
    """Grouped-einsum GQA attention — the gqa-native XLA path: K/V stay
    [*, kv_len, nkv, hd] and the query heads fold into a (nkv, g) split, so
    no q-width KV broadcast ever enters the program (the masked/cached
    model paths that can't take the flash kernel still avoid the nq/nkv×
    KV blow-up). Bit-for-bit it is the same math as the widened reference
    up to einsum reassociation."""
    q_len, num_heads = q.shape[-3], q.shape[-2]
    kv_len, kv_heads = k.shape[-3], k.shape[-2]
    g = num_heads // kv_heads
    # query head h = kv*g + gi (repeat_kv repeats each kv head g times
    # consecutively, so h // g is its kv head)
    q5 = q.reshape(q.shape[:-2] + (kv_heads, g, q.shape[-1]))
    logits = jnp.einsum("...qngd,...knd->...ngqk", q5, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        logits = jnp.where(_causal_window_mask(q_len, kv_len, q_offset,
                                               window),
                           logits, NEG_INF)
    def to_grouped(m):
        # [.., 1|nh, q, k] → broadcastable against [.., nkv, g, q, k]
        if m.shape[-3] == num_heads and g > 1:
            return m.reshape(m.shape[:-3] + (kv_heads, g) + m.shape[-2:])
        return m[..., None, :, :]
    if bias is not None:
        logits = logits + to_grouped(bias).astype(jnp.float32)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(to_grouped(mask), logits, NEG_INF)
        else:
            logits = logits + to_grouped(mask).astype(jnp.float32)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum("...ngqk,...knd->...qngd", probs.astype(v.dtype), v)
    # (values may be narrower than keys: a latent pool's)
    return out.reshape(q.shape[:-1] + v.shape[-1:]).astype(q.dtype)


@register("attention", backend="xla")
def attention_xla(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, scale: Optional[float] = None,
                  mask: Optional[jnp.ndarray] = None,
                  bias: Optional[jnp.ndarray] = None,
                  q_offset: int = 0,
                  window: Optional[int] = None) -> jnp.ndarray:
    """mask: optional [batch, 1|heads, q_len, kv_len] additive or boolean mask.
    bias: optional ADDITIVE logits term (same broadcast shape; differentiable).
    ``q_offset``: absolute position of q[0] within the kv sequence (decode /
    chunked long-seq paths). ``window``: optional sliding-window length
    (requires ``causal``): only kv positions in ``(q_pos - window, q_pos]``
    are visible."""
    q_len, num_heads = q.shape[-3], q.shape[-2]
    kv_len, kv_heads = k.shape[-3], k.shape[-2]
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if window is not None:
        assert causal, "window requires causal attention"
        assert window >= 1, f"sliding window must be >= 1, got {window}"
    if gqa_native_active() and kv_heads != num_heads:
        return _attention_xla_grouped(q, k, v, causal=causal, scale=scale,
                                      mask=mask, bias=bias,
                                      q_offset=q_offset, window=window)
    k, v = widen_kv(k, v, num_heads)
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        causal_mask = _causal_window_mask(q_len, kv_len, q_offset, window)
        logits = jnp.where(causal_mask, logits, NEG_INF)
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, NEG_INF)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True))
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
    out = jnp.einsum("...hqk,...khd->...qhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


_attention = op("attention")


def attention(q, k, v, *, mask: Optional[jnp.ndarray] = None, **kwargs):
    """The attention op. A ``mask`` is the XLA implementation's alone (the
    kernels mask causally and by length natively), so such a call never
    reaches a kernel — or the layout a kernel needs over a mesh."""
    if mask is not None:
        return attention_xla(q, k, v, mask=mask, **kwargs)
    return _attention(q, k, v, **kwargs)
