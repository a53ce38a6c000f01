"""Rotary position embedding.

Reference parity: ``csrc/transformer/inference/csrc/apply_rotary_pos_emb.cu``
(bound through ``ops/transformer/inference/op_binding/rotary``). Pure-XLA here;
the elementwise rotation fuses into the surrounding matmuls on TPU.
"""

from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from .registry import op, register


def rope_frequencies(head_dim: int, max_len: int, theta: float = 10000.0,
                     dtype=jnp.float32):
    """Precompute cos/sin tables [max_len, head_dim/2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def yarn_inv_frequencies(head_dim: int, theta: float, factor: float,
                         original_max_len: int, beta_fast: float = 32.0,
                         beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's inverse frequencies ``[head_dim / 2]`` (arXiv:2309.00071, as
    DeepSeek-V2/V3 publish it): dimension ``i`` turns ``original_max_len *
    f_i / 2 pi`` times over the original context; the dimensions that turn
    more than ``beta_fast`` times keep ``f_i = theta ** (-2 i / d)``, those
    that turn fewer than ``beta_slow`` times are interpolated to ``f_i /
    factor``, and a linear ramp between the two correction dimensions
    (floor and ceil of ``d ln(L / (2 pi beta)) / (2 ln theta)``) blends the
    rest."""
    half = head_dim // 2
    freq = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)

    def correction_dim(turns):
        return head_dim * math.log(original_max_len / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (freq / factor * ramp + freq * (1 - ramp)).astype(np.float32)


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """``0.1 * mscale * ln(factor) + 1`` (1 at ``factor <= 1``): YaRN's
    attention temperature, by which a family scales its softmax scale
    (squared) or its cos / sin tables."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(head_dim: int, max_len: int, theta: float,
                     factor: float, original_max_len: int,
                     beta_fast: float = 32.0, beta_slow: float = 1.0,
                     table_scale: float = 1.0, dtype=jnp.float32):
    """cos/sin tables ``[max_len, head_dim / 2]`` at YaRN's frequencies
    (:func:`yarn_inv_frequencies`), BOTH times ``table_scale``: YaRN's
    attention temperature in either of the forms it is published in - the
    ``mscale / mscale_all_dim`` quotient of two :func:`yarn_mscale` (A.X-K1's
    latent heads) or an ``attention_factor`` given outright for a plain
    head (Mellum 2's full layers: 0.1 ln(factor) + 1, so the scores carry
    its square). Made once, as :func:`rope_frequencies`' are."""
    inv_freq = jnp.asarray(yarn_inv_frequencies(
        head_dim, theta, factor, original_max_len, beta_fast, beta_slow))
    freqs = jnp.outer(jnp.arange(max_len, dtype=jnp.float32), inv_freq)
    return ((jnp.cos(freqs) * table_scale).astype(dtype),
            (jnp.sin(freqs) * table_scale).astype(dtype))


@register("rotary_embed", backend="xla")
def apply_rotary_xla(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                     positions: jnp.ndarray = None) -> jnp.ndarray:
    """x: [..., seq, heads, head_dim]; cos/sin: [max_len, head_dim/2];
    positions: [..., seq] integer positions (defaults to arange)."""
    seq = x.shape[-3]
    if positions is None:
        c = cos[:seq]
        s = sin[:seq]
        # broadcast over leading batch dims and the heads dim
        c = c[:, None, :]
        s = s[:, None, :]
    else:
        c = cos[positions][..., :, None, :]
        s = sin[positions][..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


apply_rotary = op("rotary_embed")


def apply_rotary_interleaved(x: jnp.ndarray, cos: jnp.ndarray,
                             sin: jnp.ndarray,
                             positions: jnp.ndarray = None) -> jnp.ndarray:
    """GPT-J convention: rotate every two adjacent dims ((x0,x1), (x2,x3), …)
    instead of split halves. Reference: the v1 injection path handles both
    conventions in ``apply_rotary_pos_emb.cu`` (``rotate_every_two`` vs
    ``rotate_half``)."""
    seq = x.shape[-3]
    if positions is None:
        c = cos[:seq][:, None, :]
        s = sin[:seq][:, None, :]
    else:
        c = cos[positions][..., :, None, :]
        s = sin[positions][..., :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., ::2], xf[..., 1::2]
    out = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def apply_rotary_partial(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                         positions: jnp.ndarray = None, *,
                         rotary_dim: int = None,
                         interleaved: bool = False) -> jnp.ndarray:
    """Rotate only the first ``rotary_dim`` dims of the head (GPT-NeoX
    ``rotary_pct``, GPT-J ``rotary_dim``); the tail passes through."""
    rd = rotary_dim if rotary_dim is not None else x.shape[-1]
    rot_fn = apply_rotary_interleaved if interleaved else apply_rotary
    if rd >= x.shape[-1]:
        return rot_fn(x, cos, sin, positions)
    x_rot, x_pass = x[..., :rd], x[..., rd:]
    return jnp.concatenate([rot_fn(x_rot, cos, sin, positions), x_pass],
                           axis=-1)
