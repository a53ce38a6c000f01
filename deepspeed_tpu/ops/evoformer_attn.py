"""DS4Sci EvoformerAttention equivalent (AlphaFold-style MSA attention).

Reference parity: ``csrc/deepspeed4science/evoformer_attn`` (CUTLASS kernels
behind ``DS4Sci_EvoformerAttention``, ``op_builder/evoformer_attn.py``) —
attention over the residue dimension of 5-D MSA tensors with up to two
additive biases (mask bias broadcast over heads/rows, and the pair bias).
On TPU the fused form is exactly what XLA produces from the einsum chain
(fp32 softmax accumulation, bf16 matmuls on the MXU); sequence lengths large
enough to need blockwise computation route through the shared flash-attention
kernel by reshaping rows into the batch dim.

Shapes (reference API): q/k/v [*, n_seq, n_res, heads, dim];
biases: list of arrays broadcastable to [*, n_seq, heads, n_res, n_res].
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from .registry import on_tpu

NEG_INF = -1e30


def evoformer_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        biases: Optional[Sequence[jnp.ndarray]] = None,
                        scale: Optional[float] = None,
                        use_kernel: Optional[bool] = None) -> jnp.ndarray:
    """softmax(q·kᵀ/√d + Σ biases)·v over the residue axis.

    q/k/v: [*, s, r, h, d] (MSA rows s, residues r). Returns same shape as q.

    Kernel path (default on TPU): MSA rows fold into the batch dim and the
    summed bias rides the flash kernel's additive-bias input. The score/probs
    matrices stay blockwise in VMEM (the XLA path materializes BOTH in fp32);
    the SUMMED fp32 bias is still materialized once — same footprint as one
    logits tensor — and dbias flows through the backward kernel (the DS4Sci
    kernel's differentiable pair bias). Per-input block-indexed biases (no
    summed materialization) are a future optimization.
    """
    *lead, s, r, h, d = q.shape
    scale = scale if scale is not None else d ** -0.5
    if use_kernel is None:
        use_kernel = on_tpu()
    if use_kernel:
        from .pallas.flash_attention import flash_attention

        fold = lambda x: x.reshape((-1, r, h, d))  # noqa: E731
        bias = None
        if biases:
            bias = sum(jnp.broadcast_to(b.astype(jnp.float32),
                                        tuple(lead) + (s, h, r, r))
                       for b in biases)
            bias = bias.reshape((-1, h, r, r))
        out = flash_attention(fold(q), fold(k), fold(v), causal=False,
                              scale=scale, bias=bias)
        return out.reshape(q.shape).astype(q.dtype)
    logits = jnp.einsum("...sqhd,...skhd->...shqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    for b in (biases or ()):
        logits = logits + b.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("...shqk,...skhd->...sqhd", probs.astype(v.dtype), v)
    return out.astype(q.dtype)


def msa_row_attention(msa: jnp.ndarray, wq, wk, wv, wo,
                      pair_bias: Optional[jnp.ndarray] = None,
                      mask: Optional[jnp.ndarray] = None,
                      num_heads: int = 8) -> jnp.ndarray:
    """MSA row-wise gated self-attention w/ pair bias (the op's main user in
    AlphaFold-style stacks). msa: [*, s, r, c]; pair_bias [*, h, r, r];
    mask [*, s, r] (1 = valid)."""
    *lead, s, r, c = msa.shape
    hd = c // num_heads
    q = (msa @ wq).reshape(*lead, s, r, num_heads, hd)
    k = (msa @ wk).reshape(*lead, s, r, num_heads, hd)
    v = (msa @ wv).reshape(*lead, s, r, num_heads, hd)
    biases: List[jnp.ndarray] = []
    if mask is not None:
        biases.append(jnp.where(mask[..., :, None, None, :].astype(bool),
                                0.0, NEG_INF))
    if pair_bias is not None:
        biases.append(pair_bias[..., None, :, :, :])
    out = evoformer_attention(q, k, v, biases)
    return out.reshape(*lead, s, r, c) @ wo


def msa_column_attention(msa: jnp.ndarray, wq, wk, wv, wo,
                         mask: Optional[jnp.ndarray] = None,
                         num_heads: int = 8) -> jnp.ndarray:
    """Column-wise attention = row attention on the transposed MSA."""
    msa_t = jnp.swapaxes(msa, -3, -2)
    mask_t = jnp.swapaxes(mask, -2, -1) if mask is not None else None
    out = msa_row_attention(msa_t, wq, wk, wv, wo, mask=mask_t,
                            num_heads=num_heads)
    return jnp.swapaxes(out, -3, -2)
