"""Block-sparse attention with configurable layouts.

Reference parity: ``deepspeed/ops/sparse_attention`` (triton-era
BigBird/Longformer-style block-sparse attention; ``csrc/sparse_attention``).
TPU-first: the layout is a static [q_blocks, kv_blocks] boolean matrix baked
into the jit program as an additive mask — XLA prunes fully-masked blocks of
the fused attention when it tiles, and the Pallas flash kernel path can skip
them outright. Layout builders mirror the reference's config families:
``fixed`` (local + global strided), ``sliding_window``, ``bigbird``
(window + global + random).
"""

from __future__ import annotations

import functools

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .attention import attention
from .registry import on_tpu


def sliding_window_layout(num_blocks: int, window_blocks: int = 3,
                          causal: bool = True) -> np.ndarray:
    lay = np.zeros((num_blocks, num_blocks), bool)
    for i in range(num_blocks):
        lo = max(0, i - window_blocks + 1)
        hi = i + 1 if causal else min(num_blocks, i + window_blocks)
        lay[i, lo:hi] = True
    return lay


def fixed_layout(num_blocks: int, local_blocks: int = 4, stride: int = 4,
                 causal: bool = True) -> np.ndarray:
    """Reference 'fixed' sparsity: local chunks + every stride-th block."""
    lay = np.zeros((num_blocks, num_blocks), bool)
    for i in range(num_blocks):
        chunk = i // local_blocks
        lay[i, chunk * local_blocks:(chunk + 1) * local_blocks] = True
        lay[i, ::stride] = True
    if causal:
        lay &= np.tril(np.ones((num_blocks, num_blocks), bool))
    else:
        lay |= lay.T
    return lay


def bigbird_layout(num_blocks: int, window_blocks: int = 3,
                   global_blocks: int = 1, random_blocks: int = 2,
                   seed: int = 0, causal: bool = False) -> np.ndarray:
    lay = sliding_window_layout(num_blocks, window_blocks, causal=causal)
    lay[:, :global_blocks] = True
    lay[:global_blocks, :] = True
    rs = np.random.RandomState(seed)
    for i in range(num_blocks):
        lay[i, rs.choice(num_blocks, size=min(random_blocks, num_blocks),
                         replace=False)] = True
    if causal:
        lay &= np.tril(np.ones((num_blocks, num_blocks), bool))
    return lay


def _dense_masked(q, k, v, layout, block_size, causal, scale):
    s = q.shape[1]
    block_mask = jnp.asarray(layout)
    token_mask = jnp.repeat(jnp.repeat(block_mask, block_size, 0),
                            block_size, 1)  # [s, s]
    if causal:
        token_mask = token_mask & jnp.tril(jnp.ones((s, s), bool))
    return attention(q, k, v, causal=False,
                     mask=token_mask[None, None], scale=scale)


def blocksparse_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                          layout: np.ndarray, block_size: int,
                          causal: bool = True,
                          scale: Optional[float] = None,
                          use_kernel: Optional[bool] = None) -> jnp.ndarray:
    """q/k/v: [batch, seq, heads, head_dim]; layout [q_blocks, kv_blocks]
    (static). Tokens attend iff their blocks are connected AND (optionally)
    causally ordered.

    Kernel path (default on TPU): the Pallas block-sparse flash kernels SKIP
    inactive blocks in BOTH directions — the backward streams the same
    compacted block lists with the forward's saved logsumexp, so training
    compute and memory scale with layout density, not S²."""
    s = q.shape[1]
    if s % block_size:
        raise ValueError(f"seq {s} not divisible by block {block_size}")
    nb = s // block_size
    if layout.shape != (nb, nb):
        raise ValueError(f"layout {layout.shape} != ({nb},{nb})")
    from .pallas.sparse_attention import compact_layout

    # validates every q row keeps >=1 active block (empty-row softmax is
    # undefined — and the kernel fwd / dense bwd would disagree about it)
    compact_layout(layout, causal)
    if use_kernel is None:
        use_kernel = on_tpu()
    if not use_kernel:
        return _dense_masked(q, k, v, layout, block_size, causal, scale)
    lay = np.asarray(layout, bool)
    fn = _kernel_vjp(lay.tobytes(), lay.shape[0], block_size, causal,
                     None if scale is None else float(scale))
    return fn(q, k, v)


@functools.lru_cache(maxsize=64)
def _kernel_vjp(layout_bytes: bytes, nb: int, block_size: int, causal: bool,
                scale: Optional[float]):
    """One cached custom_vjp closure per (layout, geometry) — a per-call
    closure would defeat JAX's function-identity trace caches. Forward AND
    backward run the skipping Pallas kernels (round 5): the backward
    streams the same compacted block lists with the forward's saved lse,
    so sparse training cost scales with layout density, not S²."""
    from .attention import widen_kv
    from .pallas.sparse_attention import (_sparse_fwd_lse,
                                          sparse_flash_attention_bwd)

    lay = np.frombuffer(layout_bytes, bool).reshape(nb, nb)

    def _widened(q, k, v):
        h = q.shape[2]
        sc = q.shape[-1] ** -0.5 if scale is None else scale
        kw, vw = widen_kv(k, v, h)
        o, lse = _sparse_fwd_lse(q, kw, vw, lay, block_size, causal=causal,
                                 scale=sc)
        return o, lse, kw, vw, sc

    @jax.custom_vjp
    def _sparse(q, k, v):
        return _widened(q, k, v)[0]

    def _fwd(q, k, v):
        o, lse, _, _, _ = _widened(q, k, v)
        # residuals stay NARROW: k/v re-widen in _bwd (widen_kv is cheap,
        # the widened copies are h/hkv× the memory) and lse keeps one lane
        # of its 128-replicated layout
        return o, (q, k, v, o, lse[..., :1])

    def _bwd(res, g):
        q, k, v, o, lse1 = res
        h, hkv = q.shape[2], k.shape[2]
        sc = q.shape[-1] ** -0.5 if scale is None else scale
        kw, vw = widen_kv(k, v, h)
        lse = jnp.broadcast_to(lse1, lse1.shape[:-1] + (128,))
        dq, dk, dv = sparse_flash_attention_bwd(
            q, kw, vw, o, lse, g, lay, block_size, causal=causal, scale=sc)

        def narrow(dwide):
            if hkv == h:
                return dwide
            b, s, _, d = dwide.shape
            return dwide.reshape(b, s, hkv, h // hkv, d).sum(axis=3)

        return dq, narrow(dk), narrow(dv)

    _sparse.defvjp(_fwd, _bwd)
    return _sparse
