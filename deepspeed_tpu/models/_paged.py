"""Shared paged-KV attention step — the one copy of the v2 block-table
protocol every family's ``apply_paged`` builds on.

Contract (see ``models/llama.py`` for the layout): the KV pool is
``[num_blocks, kv_heads, block_size, hd]`` per layer (last two dims are the
decode kernel's per-block tile — TPU tiling legal), block tables are
fixed-width ``[b, max_blocks]`` indices into the pool, block 0 is the trash
block that absorbs writes for padded tokens, and ``positions`` are absolute
token positions (``context_lens + arange(t)``).

Quantized KV mode (``inference.kv_quant``, docs/serving.md "Quantized KV
cache"): the cache dict additionally carries ``k_scale``/``v_scale`` pools
``[num_blocks, kv_heads, block_size, ngroups]`` fp32, K/V pools hold int8
codes, and :func:`paged_attention_step` receives each pool as a
``(codes, scales)`` tuple (:func:`scan_layers`). Fill-time quantization is
fused into the cache-update scatter (per-token groupwise scales — a token's
write never touches another position's scale), and dequant is fused into
the attention reads: in-register inside both Pallas kernels (``paged_decode``
for one query token, ``paged_prefill`` for more). There is NO standalone
int8→bf16 convert pass over the pool — QUANT_TPU_LIVE.json shows that path
losing to bf16 outright.

Reads: both kernels walk the block table over the live context
(``ops/pallas/paged_attention.py``). Nothing here gathers a dense view of
the pool; only the ops' XLA references do, and the registry picks those off
a TPU alone.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.quantization import kv_quantize_int8


def init_paged_pools(num_layers: int, num_blocks: int, num_kv_heads: int,
                     block_size: int, head_size: int, dtype=jnp.bfloat16,
                     kv_quant_group: Optional[int] = None):
    """The one cache-pool constructor every family's ``init_paged_cache``
    delegates to. Plain mode returns the historical ``{"k", "v"}`` dict;
    with ``kv_quant_group`` set (``inference.kv_quant.group_size``, clamped
    to ``head_size``) the pools hold int8 codes plus fp32
    ``[L, num_blocks, nkv, bs, ngroups]`` scale pools beside them — the
    per-block scale table that every block-lifecycle op (COW copy, fork,
    spill, truncate) carries automatically because it is part of the cache
    pytree. Scales init to ZERO so unwritten positions and the trash block
    dequantize to exactly the bf16 pool's zeros."""
    shape = (num_layers, num_blocks, num_kv_heads, block_size, head_size)
    if kv_quant_group is None:
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    gs = min(int(kv_quant_group), head_size)
    if gs < 1 or head_size % gs:
        raise ValueError(
            f"kv_quant.group_size {kv_quant_group} does not divide "
            f"head_size {head_size}")
    sshape = shape[:-1] + (head_size // gs,)
    return {"k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32)}


def _split_kv(cache):
    """From the cache dict to :func:`paged_attention_step`'s K/V entries:
    plain pools stay arrays; quantized pools (``k_scale`` present) become
    ``(codes, scales)`` tuples so ``lax.scan`` threads codes AND scales per
    layer with no per-family plumbing. Returns ``(k_entry, v_entry)``."""
    if "k_scale" in cache:
        return ((cache["k"], cache["k_scale"]),
                (cache["v"], cache["v_scale"]))
    return cache["k"], cache["v"]


def _join_kv(k_entry, v_entry):
    """Inverse of :func:`_split_kv`: rebuild the cache dict from the scan's
    stacked per-layer outputs."""
    if isinstance(k_entry, tuple):
        return {"k": k_entry[0], "k_scale": k_entry[1],
                "v": v_entry[0], "v_scale": v_entry[1]}
    return {"k": k_entry, "v": v_entry}


def scan_layers(body, x, layers, cache, *extras):
    """The pools' way through a program's layers, for every family: the
    stacked ``layers`` are scanned with each layer's slice of the K/V pools
    (and any per-layer ``extras`` - exaone4's windows and rope flags) as
    scanned INPUTS, and the updated slices are stacked back on the way out.
    ``body(x, (layer, k_entry, v_entry, *extras))`` returns
    ``(x, (k_entry, v_entry))`` with the entries as
    :func:`paged_attention_step` hands them back. Returns ``(x, cache)``.

    What the scan itself adds around the blocks is pool traffic - each
    layer's slice of the pools in, the updated slices stacked back - so it
    carries the pool update's name; the blocks' own scopes lie inside it.
    Whoever changes how the pools travel (a donated carry, ``[L, ...]``
    pools the kernels index: ROADMAP Queue A2) changes it here."""
    with jax.named_scope("kv_write"):
        x, (new_k, new_v) = lax.scan(
            body, x, (layers,) + _split_kv(cache) + extras)
    return x, _join_kv(new_k, new_v)


def paged_attention_step(q, k, v, k_cache, v_cache, block_tables,
                         context_lens, positions, valid, *,
                         window=None) -> Tuple:
    """Scatter this step's K/V into the block pool, then attend over it.

    q [b, t, nh, hd]; k/v [b, t, nkv, hd]. ``k_cache``/``v_cache`` are
    either plain pools or ``(codes, scales)`` tuples (:func:`scan_layers` —
    quantized KV mode). ``window``: optional per-layer sliding-window length
    (int or traced scalar — exaone4 scans per-layer windows). Single-token
    decode dispatches the paged flash-decode kernel, every multi-token call
    the paged flash-prefill kernel (each windowed or plain-causal, with the
    dequant fused in quantized mode); ``valid`` [b, t] is a prefix mask of
    each sequence's real rows, and a padded row's output is unspecified.
    Returns (attn_out [b, t, nh, hd], k_cache, v_cache) with the cache
    entries in the same representation they arrived in."""
    b, t = q.shape[0], q.shape[1]
    nkv, hd = k.shape[-2], k.shape[-1]
    quant = isinstance(k_cache, tuple)
    if quant:
        k_codes, k_scales = k_cache
        v_codes, v_scales = v_cache
        bs = k_codes.shape[2]
        group_size = hd // k_scales.shape[-1]
    else:
        bs = k_cache.shape[2]

    with jax.named_scope("kv_write"):   # the pool update, by its own name
        blk_idx = jnp.take_along_axis(block_tables, positions // bs, axis=1)
        blk_idx = jnp.where(valid, blk_idx, 0)
        off = positions % bs
        # advanced indices (blk_idx, off) straddle the kv-head slice, so the
        # result dims land in front: [b, t, nkv, hd] — exactly k's layout
        if quant:
            # fill-time quantization fused into the cache-update: codes and
            # the per-(token, head, group) scales scatter in the same program
            qk, sk = kv_quantize_int8(k, group_size)
            qv, sv = kv_quantize_int8(v, group_size)
            k_codes = k_codes.at[blk_idx, :, off].set(qk)
            v_codes = v_codes.at[blk_idx, :, off].set(qv)
            k_scales = k_scales.at[blk_idx, :, off].set(sk)
            v_scales = v_scales.at[blk_idx, :, off].set(sv)
        else:
            k_cache = k_cache.at[blk_idx, :, off].set(k.astype(k_cache.dtype))
            v_cache = v_cache.at[blk_idx, :, off].set(v.astype(v_cache.dtype))

    from ..ops import pallas as _pallas_ops  # noqa: F401 (registers)
    from ..ops.registry import get_op

    # int8 pools reach the kernels as codes with their scale pools beside
    # them, dequantized in-register
    if quant:
        pools = (k_codes, v_codes)
        scales = {"k_scale": k_scales, "v_scale": v_scales}
    else:
        pools, scales = (k_cache, v_cache), {}
    if t == 1:
        out = get_op("paged_decode_attention")(
            q[:, 0], *pools, block_tables, context_lens, window=window,
            **scales)[:, None]
    else:
        # every multi-token call - a prefill chunk at a context offset, a
        # batched prefill, a prefix-cache suffix, a speculative verify
        # window - walks the block table over the live context in one flash
        # kernel. Off a TPU the op is the gathered XLA reference, as for
        # every op. ``valid`` is a prefix mask, so its sum is each
        # sequence's count of real rows.
        n_valid = jnp.sum(valid, axis=1, dtype=jnp.int32)
        out = get_op("paged_prefill_attention")(
            q, *pools, block_tables, context_lens, n_valid, window=window,
            **scales)
    if quant:
        return out, (k_codes, k_scales), (v_codes, v_scales)
    return out, k_cache, v_cache
