"""Bytes and operations of latent (MLA) attention over a cache of ONE row a
token a layer - the token's normed latent and its roped key - from shapes:
what ``mla_decode_roofline`` and ``mla_prefill_roofline`` divide by. Beside
``costs.py`` and not in it: ``costs.kv_bytes_per_token`` counts
``num_key_value_heads`` heads of keys and values, which this cache does not
hold. The configuration is the one as it is run.

Both counts are the ABSORBED form's, whichever implementation runs: a query
head meets a cached row as ``kv_lora_rank + qk_rope_head_dim`` numbers of
key and ``kv_lora_rank`` of value. A floor counts what the model needs: the
lanes a pool pads its rows with, the tiles a kernel computes and then masks,
and the ``W_uk`` / ``W_uv`` matmuls around the walk are not in it.
"""

from __future__ import annotations

from .costs import BF16_BYTES
from .costs_window import chunk_keys  # (row i of a chunk reads ctx + i + 1)


def latent_width(cfg: dict) -> int:
    """Numbers one token keeps a layer."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_row_bytes(cfg: dict) -> int:
    """Bytes of one token's row in one layer, unpadded."""
    return latent_width(cfg) * BF16_BYTES


def decode_kv_bytes(cfg: dict, kv_tokens_latent: float) -> float:
    """Least bytes the decode rows' walks of one call read from HBM:
    ``kv_tokens_latent`` - the rows' contexts, summed; ONE layer's count, the
    engine's span argument - in every layer."""
    return cfg["num_hidden_layers"] * kv_tokens_latent * latent_row_bytes(cfg)


def attn_flops_per_pair(cfg: dict) -> float:
    """Operations of one query head at one (row, key) pair: the score over
    the row's whole width and the weighted sum over its latent."""
    return 2.0 * (latent_width(cfg) + cfg["kv_lora_rank"])


def chunk_attn_flops(cfg: dict, ctx: int, tokens: int) -> float:
    """Useful operations of a prefill chunk's latent attention over every
    layer: every query head at every (row, key) pair the causal mask
    keeps."""
    return cfg["num_hidden_layers"] * cfg["num_attention_heads"] \
        * attn_flops_per_pair(cfg) * chunk_keys(ctx, tokens)


def full_kv_bytes_per_token_layer(cfg: dict) -> int:
    """What the EXPANDED heads' keys and values of one token would take in
    one layer: the cache this one stands in for."""
    return cfg["num_attention_heads"] * BF16_BYTES * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])
