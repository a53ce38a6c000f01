"""Bytes and operations of attention over TWO KINDS of layer in one stack -
full layers that read the whole context and window layers that read the last
``sliding_window`` tokens - from shapes: what ``mixed_kv_decode_roofline``,
``mixed_kv_prefill_roofline`` and ``kv_resident_share`` divide by. Beside
``costs.py`` and not in it: ``costs.kv_bytes_per_token`` counts every layer
as holding the whole context. The configuration is the one as it is run
(``layer_types``: the published list, of which the first
``num_hidden_layers`` run).
"""

from __future__ import annotations

from .costs import BF16_BYTES, head_dim


def layers_of(cfg: dict) -> dict:
    """``{"full": n, "window": n}``: the layers of each kind that run."""
    types = cfg["layer_types"][:cfg["num_hidden_layers"]]
    window = sum(t == "sliding_attention" for t in types)
    return {"full": len(types) - window, "window": window}


def kv_bytes_per_token_layer(cfg: dict) -> int:
    """Bytes of cached keys and values ONE token holds in ONE layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BF16_BYTES


def decode_kv_bytes(cfg: dict, kv_tokens_full: float,
                    kv_tokens_window: float) -> float:
    """Least bytes the decode rows' walks of one call read from HBM:
    ``kv_tokens_full`` - the rows' contexts, summed - in every full layer and
    ``kv_tokens_window`` - each ``min(context, window)`` - in every window
    layer (the engine's span arguments, ONE layer's counts each)."""
    n = layers_of(cfg)
    return (n["full"] * kv_tokens_full + n["window"] * kv_tokens_window) \
        * kv_bytes_per_token_layer(cfg)


def chunk_keys(ctx: int, tokens: int, window=None) -> int:
    """Keys the ``tokens`` rows of a chunk at context offset ``ctx`` attend,
    summed over the rows: row ``i`` sits at position ``ctx + i`` and reads
    ``ctx + i + 1`` keys, or the last ``window`` of them."""
    if window is None:
        return tokens * ctx + tokens * (tokens + 1) // 2
    return sum(min(ctx + i + 1, window) for i in range(tokens))


def chunk_attn_flops(cfg: dict, ctx: int, tokens: int) -> float:
    """Useful operations of a prefill chunk's attention over every layer:
    ``q k^T`` and ``p v`` are each ``2 * head_dim`` operations a query head
    a (row, key) pair the mask keeps. What a kernel computes in tiles the
    mask then drops is not useful and is not counted."""
    n = layers_of(cfg)
    pairs = n["full"] * chunk_keys(ctx, tokens) \
        + n["window"] * chunk_keys(ctx, tokens, cfg["sliding_window"])
    return 4.0 * cfg["num_attention_heads"] * head_dim(cfg) * pairs


def resident_share(cfg: dict, kv_tokens_full: float,
                   kv_tokens_window: float) -> float:
    """Of the tokens ONE block table would keep resident - every layer the
    whole context - the share the two kinds keep: the whole context in the
    full layers, what lies inside the window in the window layers."""
    n = layers_of(cfg)
    return (n["full"] * kv_tokens_full + n["window"] * kv_tokens_window) \
        / ((n["full"] + n["window"]) * kv_tokens_full)
