"""Bytes and operations of a learned token selection (an indexer inside
attention: ``sa_config``), from shapes: what ``sparse_index_roofline`` and
``sparse_attn_roofline`` divide by, and the bytes of the cache that has a
third pool. Beside ``costs.py`` and not in it: ``costs.kv_bytes_per_token``
knows two pools and every key of the context. Everything is for ONE layer
of ONE call unless it says otherwise; the configuration is the one as it is
run. The floors count what the MODEL needs, whatever implements it: a kernel
that walks the whole context and masks reads low against them - the room a
gather over the selected pages has - and none can read over 100 %.
"""

from __future__ import annotations

from .costs import BF16_BYTES, head_dim


def index_key_bytes_per_token(cfg: dict) -> int:
    """Bytes of ONE layer's cached index key of one token: one key head."""
    sa = cfg["sa_config"]
    return sa["indexer_num_kv_heads"] * sa["indexer_head_dim"] * BF16_BYTES


def kv_bytes_per_token_layer(cfg: dict) -> int:
    """Bytes of ONE layer's cached keys and values of one token."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg) * BF16_BYTES


def cache_bytes_per_token(cfg: dict) -> int:
    """Bytes of cache one context token holds: K, V and the index key,
    every layer."""
    return cfg["num_hidden_layers"] * (kv_bytes_per_token_layer(cfg)
                                       + index_key_bytes_per_token(cfg))


def indexer_params(cfg: dict) -> int:
    """Matmul weights of one layer's indexer: WqI, WkI, Ww."""
    sa = cfg["sa_config"]
    return cfg["hidden_size"] * (
        sa["indexer_num_heads"] * sa["indexer_head_dim"]
        + sa["indexer_num_kv_heads"] * sa["indexer_head_dim"]
        + sa["indexer_num_heads"])


def layer_params(cfg: dict) -> dict:
    """Parameters of one layer as this share of the deployment holds it."""
    h, hd = cfg["hidden_size"], head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"attention": h * nh * hd + 2 * h * nkv * hd + nh * hd * h,
            "indexer": indexer_params(cfg),
            "router": h * cfg["num_local_experts"],
            "expert": 3 * h * cfg["moe_intermediate_size"],
            "experts_held": cfg["num_experts"]}


def weight_bytes(cfg: dict) -> int:
    """bf16 bytes of the weights this share holds: the layers (norms not
    counted) and the untied embedding and head."""
    p = layer_params(cfg)
    layer = p["attention"] + p["indexer"] + p["router"] \
        + p["experts_held"] * p["expert"]
    return BF16_BYTES * (cfg["num_hidden_layers"] * layer
                         + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def index_floor_s(cfg: dict, ctx_scored: float, longest: float,
                  peaks) -> float:
    """Least time ONE layer's index scores of one call can take. Its query
    rows score ``ctx_scored`` (row, cached token) pairs, ``2 x heads x head
    size`` operations each; the index keys of the call's sequences are read
    once (``longest``: the cached tokens whose keys the call must read - a
    chunk's context once, each decode row's own)."""
    sa = cfg["sa_config"]
    flops = 2.0 * ctx_scored * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    return max(longest * index_key_bytes_per_token(cfg)
               / peaks.hbm_bytes_per_s, flops / peaks.bf16_flops)


def attn_floor_s(cfg: dict, kv_selected: float, kv_read: float,
                 peaks) -> float:
    """Least time ONE layer's attention over the SELECTED tokens of one call
    can take: ``kv_selected`` (row, selected token) pairs at ``4 x heads x
    head size`` operations each (scores and the weighted sum), and
    ``kv_read`` selected tokens whose keys and values must be read (a decode
    row its ``min(context, topk)``; a chunk's rows share theirs: its context
    once)."""
    flops = 4.0 * kv_selected * cfg["num_attention_heads"] * head_dim(cfg)
    return max(kv_read * kv_bytes_per_token_layer(cfg)
               / peaks.hbm_bytes_per_s, flops / peaks.bf16_flops)
