"""Operations and bytes a model needs, computed from its shapes.

The functions every utilization metric divides by live here, with the
benchmark, so that no later PR can move a utilization by recounting. They
take the configuration as it is run (the published keys of a Hugging Face
``config.json``, depth already cut).
"""

from __future__ import annotations

BF16_BYTES = 2


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def attention_params(cfg: dict) -> int:
    """Matmul weights of one attention block: q, k, v and output."""
    h, hd = cfg["hidden_size"], head_dim(cfg)
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return h * nh * hd + 2 * h * nkv * hd + nh * hd * h


def ffn_params(cfg: dict) -> int:
    """Matmul weights of ONE SwiGLU feed-forward (one expert of a sparse
    layer): gate, up and down."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def experts(cfg: dict) -> int:
    return cfg.get("num_local_experts", 1)


def experts_per_token(cfg: dict) -> int:
    return cfg.get("num_experts_per_tok", 1)


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * experts(cfg) if experts(cfg) > 1 else 0


def head_params(cfg: dict) -> int:
    """The output head. The input embedding is a lookup, not a matmul, and
    is not counted (``bench.py:model_flops_per_token`` counted it: +19% at 2
    layers)."""
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params_per_token(cfg: dict) -> int:
    """Weights one token is multiplied by in a forward pass."""
    per_layer = (attention_params(cfg) + router_params(cfg)
                 + experts_per_token(cfg) * ffn_params(cfg))
    return cfg["num_hidden_layers"] * per_layer + head_params(cfg)


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward + backward operations one token requires, recomputation not
    counted: ``6 * matmul weights`` (2 forward, 4 backward) plus causal
    attention, ``6 * L * S * h`` - the scores and the weighted sum are each
    ``2 * S * h`` a token over the full square, the causal mask keeps half,
    and forward + backward is three times the forward."""
    attn = 6 * cfg["num_hidden_layers"] * seq_len \
        * cfg["num_attention_heads"] * head_dim(cfg)
    return 6.0 * matmul_params_per_token(cfg) + attn


def weight_bytes_read_per_decode_step(cfg: dict, batch: int) -> int:
    """Bytes of weights a decode step over ``batch`` sequences must read:
    every attention block, the head, and each expert that some token of the
    batch is routed to (all of them once ``batch * experts_per_token``
    reaches a few times the expert count, which the cells' batches do; for
    smaller batches the expected number of distinct experts under uniform
    routing)."""
    e, k = experts(cfg), experts_per_token(cfg)
    touched = e * (1.0 - (1.0 - k / e) ** batch) if e > 1 else 1.0
    per_layer = attention_params(cfg) + router_params(cfg) \
        + touched * ffn_params(cfg)
    return int((cfg["num_hidden_layers"] * per_layer + head_params(cfg))
               * BF16_BYTES)


def kv_bytes_per_token(cfg: dict) -> int:
    """Bytes of cached keys and values one context token holds."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * BF16_BYTES)


def decode_step_bytes(cfg: dict, batch: int, live_kv_tokens: float) -> float:
    """Least bytes one decode step reads from HBM: the weights it touches
    and the live keys and values of every sequence in the batch."""
    return weight_bytes_read_per_decode_step(cfg, batch) \
        + live_kv_tokens * kv_bytes_per_token(cfg)
