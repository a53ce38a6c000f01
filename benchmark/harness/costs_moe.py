"""Bytes and operations of a sparse layer's EXPERT BANK, from shapes: what
``moe_experts_roofline`` divides by. Beside ``costs.py`` and not in it,
because ``costs.experts()`` reads ``num_local_experts`` alone and OLMoE
publishes ``num_experts``; this module reads either. Everything is for ONE
layer's bank over ONE call; the configuration is the one as it is run.
"""

from __future__ import annotations

from .costs import BF16_BYTES, ffn_params


def experts(cfg: dict) -> int:
    """Experts of one sparse layer, under either published name."""
    n = cfg.get("num_experts", cfg.get("num_local_experts"))
    if not n:
        raise KeyError("the configuration names no number of experts "
                       "(num_experts or num_local_experts)")
    return n


def experts_touched(cfg: dict, tokens: float) -> float:
    """Distinct experts that ``tokens`` tokens reach, each choosing
    ``num_experts_per_tok`` different ones, in expectation under uniform
    routing (what a random router gives): all of them once the call is a few
    times the expert count long, fewer for a small decode batch."""
    e, k = experts(cfg), cfg["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def bank_bytes(cfg: dict, tokens: float) -> float:
    """Bytes of expert weights one layer's bank must read for a call of
    ``tokens`` tokens: gate, up and down of every expert touched, in bf16.
    Activations are not counted (a few per cent of the weights at these
    widths), so the floor is a little low, never high."""
    return experts_touched(cfg, tokens) * ffn_params(cfg) * BF16_BYTES


def bank_flops(cfg: dict, rows_routed: float) -> float:
    """Useful operations of one layer's bank: each ROUTED row (a token times
    one of its experts) through one SwiGLU expert, 2 operations a weight.
    Rows an implementation computes beyond those (padding to a capacity) are
    not useful and are not counted."""
    return 2.0 * rows_routed * ffn_params(cfg)


def bank_floor_s(cfg: dict, rows_routed: float, peaks) -> float:
    """Least time one layer's bank can take for a call that routes
    ``rows_routed`` rows: the larger of its weights over the HBM peak and
    its useful operations over the bf16 peak."""
    tokens = rows_routed / cfg["num_experts_per_tok"]
    return max(bank_bytes(cfg, tokens) / peaks.hbm_bytes_per_s,
               bank_flops(cfg, rows_routed) / peaks.bf16_flops)
