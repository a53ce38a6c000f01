"""Bytes and operations of a power-retention layer, from the configuration's
published keys: what ``retention_decode_roofline`` and
``retention_chunk_roofline`` divide by. Counted for the SYMMETRIC state -
``d (d + 1) / 2`` products a key-value head, the least a degree-2 state can
hold - in the role's state type, whatever layout the kernel streams (the
program's tiled one has 8704 rows where this counts 8256): the share then
reads the same work whatever implements it, and a kernel that streams a
wider layout reads that much under its roofline.
"""

from __future__ import annotations

STATE_BYTES = {"float32": 4, "bfloat16": 2}


def state_entries(cfg: dict) -> int:
    """``phi``'s entries a key-value head: ``d (d + 1) / 2``."""
    d = cfg["head_dim"]
    return d * (d + 1) // 2


def state_bytes_per_row(cfg: dict, role: dict) -> int:
    """Bytes of ONE layer's state of ONE sequence: a key-value head's ``S
    [D, d]`` and ``z [D]``, in the role's ``state_dtype``."""
    return (cfg["num_key_value_heads"] * state_entries(cfg)
            * (cfg["head_dim"] + 1)
            * STATE_BYTES[role["program_options"]["state_dtype"]])


def decode_update_floor_bytes(cfg: dict, role: dict, rows: float) -> float:
    """Least bytes the single-token update of ONE layer moves for ``rows``
    sequences: each row's state read once and written once. The token's own
    vectors (35 KB of ``phi`` a head) are not counted: a little low, never
    high."""
    return 2.0 * rows * state_bytes_per_row(cfg, role)


def chunk_flops(cfg: dict, rows: float) -> float:
    """Useful operations of ONE layer's chunked form over ``rows`` tokens of
    one sequence, the linear form's: every query head reads the state out
    (``phi(q)`` against ``S`` and ``z``: 2 D (d + 1) a token a head) and
    every key-value head adds to it (as many). What a kernel spends on the
    tokens of its own tile among themselves is its choice and not counted."""
    per_head = 2.0 * state_entries(cfg) * (cfg["head_dim"] + 1)
    return rows * per_head * (cfg["num_attention_heads"]
                              + cfg["num_key_value_heads"])


def chunk_floor_s(cfg: dict, role: dict, rows: float, peaks) -> float:
    """Least time ONE layer's chunked form can take over ``rows`` tokens of
    one sequence: the larger of its operations over the bf16 peak and one
    read and one write of the sequence's state over the HBM peak."""
    return max(chunk_flops(cfg, rows) / peaks.bf16_flops,
               decode_update_floor_bytes(cfg, role, 1) / peaks.hbm_bytes_per_s)
