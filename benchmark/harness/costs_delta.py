"""Bytes and operations of a gated delta-rule (KDA) layer, from the
configuration's published keys: what ``delta_decode_roofline`` and
``delta_chunk_roofline`` divide by. Counted for the recurrence itself - a
``[dk, dv]`` state a head in the role's state type, and the three products a
token makes with it - whatever the program's kernels do on the way (the
chunked form's triangular systems among a tile's own tokens are its choice
and not counted): the share then reads the same work whatever implements
it.
"""

from __future__ import annotations

STATE_BYTES = {"float32": 4, "bfloat16": 2}


def layers(cfg: dict) -> int:
    """The layers that are no GQA layer."""
    n = cfg["num_hidden_layers"]
    return n - sum(1 for l in cfg["gqa_layers"] if l < n)


def state_entries(cfg: dict) -> int:
    """Numbers of ONE layer's state of ONE sequence: a ``[dk, dv]`` matrix a
    head (``dk = dv = linear_attn_config.head_dim``)."""
    lin = cfg["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] * lin["head_dim"]


def state_bytes_per_row(cfg: dict, role: dict) -> int:
    return state_entries(cfg) \
        * STATE_BYTES[role["program_options"]["state_dtype"]]


def decode_update_floor_bytes(cfg: dict, role: dict, rows: float) -> float:
    """Least bytes the single-token update of ONE layer moves for ``rows``
    sequences: each row's state read once and written once. The token's own
    vectors (its decay, key, value and query: 2 KB a head) are not counted:
    a little low, never high."""
    return 2.0 * rows * state_bytes_per_row(cfg, role)


def chunk_flops(cfg: dict, rows: float) -> float:
    """Useful operations of ONE layer's recurrence over ``rows`` tokens of
    one sequence: a token reads the decayed state at its key (``S^T k``),
    adds the rank-one correction (``k r^T``) and reads it at its query
    (``S^T q``) - 2 dk dv each a head."""
    return rows * 3 * 2.0 * state_entries(cfg)


def chunk_floor_s(cfg: dict, role: dict, rows: float, peaks) -> float:
    """Least time ONE layer's chunked form can take over ``rows`` tokens of
    one sequence: the larger of its operations over the bf16 peak and one
    read and one write of the sequence's state over the HBM peak."""
    return max(chunk_flops(cfg, rows) / peaks.bf16_flops,
               decode_update_floor_bytes(cfg, role, 1) / peaks.hbm_bytes_per_s)
