"""Bytes of a hybrid state-space model's recurrent state, from shapes: what
``ssm_decode_roofline`` divides by. Beside ``costs.py`` and not in it:
``costs.kv_bytes_per_token`` and ``weight_bytes_read_per_decode_step`` count
one attention block a layer of ``num_hidden_layers``, where this family has
two kinds of layer, counted here from ``layer_types``. The configuration is
the one as it is run (the published keys); the state's type is the role's.
"""

from __future__ import annotations

STATE_BYTES = {"float32": 4, "bfloat16": 2}


def layer_counts(cfg: dict) -> dict:
    """Layers of each kind, from ``layer_types``."""
    kinds = cfg["layer_types"]
    return {kind: kinds.count(kind) for kind in sorted(set(kinds))}


def state_bytes_per_row(cfg: dict, role: dict) -> int:
    """Bytes of ONE Mamba layer's recurrent state of ONE sequence: heads x
    head size x state size values in the role's ``state_dtype``. The
    convolution's tail (``d_conv - 1`` rows) is not counted: the floor is a
    little low, never high."""
    itemsize = STATE_BYTES[role["program_options"]["state_dtype"]]
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
            * itemsize)


def decode_update_floor_bytes(cfg: dict, role: dict, rows: float) -> float:
    """Least bytes the single-token state update of ONE Mamba layer moves
    for ``rows`` sequences: each row's state read once and written once."""
    return 2.0 * rows * state_bytes_per_row(cfg, role)
