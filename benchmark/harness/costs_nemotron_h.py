"""Bytes and operations of the two mechanisms Nemotron-H changes, from THIS
configuration's published keys: what ``ssm_grouped_decode_roofline`` and
``moe_relu2_experts_roofline`` divide by. Beside ``costs_ssm.py`` and
``costs_moe.py`` and not in them: those read Granite's keys (``layer_types``,
``mamba_n_heads``) and count an expert as three matrices of SwiGLU; this
family spells its layers in ``hybrid_override_pattern``, names its Mamba
sizes ``mamba_num_heads`` / ``mamba_head_dim`` / ``ssm_state_size``, and an
expert is TWO matrices. The configuration is the one as it is run;
``num_experts`` is the experts HELD of the ``n_routed_experts`` routed.
"""

from __future__ import annotations

from .costs import BF16_BYTES

STATE_BYTES = {"float32": 4, "bfloat16": 2}
KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def layer_counts(cfg: dict) -> dict:
    """Layers of each kind, from ``hybrid_override_pattern``."""
    pattern = cfg["hybrid_override_pattern"]
    return {name: pattern.count(kind) for kind, name in KINDS.items()}


def state_bytes_per_row(cfg: dict, role: dict) -> int:
    """Bytes of ONE Mamba layer's recurrent state of ONE sequence: heads x
    head size x state size values in the role's ``state_dtype``. The
    convolution's tail and the row's B and C (1 KB each) are not counted:
    the floor is a little low, never high."""
    return (cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            * cfg["ssm_state_size"]
            * STATE_BYTES[role["program_options"]["state_dtype"]])


def decode_update_floor_bytes(cfg: dict, role: dict, rows: float) -> float:
    """Least bytes the single-token state update of ONE Mamba layer moves
    for ``rows`` sequences: each row's state read once and written once."""
    return 2.0 * rows * state_bytes_per_row(cfg, role)


def expert_params(cfg: dict) -> int:
    """Weights of ONE routed expert: up and down, two matrices."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def uniform_shares(cfg: dict) -> list:
    """The share of the rows that chooses each held expert under a uniform
    router: each row chooses ``num_experts_per_tok`` of ALL
    ``n_routed_experts`` (not of the held ones)."""
    return [cfg["num_experts_per_tok"] / cfg["n_routed_experts"]] \
        * cfg["num_experts"]


def held_experts_reached(cfg: dict, tokens: float, shares=None) -> float:
    """Of the experts held here, those ``tokens`` independent rows reach, in
    expectation: ``shares [held]`` is the share of the rows that chooses
    each (MEASURED over the run's probes where the reader has it -
    ``reference/nemotron_h.py routed_shares`` -; None: a uniform router's)."""
    shares = uniform_shares(cfg) if shares is None else shares
    return sum(1.0 - (1.0 - float(p)) ** tokens for p in shares)


def call_tokens(cfg: dict, rows_routed: float) -> float:
    """The token rows of a call whose span says ``rows_routed`` rows go to
    the HELD experts under a uniform router (``models/mixtral.py moe_rows``:
    ``rows * top_k * held // routed``; the floor's division is undone to
    within a row)."""
    return rows_routed * cfg["n_routed_experts"] \
        / (cfg["num_experts_per_tok"] * cfg["num_experts"])


def bank_floor_s(cfg: dict, tokens: float, peaks, shares=None) -> float:
    """Least time one layer's bank can take for a call over ``tokens`` token
    rows of which ``shares [held]`` choose each held expert
    (:func:`held_experts_reached`): the larger of the reached experts'
    weights over the HBM peak and the routed rows' useful operations (2 a
    weight a row) over the bf16 peak. Activations are not counted: a little
    low, never high."""
    shares = uniform_shares(cfg) if shares is None else shares
    weights = held_experts_reached(cfg, tokens, shares) \
        * expert_params(cfg) * BF16_BYTES
    routed = tokens * sum(float(p) for p in shares)
    return max(weights / peaks.hbm_bytes_per_s,
               2.0 * routed * expert_params(cfg) / peaks.bf16_flops)
