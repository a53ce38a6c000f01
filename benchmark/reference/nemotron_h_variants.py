"""Deliberately WRONG variants of the nemotron_h reference, to show what a
comparison against the right one can see (``benchmark/tools/
nemotron_h_check.py`` on the chip, ``tests/test_nemotron_h.py`` on the CPU).
Each changes one thing a port of this model is likely to get wrong; none is
ever what a cell is held to. (One more wrong form is the PROGRAM's, not the
reference's: its weights rounded to fp8 - ``families/nemotron_h.py``
``Program(weights=)``.)

``logits(name, cfg, weights, tokens)`` takes the same arguments as
``nemotron_h.logits`` after the variant's name.

One wrong reading is told apart by SHAPES and is no entry of ``FORMS``:
``d_inner`` from ``expand`` (``expand * hidden_size`` = 5376 where the
mixer's inner width is ``mamba_num_heads * mamba_head_dim`` = 4096). Its
``in_proj`` would have ``2 * 5376 + 2 * 8 * 128 + 64`` columns
(:func:`expand_in_proj_width`) where the published matrix has 10304: a port
that reads it so cannot load the weights.
"""

from __future__ import annotations

import dataclasses

from . import nemotron_h

FORMS = {
    # group 0's B and C for every head (n_groups read as 1)
    "one_bc_group": {"grouped_bc": False},
    # the gate's RMSNorm over the whole inner width, not a group at a time
    "whole_width_norm": {"grouped_norm": False},
    # Mamba-2's other order (norm_before_gate): the norm first
    "norm_before_gate": {"gate_then_norm": False},
    # the recurrent state kept in bfloat16
    "bf16_state": {"state_dtype": "bfloat16"},
    # relu where the experts have relu ** 2
    "relu": {"squared_relu": False},
    # a SwiGLU-shaped reading of the two matrices
    "gated": {"two_matrix": False},
    # the routed sum without routed_scaling_factor
    "no_route_scale": {"route_scale": False},
    # a softmax over the experts in the place of each expert's sigmoid
    "softmax_router": {"sigmoid_router": False},
    # the top k of the scores alone: the score-correction bias left out
    "no_choice_bias": {"bias_in_choice": False},
    # the bias in the gates as well as in the choice
    "biased_gates": {"bias_in_gates": True},
    # no shared expert
    "no_shared_expert": {"shared_expert": False},
    # rotary embedding at rope_theta, as every other decoder here applies it
    "rope": {"rope": True},
}
NAMES = tuple(FORMS)


def form(name: str) -> nemotron_h.Form:
    if name not in FORMS:
        raise ValueError(f"no variant named {name!r}")
    return dataclasses.replace(nemotron_h.RIGHT, **FORMS[name])


def logits(name: str, cfg: dict, weights, tokens, **kw):
    return nemotron_h.logits(cfg, weights, tokens, form=form(name), **kw)


def expand_in_proj_width(cfg: dict) -> int:
    """Columns ``in_proj`` would have were ``d_inner`` ``expand *
    hidden_size``: ``[z | xBC | dt]``."""
    d = cfg["expand"] * cfg["hidden_size"]
    return 2 * d + 2 * cfg["n_groups"] * cfg["ssm_state_size"] \
        + cfg["mamba_num_heads"]
