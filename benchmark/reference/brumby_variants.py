"""Deliberately WRONG variants of the brumby reference, to show what a
comparison against the right one can see (``benchmark/tools/brumby_check.py``
on the chip, ``tests/test_brumby.py`` on the CPU). Each changes one thing a
port of this model is likely to get wrong; none is ever what a cell is held
to. (One more wrong form is the PROGRAM's, not the reference's: its weights
rounded to fp8 - ``families/brumby.py`` ``Program(weights=)``.)

``logits(name, cfg, weights, tokens)`` takes the same arguments as
``brumby.logits`` after the variant's name.
"""

from __future__ import annotations

import dataclasses

from . import brumby

FORMS = {
    # plain gated linear attention: q . k where the release squares it
    "degree_1": {"degree": 1},
    # nothing is forgotten: g = 1
    "no_gate": {"gate": False},
    # the sum without its quotient (no ``sum_of_keys``)
    "no_normaliser": {"normaliser": False},
    # key-value head 0's gate for every head (the gate read as one scalar a
    # token)
    "one_gate": {"gate_per_head": False},
    # Qwen3's own layer: causal softmax attention of q k^T / sqrt(d)
    "softmax_attention": {"softmax": True},
    # no per-head RMSNorm of q and k (Qwen2's projections)
    "no_qk_norm": {"qk_norm": False},
    # no rotary embedding (a linear-attention layer often has none)
    "no_rope": {"rope": False},
    # the recurrent state rounded to bfloat16 after every token
    "bf16_state": {"state_dtype": "bfloat16"},
}
NAMES = tuple(FORMS)


def form(name: str) -> brumby.Form:
    if name not in FORMS:
        raise ValueError(f"no variant named {name!r}")
    return dataclasses.replace(brumby.RIGHT, **FORMS[name])


def logits(name: str, cfg: dict, weights, tokens, **kw):
    return brumby.logits(cfg, weights, tokens, form=form(name), **kw)
