"""Deliberately WRONG variants of the solar_open2 reference, to show what a
comparison against the right one can see (``benchmark/tools/
solar_open2_check.py`` on the chip, ``tests/test_solar_open2.py`` on the
CPU). Each changes one thing a port of this model is likely to get wrong;
none is ever what a cell is held to. (Two more wrong forms are the
PROGRAM's, not the reference's: its weights rounded to fp8, and a fault
planted in its single-token call alone - ``families/solar_open2.py``
``Program(weights=)``, ``tools/solar_open2_check.py``.)

``logits(name, cfg, weights, tokens)`` takes the same arguments as
``solar_open2.logits`` after the variant's name.
"""

from __future__ import annotations

import dataclasses

from . import solar_open2

FORMS = {
    # a gated delta rule's scalar gate: one decay a head (the channels' mean)
    "scalar_decay": {"channel_decay": False},
    # beta = sigmoid alone: the transition's eigenvalues stay >= 0
    "beta_without_2": {"beta_two": False},
    # q and k as the convolution left them
    "no_l2_norm": {"l2_norm": False},
    # no short convolution: silu of the projections alone
    "no_conv": {"conv": False},
    # a plain gated linear attention: nothing is read before it is written
    "linear_attention": {"delta_term": False},
    # no gate on a KDA layer's output
    "no_output_gate": {"output_gate": False},
    # none on a GQA layer's
    "no_gqa_gate": {"gqa_gate": False},
    # rotary at rope_theta in the GQA layers (the key is in the file)
    "rope": {"rope": True},
    # softmax over the experts where the key set's router is a sigmoid
    "softmax_router": {"sigmoid_router": False},
}
NAMES = tuple(FORMS)


def form(name: str) -> solar_open2.Form:
    if name not in FORMS:
        raise ValueError(f"no variant named {name!r}")
    return dataclasses.replace(solar_open2.RIGHT, **FORMS[name])


def logits(name: str, cfg: dict, weights, tokens, **kw):
    return solar_open2.logits(cfg, weights, tokens, form=form(name), **kw)
