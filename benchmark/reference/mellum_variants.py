"""Deliberately WRONG variants of the mellum reference, to show what a
comparison against the right one can see (``benchmark/tools/mellum_check.py``
on the chip, ``tests/test_mellum.py`` on the CPU). Each changes one thing a
port of this model is likely to get wrong; none is ever what a cell is held
to. (Two more wrong forms are the PROGRAM's, not the reference's: its
weights rounded to fp8, and a fault planted in its single-token call alone -
``tools/mellum_check.py``.)

``logits(name, cfg, weights, tokens)`` takes the same arguments as
``mellum.logits`` after the variant's name.
"""

from __future__ import annotations

import dataclasses

from . import mellum

FORMS = {
    # the window on the full layers too
    "window_on_full": {"window_on_full": True},
    # none on the window layers: every layer reads the whole context
    "no_window": {"window_on_window": False},
    # the YaRN table (attention_factor and all) on the window layers
    "yarn_on_window": {"yarn_on_window": True},
    # the plain table on the full layers
    "plain_on_full": {"yarn_on_full": False},
    # YaRN's frequencies without attention_factor on cos and sin
    "no_attention_factor": {"attention_factor": False},
    # adjacent pairs rotated (GPT-J's convention) where the model splits
    # the head in halves
    "adjacent_rope": {"half_split": False},
    # the window one token short, and one long
    "window_1023": {"window_off_by": -1},
    "window_1025": {"window_off_by": 1},
    # the chosen experts' scores as the softmax left them
    "gates_not_normalised": {"norm_gates": False},
    # a per-head RMSNorm on q and k (the key set is close to one whose
    # models carry it with no key of their own)
    "qk_norm": {"qk_norm": True},
}
NAMES = tuple(FORMS)


def form(name: str) -> mellum.Form:
    if name not in FORMS:
        raise ValueError(f"no variant named {name!r}")
    return dataclasses.replace(mellum.RIGHT, **FORMS[name])


def logits(name: str, cfg: dict, weights, tokens, **kw):
    return mellum.logits(cfg, weights, tokens, form=form(name), **kw)
