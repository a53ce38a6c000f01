"""Eight deliberately WRONG variants of the cohere2_moe reference, to show
what a comparison against the right one can see
(``benchmark/tools/cohere2_check.py`` on the chip, ``tests/
test_cohere2_moe.py`` on the CPU). Each changes one thing a port of this
block is likely to get wrong; none is ever what a cell is held to. (A ninth
wrong form is the PROGRAM's, not the reference's: a window block given back
one block early - ``families/cohere2_moe.py`` ``Program(release_early=)``.)

``logits(name, cfg, weights, tokens)`` takes the same arguments as
``cohere2_moe.logits`` after the variant's name.
"""

from __future__ import annotations

import dataclasses

from . import cohere2_moe

FORMS = {
    # a window on the full layers too: nothing reads the far context
    "window_on_full": {"window_on_full": True},
    # no window on the window layers: every layer reads it all
    "no_window": {"window_on_window": False},
    # rope on the full layers, which have no positional embedding
    "rope_on_full": {"rope_on_full": True},
    # rope in the half-split convention (dimension i with i + d/2)
    "half_split_rope": {"interleaved_rope": False},
    # a softmax over the experts in the place of each expert's sigmoid
    "softmax_router": {"sigmoid_router": False},
    # the shared experts summed, not averaged
    "shared_summed": {"shared_averaged": False},
    # a sequential block: the experts read LayerNorm(x + attention)
    "sequential_block": {"parallel_block": False},
    # an RMSNorm (no mean taken out) in the place of the LayerNorm
    "rms_norm": {"layer_norm": False},
}
NAMES = tuple(FORMS)


def form(name: str) -> cohere2_moe.Form:
    if name not in FORMS:
        raise ValueError(f"no variant named {name!r}")
    return dataclasses.replace(cohere2_moe.RIGHT, **FORMS[name])


def logits(name: str, cfg: dict, weights, tokens, **kw):
    return cohere2_moe.logits(cfg, weights, tokens, form=form(name), **kw)
