"""Deliberately WRONG variants of the axk1 reference, to show what a
comparison against the right one can see (``benchmark/tools/axk1_check.py``
on the chip, ``tests/test_axk1.py`` on the CPU). Each changes one thing a
port of this block is likely to get wrong; none is ever what a cell is held
to. (One more wrong form is the PROGRAM's, not the reference's: its weights
rounded to fp8 - ``families/axk1.py`` ``Program(weights=)``.)

``logits(name, cfg, weights, tokens)`` takes the same arguments as
``axk1.logits`` after the variant's name.
"""

from __future__ import annotations

import dataclasses

from . import axk1

FORMS = {
    # the plain top 8 of the 192, no group limit
    "ungrouped": {"grouped": False},
    # a softmax over the experts in the place of each expert's sigmoid
    "softmax_router": {"sigmoid_router": False},
    # the routed sum without routed_scaling_factor
    "no_route_scale": {"route_scale": False},
    # the softmax scale without YaRN's m ** 2
    "no_mscale": {"mscale": False},
    # plain rope: the published theta at every dim, no interpolation
    "plain_rope": {"yarn": False},
    # rope in the half-split convention (dimension i with i + d/2)
    "half_split_rope": {"interleaved_rope": False},
    # the rope key roped BEFORE the cache's norm, and normed with the latent
    "rope_before_norm": {"rope_after_norm": False},
    # no norm on the latent
    "no_latent_norm": {"latent_norm": False},
}
NAMES = tuple(FORMS)


def form(name: str) -> axk1.Form:
    if name not in FORMS:
        raise ValueError(f"no variant named {name!r}")
    return dataclasses.replace(axk1.RIGHT, **FORMS[name])


def logits(name: str, cfg: dict, weights, tokens, **kw):
    return axk1.logits(cfg, weights, tokens, form=form(name), **kw)
