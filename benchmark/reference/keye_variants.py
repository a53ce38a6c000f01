"""Four deliberately WRONG variants of the Keye reference, to show what a
comparison against the right one can see (``benchmark/tools/
wrong_reference_check.py`` and ``keye_check.py`` on the chip,
``tests/test_keye.py`` on the CPU). Each changes one thing a port of the
learned selection is likely to get wrong; none is ever what a cell is held to.

``logits(name, cfg, weights, tokens)`` takes the same arguments as
``keye.logits`` after the variant's name.
"""

from __future__ import annotations

import functools

from . import keye


def no_selection(scores, q_pos, k_pos, topk):
    """Dense attention: every causal key, whatever the indexer says."""
    del scores, topk
    return k_pos[None, :] <= q_pos[:, None]


def newest(scores, q_pos, k_pos, topk):
    """A sliding window: the newest ``topk`` keys in place of the learned
    ``topk``."""
    del scores
    back = q_pos[:, None] - k_pos[None, :]
    return (back >= 0) & (back < topk)


def form(name: str, cfg: dict):
    """``(cfg, select, rope_index)`` of the variant: what ``keye.attention``
    is given in the place of the right form's ``(cfg, learned_selection,
    True)`` (``keye.held`` takes the same triple)."""
    if name == "no_selection":
        return cfg, no_selection, True
    if name == "newest_topk":
        return cfg, newest, True
    if name == "half_topk":
        sa = dict(cfg["sa_config"])
        sa["topk"] //= 2
        return {**cfg, "sa_config": sa}, keye.learned_selection, True
    if name == "no_index_rope":
        return cfg, keye.learned_selection, False
    raise ValueError(f"no variant named {name!r}")


def logits(name: str, cfg: dict, weights, tokens, **kw):
    cfg, select, rope_index = form(name, cfg)
    return keye.logits(cfg, weights, tokens, layer_fn=functools.partial(
        keye.layer, select=select, rope_index=rope_index), **kw)


NAMES = ("no_selection", "newest_topk", "half_topk", "no_index_rope")
