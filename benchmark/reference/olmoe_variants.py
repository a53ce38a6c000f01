"""Three deliberately WRONG variants of the OLMoE reference, to show what a
comparison against the right one can see
(``benchmark/tools/wrong_reference_check.py`` on the chip,
``tests/test_olmoe.py`` on the CPU). Each changes one thing a port of the
block is likely to get wrong; none is ever what a cell is held to.

``logits(name, cfg, weights, tokens)`` takes the same arguments as
``olmoe.logits`` after the variant's name.
"""

from __future__ import annotations

import functools

from . import blocks, olmoe


def per_head_norm(a, weight, heads, eps):
    """Each head's 128 outputs normed by themselves (Qwen3's kind of
    QK-norm, ``models/llama.py``'s ``qk_norm``), under the same weights."""
    s, width = a.shape
    per_head = a.reshape(s, heads, width // heads)
    return blocks.rms_norm(per_head, weight.reshape(heads, -1), eps) \
        .reshape(s, width)


class _RolledExperts:
    """The weights with every layer's experts moved on by one: a token's
    router weights then meet the wrong experts."""

    def __init__(self, weights):
        self._weights = weights

    def __getattr__(self, name):
        return getattr(self._weights, name)

    def layer(self, i: int) -> dict:
        w = dict(self._weights.layer(i))
        w["experts"] = w["experts"][1:] + w["experts"][:1]
        return w


def logits(name: str, cfg: dict, weights, tokens):
    if name == "per_head_norm":
        return olmoe.logits(cfg, weights, tokens, layer_fn=functools.partial(
            olmoe.layer, norm=per_head_norm))
    if name == "renormalised_gates":
        return olmoe.logits({**cfg, "norm_topk_prob": True}, weights, tokens)
    if name == "rolled_experts":
        return olmoe.logits(cfg, _RolledExperts(weights), tokens)
    raise ValueError(f"no variant named {name!r}")


NAMES = ("per_head_norm", "renormalised_gates", "rolled_experts")
