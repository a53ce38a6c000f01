"""Deliberately WRONG variants of the zaya reference, to show what a
comparison against the right one can see (``benchmark/tools/zaya_check.py``
on the chip, ``tests/test_zaya.py`` on the CPU). Each changes one thing the
configuration file lists under ``assumed``, or one thing a port of this
model is likely to get wrong; none is ever what a cell is held to. (Two more
wrong forms are the PROGRAM's, not the reference's: its weights rounded to
fp8, and a fault planted in its single-token call alone -
``tools/zaya_check.py``.)

``logits(name, cfg, weights, tokens)`` takes the same arguments as
``zaya.logits`` after the variant's name. The two TAIL forms are faults of a
served program - what a call's first row reads of the tokens before it -
and take the positions its calls began at (``starts``:
``zaya.call_starts``).
"""

from __future__ import annotations

import dataclasses

from . import zaya

FORMS = {
    # q and k from the projections and their mean alone
    "no_conv": {"conv": False},
    # the second convolution a tap a channel: its blocks' diagonals
    "conv1_depthwise": {"conv1_grouped": False},
    # the convolution's output alone
    "no_qk_mean": {"qk_mean": False},
    # KV head 1 carries THIS token's second half
    "no_value_shift": {"value_shift": False},
    # the tail not carried from call to call: zeros where the pool's row
    # belongs, at every chunk boundary and every decode row
    "tail_dropped": {"tail": "dropped"},
    # the tail kept in a type narrower than the compute type
    "tail_fp8": {"tail": "float8_e4m3fn"},
    # q and k as the sum leaves them
    "no_l2_norm": {"l2_norm": False},
    # the keys' learned scale left out
    "no_tau": {"tau": False},
    # rope over all 128 dimensions of a head
    "rope_whole_head": {"rope_whole_head": True},
    # every layer's router from its own input alone
    "router_not_carried": {"carry_router": False},
    # the router's weights and rows rounded to bf16
    "router_bf16": {"router_bf16": True},
    # the chosen expert's gate 1 (top-1 renormalised)
    "gate_renormalised": {"renorm_gate": True},
    # the skip's rows through expert 0
    "skip_to_expert0": {"skip_to_expert0": True},
    # the residual stream's own scale left out
    "no_residual_scale": {"residual_scale": False},
}
NAMES = tuple(FORMS)
TAIL_FORMS = ("tail_dropped", "tail_fp8")


def form(name: str) -> zaya.Form:
    if name not in FORMS:
        raise ValueError(f"no variant named {name!r}")
    return dataclasses.replace(zaya.RIGHT, **FORMS[name])


def logits(name: str, cfg: dict, weights, tokens, starts=None, **kw):
    if name in TAIL_FORMS:
        assert starts is not None, "a tail form needs the calls' starts"
        kw["starts"] = starts
    return zaya.logits(cfg, weights, tokens, form=form(name), **kw)
