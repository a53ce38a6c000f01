"""The ``mellum`` family: Mellum2-12B-A2.5B's published keys ->
``deepspeed_tpu.models.mellum`` (a sequential block over window and full
attention layers, a rope table a kind, every layer under a 64-expert
softmax router), the configuration's rule for random weights (``init``),
and the parameter tree -> the plain reference's weights, read lazily: one
layer's matrices or ONE expert's cut out of the stack when asked for. The
program's module is loaded when a cell asks for it: no other family's
set-up pays for it.
"""

from __future__ import annotations

import collections.abc
import dataclasses
import os
import sys
import types

from . import mixed_program
from .cohere2_moe import _Each  # a lazy sequence of ONE expert's matrices

REFERENCE = "mellum"
CONFIG_FILE = "mellum2-12b-a2.5b-instruct.json"
# The rules of this configuration's random weights that could differ from
# the program's own ``init``, each chosen on the chip (``tools/
# mellum_check.py --gains / --router-gains``; the configuration's ``assumed``
# (``weights``) and PERF.md section 6, PR 61, have the readings):
# Wq and Wk each scaled by ``sqrt(QK_GAIN)``, so that a window layer's scores
# ``q . k / sqrt(128)`` spread by QK_GAIN where a fan-in draw gives 1 (a full
# layer's by ``attention_factor ** 2`` times that). 2.0 was tried: the right
# form's quiet row rose 6-14 x and a window one token off only 7 x. So 1.0.
QK_GAIN = 1.0
# Every router's columns times ROUTER_GAIN: a row's router logits spread over
# N(0, 2^2) where a fan-in draw gives N(0, 1). A trained softmax router
# separates its experts; at unit spread the eight chosen gates are all but
# equal, so the expert bf16 orders the other way (one row in two carries one,
# in some layer) is worth an eighth of a layer, and one served token in a
# thousand lay more than 0.4 under the reference's top. At 2.0 a probe's loud
# rows halve, its median row falls by a third and a window one token off
# reads LOUDER (0.039 where 1.0 read 0.031-0.035 on the same seed); at 3.0
# the right form's quiet row passes its limit at 12 k. It changes no shape,
# no byte and no row count.
ROUTER_GAIN = 2.0


def _program():
    try:
        from deepspeed_tpu.models import mellum
    except ImportError:
        from benchmark.harness.manifest import ManifestError

        raise ManifestError(
            "this program has no models/mellum.py: it cannot run the "
            "mellum family") from None
    return mellum


def module():
    """The program's module with ``init`` below in the place of its own
    (the harness draws a cell's weights by ``module().init``)."""
    return types.SimpleNamespace(**{**vars(_program()), "init": init})


def init(cfg, rng, gain=None, **kw):
    """The program's ``init`` with Wq and Wk at ``sqrt(gain)`` (None:
    ``QK_GAIN`` as it stands when the weights are drawn) and the routers'
    columns times ``ROUTER_GAIN``."""
    gain = QK_GAIN if gain is None else gain
    params = _program().init(cfg, rng, **kw)
    for name in ("wq", "wk"):
        w = params["layers"][name]
        params["layers"][name] = (w * gain ** 0.5).astype(w.dtype)
    moe = params["layers"]["moe"]
    moe["router"] = moe["router"] * ROUTER_GAIN
    return params


# published keys this family runs at ONE value: (key, the value, what the
# program would need for another)
PUBLISHED_AS = (
    ("model_type", "mellum", "another family's module"),
    ("attention_bias", False, "biases on the projections"),
    ("tie_word_embeddings", False, "a tied head"),
    ("hidden_act", "silu", "another activation in the experts"),
    ("norm_topk_prob", True, "raw top-k gates"),
    ("use_sliding_window", True, "a stack with no window kind"),
    ("max_window_layers", 0, "a rule beside layer_types for which layers "
                             "slide"),
)


def build_cfg(hf: dict, **program_options):
    """Every published size from the configuration file. ``num_local_experts``
    (ADDED: the configuration's ``assumed``) is the router's width and
    ``num_experts`` the experts held; here they are equal. A published key
    the program does not run as published is refused BY NAME, not dropped."""
    m = _program()
    for key, value, needs in PUBLISHED_AS:
        if hf[key] != value:
            raise ValueError(f"models/mellum.py runs {key} = {value!r} "
                             f"alone ({hf[key]!r} needs {needs})")
    if set(hf["mlp_layer_types"]) != {"sparse"}:
        raise ValueError("models/mellum.py has no dense feed-forward: "
                         "mlp_layer_types must be all 'sparse'")
    rope = hf["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    if full["rope_type"] != "yarn" or sliding["rope_type"] != "default" \
            or full["rope_theta"] != sliding["rope_theta"]:
        raise ValueError("models/mellum.py ropes its full layers by a YaRN "
                         "table and its window layers by the plain one at "
                         "the same theta: rope_parameters says otherwise")
    if hf.get("num_local_experts", hf["num_experts"]) != hf["num_experts"]:
        raise ValueError("models/mellum.py holds every expert: num_experts "
                         "must be the router's width, num_local_experts")
    layers = hf["num_hidden_layers"]
    return dataclasses.replace(
        m.MellumConfig(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["moe_intermediate_size"], num_layers=layers,
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        num_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        layer_types=tuple(hf["layer_types"][:layers]),
        sliding_window=hf["sliding_window"],
        max_seq_len=hf["max_position_embeddings"],
        rope_theta=float(sliding["rope_theta"]),
        rope_factor=float(full["factor"]),
        rope_original_max_len=full["original_max_position_embeddings"],
        rope_beta_fast=float(full["beta_fast"]),
        rope_beta_slow=float(full["beta_slow"]),
        attention_factor=float(full["attention_factor"]),
        rms_norm_eps=hf["rms_norm_eps"], **program_options)


class _Layer(collections.abc.Mapping):
    """One layer's weights under the reference's names, each cut out of the
    stacked tree when it is read."""

    _NAMES = {"attn_norm": "attn_norm", "q": "wq", "k": "wk", "v": "wv",
              "o": "wo", "ffn_norm": "mlp_norm"}

    def __init__(self, layers, i: int):
        moe = layers["moe"]
        self._make = {name: (lambda leaf=leaf: layers[leaf][i])
                      for name, leaf in self._NAMES.items()}
        self._make.update({
            "router": lambda: moe["router"][i],
            "experts": lambda: _Each(
                moe["w_up"].shape[1],
                lambda e: (moe["w_gate"][i, e], moe["w_up"][i, e],
                           moe["w_down"][i, e]))})

    def __getitem__(self, name):
        return self._make[name]()

    def __iter__(self):
        return iter(self._make)

    def __len__(self):
        return len(self._make)


class Weights:
    """The program's stacked parameter tree, read one layer at a time under
    the reference's names. ``program`` is the program these weights are
    served by, for the reference's comparison beyond the served tokens
    (``reference/mellum.py`` ``logits_and_margin``)."""

    def __init__(self, params, role=None):
        self._layers = params["layers"]
        self.embed = params["embed"]
        self.final_norm = params["final_norm"]
        self.head = params["lm_head"]           # [hidden, vocab]
        self.program = Program(params, role)

    def layer(self, i: int) -> _Layer:
        return _Layer(self._layers, i)


def serve_role(hf: dict) -> dict:
    """The serve role of this family's configuration file, at the rehearsal's
    sizes where ``hf`` has the rehearsal's widths."""
    from benchmark.harness import manifest

    data = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs",
                                           CONFIG_FILE))
    role = data["roles"]["serve"]
    reh = data["rehearsal"]
    if hf["hidden_size"] == reh["published"]["hidden_size"]:
        role = {**role,
                "engine": manifest.merge(role["engine"], reh["serve_engine"]),
                "held": manifest.merge(role["held"], reh["serve_held"])}
    return role


def Program(params, role=None, **kw):
    """This family's program beside its reference, run as the window runs it
    (``families/mixed_program.py``: every call a mixed call over the role's
    slots, other sequences live in the other slots)."""
    return mixed_program.MixedProgram(sys.modules[__name__], params, role,
                                      **kw)
