"""The ``solar_open2`` family: Solar-Open2-250B's published keys ->
``deepspeed_tpu.models.solar_open2`` (gated delta-rule layers on a per-slot
state pool beside gated rope-less GQA layers on the paged pool, every layer
under a 320-wide sigmoid router with a shared expert, one chip's share of
the bank switched on), and the parameter tree, stacked by KIND of layer, ->
the plain reference's weights, read lazily: one layer's matrices or ONE
expert's cut out of the stack when asked for (the engine holds 11 GB while a
probe's reference runs). The program's module is loaded when a cell asks for
it: no other family's set-up pays for it.

The configuration's random weights are the program's own ``init`` (fan-in
scaled normals, norm weights of one, the release's draws for ``A_log`` and
``dt_bias``, the choice bias zeros) with ONE rule of this configuration's own
(``ROUTER_GAIN``), said in the configuration's ``assumed`` (``weights``).
"""

from __future__ import annotations

import collections.abc
import dataclasses
import functools
import os
import sys
import types

from . import mixed_program
from .cohere2_moe import _Each  # a lazy sequence of ONE expert's matrices

REFERENCE = "solar_open2"
CONFIG_FILE = "solar-open2-250b.json"
# the one rule of this configuration's random weights that differs from the
# program's own ``init``: every router's columns times ROUTER_GAIN, so that a
# row's router logits spread over N(0, 2^2) where a fan-in draw gives N(0,
# 1). A trained sigmoid router separates its experts (DeepSeek-V3's scores
# run from ~0 to ~0.9); at unit spread the eight chosen scores all lie in
# 0.88-0.95, the gates are all but uniform, and "softmax in the sigmoid's
# place" - which chooses the same eight - moved the logits by 0.07-0.11
# where bf16's own expert flips move a probe's median row by 0.02-0.06 (on
# the chip, PERF.md section 6, PR 57): no limit had room on both sides. The
# gain changes no shape, no byte and no row count.
ROUTER_GAIN = 2.0


def _program():
    try:
        from deepspeed_tpu.models import solar_open2
    except ImportError:
        from benchmark.harness.manifest import ManifestError

        raise ManifestError(
            "this program has no models/solar_open2.py: it cannot run the "
            "solar_open2 family") from None
    return solar_open2


def module():
    """The program's module with ``init`` below in the place of its own
    (the harness draws a cell's weights by ``module().init``)."""
    return types.SimpleNamespace(**{**vars(_program()), "init": init})


def init(cfg, rng, **kw):
    """The program's ``init`` with every router's columns times
    ``ROUTER_GAIN``."""
    params = _program().init(cfg, rng, **kw)
    for kind in ("delta", "attn"):
        moe = params[kind]["moe"]
        moe["router"] = moe["router"] * ROUTER_GAIN
    return params


def build_cfg(hf: dict, **program_options):
    """Every published size from the configuration file; ``num_experts``
    (ADDED: the configuration's ``assumed``) is the experts HELD here of the
    ``n_routed_experts`` the router chooses among, ``experts_first`` (absent:
    0) the first of them. What the program does not have is refused, not
    dropped."""
    m = module()
    lin = hf["linear_attn_config"]
    for key in ("tie_word_embeddings", "use_rope", "first_k_dense_replace",
                "kda_use_full_proj"):
        if hf.get(key):
            raise ValueError(f"models/solar_open2.py has no {key}")
    if not (hf["use_gqa_gate"] and hf["kda_allow_neg_eigval"]
            and hf["n_shared_experts"] == 1 and hf["norm_topk_prob"]
            and lin.get("num_kv_heads") is None):
        raise ValueError("the configuration is not one "
                         "models/solar_open2.py runs as published")
    routed, held = hf["n_routed_experts"], hf["num_experts"]
    layers = hf["num_hidden_layers"]
    return dataclasses.replace(
        m.SolarOpen2Config(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        num_layers=layers,
        gqa_layers=tuple(l for l in hf["gqa_layers"] if l < layers),
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        delta_heads=lin["num_heads"], delta_head_dim=lin["head_dim"],
        delta_conv=lin["short_conv_kernel_size"],
        delta_rank=lin["head_dim"],
        intermediate_size=hf["moe_intermediate_size"],
        num_shared_experts=hf["n_shared_experts"],
        num_experts=routed, top_k=hf["num_experts_per_tok"],
        route_scale=float(hf["routed_scaling_factor"]),
        norm_topk_prob=hf["norm_topk_prob"],
        experts_held=None if held == routed
        else (hf.get("experts_first", 0), held),
        max_seq_len=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"], **program_options)


class _Layer(collections.abc.Mapping):
    """One layer's weights under the reference's names, each cut out of its
    kind's stack when it is read; ``kind`` is the layer's type."""

    _ATTENTION = {"norm": "norm", "q": "wq", "k": "wk", "v": "wv",
                  "gate": "w_gate", "o": "wo"}
    _DELTA = {"norm": "norm", "f2": "w_f2", "dt_bias": "dt_bias",
              "A_log": "A_log", "g2": "w_g2", "o_norm": "o_norm", "o": "wo"}

    def __init__(self, params, kind: str, j: int):
        stack = params["attn" if kind == "attention" else "delta"]
        at = lambda leaf: functools.partial(lambda: stack[leaf][j])
        self._make = {"kind": lambda: kind, "ffn_norm": at("ffn_norm")}
        if kind == "attention":
            self._make.update({name: at(leaf)
                               for name, leaf in self._ATTENTION.items()})
        else:
            self._make.update({name: at(leaf)
                               for name, leaf in self._DELTA.items()})
            # the program keeps [q | k | v] as one matrix (and one
            # convolution) and [f1 | g1 | b | zeros] as another
            d = stack["w_qkv"].shape[-1] // 3
            r = stack["w_f2"].shape[1]
            heads = stack["A_log"].shape[-1]
            for i, n in enumerate("qkv"):
                cut = slice(i * d, (i + 1) * d)
                self._make[n] = functools.partial(
                    lambda cut: stack["w_qkv"][j, :, cut], cut)
                self._make["conv_" + n] = functools.partial(
                    lambda cut: stack["conv_w"][j, :, cut], cut)
            for n, cut in (("f1", slice(0, r)), ("g1", slice(r, 2 * r)),
                           ("b", slice(2 * r, 2 * r + heads))):
                self._make[n] = functools.partial(
                    lambda cut: stack["w_low"][j, :, cut], cut)
        moe = stack["moe"]
        self._make.update({
            "router": lambda: moe["router"][j],
            "router_bias": lambda: moe["router_bias"][j],
            "experts": lambda: _Each(
                moe["w_up"].shape[1],
                lambda e: (moe["w_gate"][j, e], moe["w_up"][j, e],
                           moe["w_down"][j, e])),
            "shared": lambda: (moe["shared_w_gate"][j],
                               moe["shared_w_up"][j],
                               moe["shared_w_down"][j])})

    def __getitem__(self, name):
        return self._make[name]()

    def __iter__(self):
        return iter(self._make)

    def __len__(self):
        return len(self._make)


class Weights:
    """The program's parameter tree, stacked by kind, read one layer at a
    time under the reference's names: ``layer(kind, j)`` is the ``j``-th
    layer of its kind (the reference walks ``gqa_layers``). ``program`` is
    the program these weights are served by, for the reference's comparison
    beyond the served tokens (``reference/solar_open2.py``
    ``logits_and_margin``)."""

    def __init__(self, params, role=None):
        self._params = params
        self.embed = params["embed"]
        self.final_norm = params["final_norm"]
        self.head = params["lm_head"]           # [hidden, vocab]
        self.program = Program(params, role)

    def layer(self, kind: str, j: int) -> _Layer:
        return _Layer(self._params, kind, j)


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
