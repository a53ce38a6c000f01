"""The ``granite_hybrid`` family: the published ``granitemoehybrid`` keys ->
``deepspeed_tpu.models.granite_hybrid`` (loaded when a cell asks for it, as
the program loads it), the configuration's rule for random weights
(``init``), and the parameter tree, stacked by KIND of layer, -> the plain
reference's weights, one layer at a time in the order ``layer_types``
gives."""

from __future__ import annotations

import dataclasses
import math
import types

REFERENCE = "granite_hybrid"
OUT_PROJECTIONS = (("mamba", "out_proj"), ("mamba", "w_out"),
                   ("attn", "wo"), ("attn", "w_out"))


def _program():
    from deepspeed_tpu.models import granite_hybrid

    return granite_hybrid


def module():
    """The program's module with ``init`` below in the place of its own:
    the harness draws a cell's weights by ``module().init`` and takes every
    other name as the program has it."""
    return types.SimpleNamespace(**{**vars(_program()), "init": init})


def init(cfg, rng, **kw):
    """The program's ``init`` with each layer's two OUTPUT projections
    (``out_proj`` / ``wo``, ``w_out``) ``out_gain`` times larger. With a
    tied table the embedded token is itself a row of the head, and at
    fan-in scale its own logit stood ~33 above unit-variance logits: every
    model, right or wrong, greedily repeated its last input token, and a
    comparison through served tokens saw nothing (found on the chip, PR
    31). A rule of this configuration's random weights, so it lives here
    and not in the program."""
    params = _program().init(cfg, rng, **kw)
    gain = out_gain(cfg)
    for kind, name in OUT_PROJECTIONS:
        w = params[kind][name]
        params[kind][name] = (w.astype("float32") * gain).astype(w.dtype)
    return params


def out_gain(cfg, share: float = 0.05) -> float:
    """Such that the embedded token (``embedding_multiplier`` x a row of the
    table) is ``share`` of the final residual stream, which the layers' 2 L
    updates of ``residual_multiplier`` x a unit-RMS vector each make up (as
    a random walk). Published sizes: 21.6."""
    token = cfg.embedding_multiplier * cfg.logits_scaling \
        / math.sqrt(cfg.hidden_size)
    walk = cfg.residual_multiplier * math.sqrt(2 * cfg.num_layers)
    return token / (share * walk)


def build_cfg(hf: dict, **program_options):
    """Every published size from the configuration file. What the program
    does not have is refused, not dropped: a sparse branch, biases, more
    than one group of B and C, an inner width that is not ``mamba_expand x
    hidden`` = heads x head size, a positional embedding, an untied head."""
    if hf["num_local_experts"] or hf["num_experts_per_tok"]:
        raise ValueError("models/granite_hybrid.py has no sparse branch")
    for key in ("attention_bias", "mamba_proj_bias", "rope_scaling"):
        if hf.get(key):
            raise ValueError(f"models/granite_hybrid.py has no {key}")
    d_inner = hf["mamba_expand"] * hf["hidden_size"]
    if (hf["mamba_n_groups"] != 1 or not hf["mamba_conv_bias"]
            or d_inner != hf["mamba_n_heads"] * hf["mamba_d_head"]
            or hf["position_embedding_type"] != "nope"
            or not hf["tie_word_embeddings"]
            or hf["hidden_act"] != "silu"
            or hf["normalization_function"] != "rmsnorm"
            or hf["shared_intermediate_size"] != hf["intermediate_size"]
            or len(hf["layer_types"]) != hf["num_hidden_layers"]):
        raise ValueError("the configuration is not one models/"
                         "granite_hybrid.py runs as published")
    return dataclasses.replace(
        _program().GraniteHybridConfig(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["shared_intermediate_size"],
        layer_types=tuple(hf["layer_types"]),
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        max_seq_len=hf["max_position_embeddings"],
        rms_norm_eps=hf["rms_norm_eps"],
        embedding_multiplier=float(hf["embedding_multiplier"]),
        attention_multiplier=float(hf["attention_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]),
        mamba_heads=hf["mamba_n_heads"], mamba_head_dim=hf["mamba_d_head"],
        mamba_state=hf["mamba_d_state"], mamba_conv=hf["mamba_d_conv"],
        mamba_chunk=hf["mamba_chunk_size"], **program_options)


class Weights:
    """The program's parameter tree, stacked by kind, read one layer at a
    time under the reference's names: ``layer(kind, j)`` is the ``j``-th
    layer of its kind (the reference walks ``layer_types``)."""

    _ATTENTION = {"norm": "norm", "q": "wq", "k": "wk", "v": "wv", "o": "wo",
                  "mlp_norm": "mlp_norm", "w_in": "w_in", "w_out": "w_out"}

    def __init__(self, params):
        self._params = params
        self.embed = params["embed"]
        self.final_norm = params["final_norm"]

    def layer(self, kind: str, j: int) -> dict:
        if kind == "attention":
            p = self._params["attn"]
            return {name: p[key][j] for name, key in self._ATTENTION.items()}
        import jax.numpy as jnp

        p = self._params["mamba"]
        w = {name: leaf[j] for name, leaf in p.items() if name != "dt_proj"}
        # the published in_proj: [z | xBC | dt] (the program keeps the dt
        # columns by themselves)
        w["in_proj"] = jnp.concatenate([p["in_proj"][j], p["dt_proj"][j]], 1)
        return w
