"""The ``olmoe`` family: OLMoE's published keys -> ``deepspeed_tpu.models.
mixtral`` (the program has one module for its sparse decoders; OLMoE is that
module with the whole-projection QK-norm on and raw top-k gates), and its
parameter tree -> the plain reference's weights."""

from __future__ import annotations

import dataclasses

from . import mixtral

REFERENCE = "olmoe"
module = mixtral.module


def build_cfg(hf: dict, **program_options):
    """``intermediate_size`` is the width of ONE expert (the release has no
    other key for it), ``num_experts`` the published name of their number;
    there is no ``head_dim`` key: a head is ``hidden_size / heads`` wide."""
    for key in ("clip_qkv", "attention_bias", "rope_scaling"):
        if hf.get(key):
            raise ValueError(f"models/mixtral.py has no {key}")
    if hf["tie_word_embeddings"]:
        raise ValueError("models/mixtral.py always carries its own head")
    if program_options.get("norm_topk_prob", True) != hf["norm_topk_prob"]:
        raise ValueError("the role's program_options and the published "
                         "configuration disagree on norm_topk_prob")
    return dataclasses.replace(
        module().MixtralConfig(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        num_experts=hf["num_experts"], top_k=hf["num_experts_per_tok"],
        max_seq_len=hf["max_position_embeddings"],
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        **program_options)


class Weights(mixtral.Weights):
    def layer(self, i: int) -> dict:
        p = self._layers
        return {**super().layer(i), "q_norm": p["q_norm"][i],
                "k_norm": p["k_norm"][i]}
