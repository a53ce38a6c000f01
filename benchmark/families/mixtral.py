"""The ``mixtral`` family: published keys -> ``deepspeed_tpu.models.mixtral``
and its parameter tree -> the plain reference's weights."""

from __future__ import annotations

import dataclasses

from . import mistral

REFERENCE = "mixtral"


def module():
    from deepspeed_tpu.models import mixtral

    return mixtral


def build_cfg(hf: dict, **program_options):
    mixtral = module()
    if hf.get("head_dim") not in (None, hf["hidden_size"]
                                  // hf["num_attention_heads"]):
        raise ValueError("MixtralConfig has no separate head_dim")
    return dataclasses.replace(
        mixtral.MixtralConfig(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        num_experts=hf["num_local_experts"], top_k=hf["num_experts_per_tok"],
        max_seq_len=hf["max_position_embeddings"],
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        **program_options)


class Weights(mistral.Weights):
    def layer(self, i: int) -> dict:
        p = self._layers
        moe = p["moe"]
        n = moe["w_gate"].shape[1]
        return {"attn_norm": p["attn_norm"][i], "q": p["wq"][i],
                "k": p["wk"][i], "v": p["wv"][i], "o": p["wo"][i],
                "ffn_norm": p["mlp_norm"][i], "router": moe["router"][i],
                "experts": [(moe["w_gate"][i, e], moe["w_up"][i, e],
                             moe["w_down"][i, e]) for e in range(n)]}
