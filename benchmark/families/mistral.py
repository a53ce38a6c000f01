"""The ``mistral`` family: published keys -> the program's own entry points
(``deepspeed_tpu.models.llama``), and the program's parameter tree -> the
plain reference's weights. Nothing of the yardstick lives here."""

from __future__ import annotations

import dataclasses

REFERENCE = "mistral"


def module():
    from deepspeed_tpu.models import llama

    return llama


def build_cfg(hf: dict, **program_options):
    """``LlamaConfig.mistral_7b`` with every published size overwritten from
    the configuration file, so that a file which departs from the preset is
    what runs."""
    llama = module()
    return dataclasses.replace(
        llama.LlamaConfig.mistral_7b(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"],
        head_dim=hf.get("head_dim"),
        max_seq_len=hf["max_position_embeddings"],
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"], **program_options)


class Weights:
    """The program's stacked parameter tree, read one layer at a time under
    the reference's names."""

    def __init__(self, params):
        self._layers = params["layers"]
        self.embed = params["embed"]
        self.final_norm = params["final_norm"]
        self.head = params["lm_head"]

    def layer(self, i: int) -> dict:
        p = self._layers
        return {"attn_norm": p["attn_norm"][i], "q": p["wq"][i],
                "k": p["wk"][i], "v": p["wv"][i], "o": p["wo"][i],
                "ffn_norm": p["mlp_norm"][i], "gate": p["w_gate"][i],
                "up": p["w_up"][i], "down": p["w_down"][i]}
