"""HF checkpoint import: transformers state dicts → stacked param pytrees.

Reference parity: the reference consumes HF models directly (``deepspeed.
initialize(model=hf_model)``, ``init_inference`` checkpoint loading
``inference/engine.py:303-471``) and reshards TP-degree-changing checkpoints
via ``SDLoaderFactory``/``MegatronSDLoader`` (``runtime/state_dict_factory.py:
21,190``). Here a user brings HF weights to the TPU framework by converting
once into the stacked [L, ...] pytree layout; resharding to any topology is
then the checkpoint layer's job (orbax/universal).

Supported families: Llama/Mistral/Qwen2/Phi-3 (→ ``models/llama``; fused
QKV/gate-up checkpoints are split), GPT-2 (→ ``models/gpt``),
Mixtral/Qwen2-MoE/OLMoE (→ ``models/mixtral``), Falcon (→ ``models/falcon``),
OPT (→ ``models/gpt``, ReLU/pre-LN), GPT-NeoX/GPT-J (→ ``models/gptneox``),
BLOOM (→ ``models/bloom``, ALiBi), BERT/DistilBERT (→ ``models/bert``), CLIP (→ ``models/clip``,
both towers + contrastive head), Megatron-GPT state dicts
(``megatron_gpt_params_from_sd``, composing with the TP-degree-changing
``SDLoaderFactory``). Accepts a live
``transformers`` model, a state-dict mapping, or a local checkpoint directory
(no network access is assumed). Un-annotated models TP-shard via the AutoTP
name-rule pass (``module_inject/auto_tp.py``).
"""

from __future__ import annotations

import os
import re
from typing import Any, Dict, Mapping, Optional

import numpy as np

from ..utils.logging import log_dist

Params = Dict[str, Any]


def _to_numpy(t) -> np.ndarray:
    if isinstance(t, np.ndarray):
        return t
    try:  # torch tensor
        return t.detach().cpu().float().numpy()
    except AttributeError:
        return np.asarray(t)


def _normalize_state_dict(src) -> Dict[str, np.ndarray]:
    """Accept a transformers model, an nn.Module, or a mapping."""
    if hasattr(src, "state_dict") and callable(src.state_dict):
        src = src.state_dict()
    if not isinstance(src, Mapping):
        raise TypeError(f"cannot read weights from {type(src)}")
    return {k: _to_numpy(v) for k, v in src.items()}


def _count_indices(sd: Dict[str, np.ndarray], pattern: str) -> int:
    """1 + max index matched by ``pattern`` (one capture group) over keys."""
    idx = [int(m.group(1)) for k in sd if (m := re.match(pattern, k))]
    if not idx:
        raise KeyError(f"no keys match {pattern!r} — wrong family/prefix?")
    return 1 + max(idx)


def _stack(sd: Dict[str, np.ndarray], pattern: str, num_layers: int,
           transpose: bool = False) -> np.ndarray:
    """Collect per-layer tensors 'prefix.{i}.suffix' into one [L, ...] array."""
    mats = []
    for i in range(num_layers):
        key = pattern.format(i=i)
        if key not in sd:
            raise KeyError(f"missing weight {key}")
        m = sd[key]
        mats.append(m.T if transpose else m)
    return np.stack(mats)


def llama_config_from_hf(hf_config) -> "Any":
    """Map a transformers LlamaConfig/MistralConfig/Qwen2Config/Phi3Config."""
    from .llama import LlamaConfig

    scaling = getattr(hf_config, "rope_scaling", None)
    if scaling:
        # longrope (Phi-3-128k) / llama3 scaling rescale even short contexts;
        # silently applying plain RoPE would give wrong logits everywhere
        raise ValueError(
            f"rope_scaling={scaling.get('type', scaling.get('rope_type'))!r} "
            f"checkpoints are not supported yet — import the base "
            f"(non-scaled) variant")
    return LlamaConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads",
                             hf_config.num_attention_heads),
        max_seq_len=getattr(hf_config, "max_position_embeddings", 4096),
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        rms_norm_eps=float(getattr(hf_config, "rms_norm_eps", 1e-5)),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        # Qwen2 always uses QKV biases; Llama exposes an attention_bias flag
        attention_bias=bool(getattr(hf_config, "attention_bias",
                                    hf_config.model_type == "qwen2")),
        # Qwen3: decoupled head_dim + per-head q/k RMSNorm, no QKV bias
        head_dim=getattr(hf_config, "head_dim", None),
        qk_norm=hf_config.model_type == "qwen3",
    )


def llama_params_from_hf(src, cfg=None) -> Params:
    """HF LlamaForCausalLM (or compatible) weights → ``models/llama`` pytree.
    HF nn.Linear stores [out, in]; our layout is [in, out] → transpose."""
    sd = _normalize_state_dict(src)
    pfx = "model." if any(k.startswith("model.") for k in sd) else ""
    L = cfg.num_layers if cfg is not None else \
        _count_indices(sd, rf"{re.escape(pfx)}layers\.(\d+)\.")
    lay = pfx + "layers.{i}."
    params: Params = {
        "embed": sd[pfx + "embed_tokens.weight"],
        "layers": {
            "attn_norm": _stack(sd, lay + "input_layernorm.weight", L),
            "wq": _stack(sd, lay + "self_attn.q_proj.weight", L, transpose=True),
            "wk": _stack(sd, lay + "self_attn.k_proj.weight", L, transpose=True),
            "wv": _stack(sd, lay + "self_attn.v_proj.weight", L, transpose=True),
            "wo": _stack(sd, lay + "self_attn.o_proj.weight", L, transpose=True),
            "mlp_norm": _stack(sd, lay + "post_attention_layernorm.weight", L),
            "w_gate": _stack(sd, lay + "mlp.gate_proj.weight", L, transpose=True),
            "w_up": _stack(sd, lay + "mlp.up_proj.weight", L, transpose=True),
            "w_down": _stack(sd, lay + "mlp.down_proj.weight", L, transpose=True),
        },
        "final_norm": sd[pfx + "norm.weight"],
    }
    if "lm_head.weight" in sd and \
            not (cfg is not None and cfg.tie_embeddings):
        params["lm_head"] = sd["lm_head.weight"].T  # tied ckpts alias it
    has_bias = (lay.format(i=0) + "self_attn.q_proj.bias") in sd
    if has_bias:
        # Qwen2 QKV biases (ADVICE r1: these were silently dropped)
        params["layers"]["bq"] = _stack(sd, lay + "self_attn.q_proj.bias", L)
        params["layers"]["bk"] = _stack(sd, lay + "self_attn.k_proj.bias", L)
        params["layers"]["bv"] = _stack(sd, lay + "self_attn.v_proj.bias", L)
    has_qk_norm = (lay.format(i=0) + "self_attn.q_norm.weight") in sd
    if has_qk_norm:
        params["layers"]["q_norm"] = _stack(sd, lay + "self_attn.q_norm.weight", L)
        params["layers"]["k_norm"] = _stack(sd, lay + "self_attn.k_norm.weight", L)
    if cfg is not None and \
            bool(getattr(cfg, "qk_norm", False)) != has_qk_norm:
        # same silent-drop class as the attention_bias check below: a
        # missing norm would silently skip in _qkv_proj; an unexpected one
        # would load leaves with no logical-axes entry
        raise ValueError(
            f"qk_norm={getattr(cfg, 'qk_norm', False)} but checkpoint "
            f"{'has' if has_qk_norm else 'lacks'} q_norm.weight tensors")
    if cfg is not None and bool(getattr(cfg, "attention_bias", False)) != has_bias:
        raise ValueError(
            f"attention_bias={getattr(cfg, 'attention_bias', False)} but "
            f"checkpoint {'has' if has_bias else 'lacks'} q_proj.bias tensors")
    log_dist(f"imported HF llama-family weights: {L} layers, "
             f"vocab {params['embed'].shape[0]}, qkv_bias={has_bias}")
    return params


def gpt2_config_from_hf(hf_config) -> "Any":
    from .gpt import GPTConfig

    return GPTConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.n_embd,
        intermediate_size=getattr(hf_config, "n_inner", None) or 4 * hf_config.n_embd,
        num_layers=hf_config.n_layer,
        num_heads=hf_config.n_head,
        max_seq_len=hf_config.n_positions,
        layer_norm_eps=float(getattr(hf_config, "layer_norm_epsilon", 1e-5)),
        tie_embeddings=True,
    )


def gpt2_params_from_hf(src, cfg=None) -> Params:
    """HF GPT2LMHeadModel weights → ``models/gpt`` pytree. GPT-2 Conv1D
    already stores [in, out] — no transpose."""
    sd = _normalize_state_dict(src)
    pfx = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
    L = cfg.num_layers if cfg is not None else \
        _count_indices(sd, rf"{re.escape(pfx)}h\.(\d+)\.")
    lay = pfx + "h.{i}."
    params: Params = {
        "embed": sd[pfx + "wte.weight"],
        "pos_embed": sd[pfx + "wpe.weight"],
        "layers": {
            "ln1_scale": _stack(sd, lay + "ln_1.weight", L),
            "ln1_bias": _stack(sd, lay + "ln_1.bias", L),
            "wqkv": _stack(sd, lay + "attn.c_attn.weight", L),
            "bqkv": _stack(sd, lay + "attn.c_attn.bias", L),
            "wo": _stack(sd, lay + "attn.c_proj.weight", L),
            "bo": _stack(sd, lay + "attn.c_proj.bias", L),
            "ln2_scale": _stack(sd, lay + "ln_2.weight", L),
            "ln2_bias": _stack(sd, lay + "ln_2.bias", L),
            "w_up": _stack(sd, lay + "mlp.c_fc.weight", L),
            "b_up": _stack(sd, lay + "mlp.c_fc.bias", L),
            "w_down": _stack(sd, lay + "mlp.c_proj.weight", L),
            "b_down": _stack(sd, lay + "mlp.c_proj.bias", L),
        },
        "final_ln_scale": sd[pfx + "ln_f.weight"],
        "final_ln_bias": sd[pfx + "ln_f.bias"],
    }
    log_dist(f"imported HF gpt2-family weights: {L} layers")
    return params


def opt_config_from_hf(hf_config) -> "Any":
    """Map a transformers OPTConfig onto the GPT family (pre-LN, ReLU,
    learned positions; reference ``inference/v2/model_implementations/opt``)."""
    from .gpt import GPTConfig

    if getattr(hf_config, "word_embed_proj_dim",
               hf_config.hidden_size) != hf_config.hidden_size:
        raise ValueError("OPT variants with word_embed_proj_dim != "
                         "hidden_size (opt-350m) are not supported")
    if not getattr(hf_config, "do_layer_norm_before", True):
        raise ValueError("OPT with do_layer_norm_before=False (opt-350m) "
                         "is not supported")
    act = getattr(hf_config, "activation_function", "relu")
    if act != "relu":
        # silently running a different activation would give wrong logits
        # (and HF 'gelu' is exact-erf vs jax's tanh default)
        raise ValueError(f"OPT activation_function={act!r} not supported "
                         "(only 'relu', the released OPT family)")
    return GPTConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.ffn_dim,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        max_seq_len=hf_config.max_position_embeddings,
        activation=act,
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", True)),
    )


def opt_params_from_hf(src, cfg=None) -> Params:
    """HF OPTForCausalLM → ``models/gpt`` pytree: q/k/v/out projections fuse
    into wqkv/bqkv; OPT's learned positions carry a +2 offset, dropped here
    by slicing the table."""
    sd = _normalize_state_dict(src)
    pfx = "model.decoder." if any(k.startswith("model.decoder.") for k in sd) \
        else "decoder." if any(k.startswith("decoder.") for k in sd) else ""
    L = cfg.num_layers if cfg is not None else \
        _count_indices(sd, rf"{re.escape(pfx)}layers\.(\d+)\.")
    lay = pfx + "layers.{i}."

    def fuse_qkv(i):
        ws = [sd[lay.format(i=i) + f"self_attn.{p}_proj.weight"].T
              for p in ("q", "k", "v")]
        bs = [sd[lay.format(i=i) + f"self_attn.{p}_proj.bias"]
              for p in ("q", "k", "v")]
        return np.concatenate(ws, axis=1), np.concatenate(bs)

    fused = [fuse_qkv(i) for i in range(L)]
    params: Params = {
        "embed": sd[pfx + "embed_tokens.weight"],
        "pos_embed": sd[pfx + "embed_positions.weight"][2:],  # OPT offset
        "layers": {
            "ln1_scale": _stack(sd, lay + "self_attn_layer_norm.weight", L),
            "ln1_bias": _stack(sd, lay + "self_attn_layer_norm.bias", L),
            "wqkv": np.stack([w for w, _ in fused]),
            "bqkv": np.stack([b for _, b in fused]),
            "wo": _stack(sd, lay + "self_attn.out_proj.weight", L,
                         transpose=True),
            "bo": _stack(sd, lay + "self_attn.out_proj.bias", L),
            "ln2_scale": _stack(sd, lay + "final_layer_norm.weight", L),
            "ln2_bias": _stack(sd, lay + "final_layer_norm.bias", L),
            "w_up": _stack(sd, lay + "fc1.weight", L, transpose=True),
            "b_up": _stack(sd, lay + "fc1.bias", L),
            "w_down": _stack(sd, lay + "fc2.weight", L, transpose=True),
            "b_down": _stack(sd, lay + "fc2.bias", L),
        },
        "final_ln_scale": sd[pfx + "final_layer_norm.weight"],
        "final_ln_bias": sd[pfx + "final_layer_norm.bias"],
    }
    if cfg is not None and not cfg.tie_embeddings:
        if "lm_head.weight" not in sd:
            raise ValueError("untied OPT config but checkpoint has no "
                             "lm_head.weight")
        params["lm_head"] = sd["lm_head.weight"].T
    log_dist(f"imported HF opt weights: {L} layers")
    return params


def phi3_params_from_hf(src, cfg=None) -> Params:
    """HF Phi3ForCausalLM → ``models/llama`` pytree. Phi-3 fuses QKV into
    ``self_attn.qkv_proj`` and gate/up into ``mlp.gate_up_proj`` (reference
    ``inference/v2/model_implementations/phi3``) — split them here."""
    sd = _normalize_state_dict(src)
    pfx = "model." if any(k.startswith("model.") for k in sd) else ""
    L = cfg.num_layers if cfg is not None else \
        _count_indices(sd, rf"{re.escape(pfx)}layers\.(\d+)\.")
    lay = pfx + "layers.{i}."
    qkv = _stack(sd, lay + "self_attn.qkv_proj.weight", L, transpose=True)
    gate_up = _stack(sd, lay + "mlp.gate_up_proj.weight", L, transpose=True)
    h = qkv.shape[1]
    if cfg is not None:
        nq = cfg.num_heads * cfg.head_size
        nkv = cfg.num_kv_heads * cfg.head_size
    else:  # phi3: q span == hidden, k/v split the rest evenly
        nq = h
        nkv = (qkv.shape[2] - nq) // 2
    inter = gate_up.shape[2] // 2
    params: Params = {
        "embed": sd[pfx + "embed_tokens.weight"],
        "layers": {
            "attn_norm": _stack(sd, lay + "input_layernorm.weight", L),
            "wq": qkv[:, :, :nq],
            "wk": qkv[:, :, nq:nq + nkv],
            "wv": qkv[:, :, nq + nkv:],
            "wo": _stack(sd, lay + "self_attn.o_proj.weight", L, transpose=True),
            "mlp_norm": _stack(sd, lay + "post_attention_layernorm.weight", L),
            "w_gate": gate_up[:, :, :inter],
            "w_up": gate_up[:, :, inter:],
            "w_down": _stack(sd, lay + "mlp.down_proj.weight", L, transpose=True),
        },
        "final_norm": sd[pfx + "norm.weight"],
    }
    if "lm_head.weight" in sd:
        params["lm_head"] = sd["lm_head.weight"].T
    log_dist(f"imported HF phi3 weights: {L} layers (split fused qkv/gate_up)")
    return params


def mixtral_config_from_hf(hf_config) -> "Any":
    from .mixtral import MixtralConfig

    return MixtralConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads",
                             hf_config.num_attention_heads),
        num_experts=hf_config.num_local_experts,
        top_k=hf_config.num_experts_per_tok,
        # HF Mixtral routes every token (no capacity limit): disable token
        # dropping so imported logits match exactly
        drop_tokens=False,
        max_seq_len=getattr(hf_config, "max_position_embeddings", 4096),
        rope_theta=float(getattr(hf_config, "rope_theta", 1e6)),
        rms_norm_eps=float(getattr(hf_config, "rms_norm_eps", 1e-5)),
        aux_loss_coef=float(getattr(hf_config, "router_aux_loss_coef", 0.02)),
    )


def mixtral_params_from_hf(src, cfg=None) -> Params:
    """HF MixtralForCausalLM → ``models/mixtral`` pytree. Experts stack to
    [L, E, ...] (reference ``inference/v2/model_implementations/mixtral``)."""
    sd = _normalize_state_dict(src)
    pfx = "model." if any(k.startswith("model.") for k in sd) else ""
    L = cfg.num_layers if cfg is not None else \
        _count_indices(sd, rf"{re.escape(pfx)}layers\.(\d+)\.")
    lay = pfx + "layers.{i}."
    E = cfg.num_experts if cfg is not None else \
        _count_indices(sd, rf"{re.escape(pfx)}layers\.0\.block_sparse_moe"
                           rf"\.experts\.(\d+)\.")

    def stack_expert(w: str) -> np.ndarray:  # → [L, E, out, in] pre-transpose
        return np.stack([
            np.stack([sd[lay.format(i=i) +
                         f"block_sparse_moe.experts.{e}.{w}.weight"].T
                      for e in range(E)]) for i in range(L)])

    params: Params = {
        "embed": sd[pfx + "embed_tokens.weight"],
        "layers": {
            "attn_norm": _stack(sd, lay + "input_layernorm.weight", L),
            "wq": _stack(sd, lay + "self_attn.q_proj.weight", L, transpose=True),
            "wk": _stack(sd, lay + "self_attn.k_proj.weight", L, transpose=True),
            "wv": _stack(sd, lay + "self_attn.v_proj.weight", L, transpose=True),
            "wo": _stack(sd, lay + "self_attn.o_proj.weight", L, transpose=True),
            "mlp_norm": _stack(sd, lay + "post_attention_layernorm.weight", L),
            "moe": {
                "router": _stack(sd, lay + "block_sparse_moe.gate.weight", L,
                                 transpose=True),
                "w_gate": stack_expert("w1"),
                "w_up": stack_expert("w3"),
                "w_down": stack_expert("w2"),
            },
        },
        "final_norm": sd[pfx + "norm.weight"],
        # tied checkpoints omit lm_head from the state dict — materialize the
        # transpose (models/mixtral always carries an explicit head)
        "lm_head": (sd["lm_head.weight"].T if "lm_head.weight" in sd
                    else sd[pfx + "embed_tokens.weight"].T.copy()),
    }
    log_dist(f"imported HF mixtral weights: {L} layers x {E} experts")
    return params


def qwen2_moe_config_from_hf(hf_config) -> "Any":
    """Map a transformers Qwen2MoeConfig (reference ``.../qwen_v2_moe``)."""
    from .mixtral import MixtralConfig

    if getattr(hf_config, "mlp_only_layers", None) or \
            getattr(hf_config, "decoder_sparse_step", 1) != 1:
        raise ValueError("Qwen2-MoE variants with dense interleaved layers "
                         "(mlp_only_layers/decoder_sparse_step>1) are not "
                         "supported — the layer stack must be uniform for "
                         "the scanned block")
    return MixtralConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.moe_intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        num_experts=hf_config.num_experts,
        top_k=hf_config.num_experts_per_tok,
        drop_tokens=False,
        norm_topk_prob=bool(getattr(hf_config, "norm_topk_prob", False)),
        attention_bias=True,  # Qwen2 family always carries QKV biases
        shared_expert_intermediate_size=int(
            getattr(hf_config, "shared_expert_intermediate_size", 0)),
        max_seq_len=getattr(hf_config, "max_position_embeddings", 4096),
        rope_theta=float(getattr(hf_config, "rope_theta", 1e6)),
        rms_norm_eps=float(getattr(hf_config, "rms_norm_eps", 1e-6)),
        aux_loss_coef=float(getattr(hf_config, "router_aux_loss_coef", 0.001)),
    )


def _mlp_experts_params_from_hf(src, cfg=None):
    """What Qwen2-MoE and OLMoE checkpoints share -> ``models/mixtral``
    pytree: Llama-named attention and norms, the router at ``mlp.gate`` and
    experts at ``mlp.experts.N.{gate,up,down}_proj``, stacked to [L, E, ...].
    Returns (params, state dict, per-layer key prefix, L, E); the caller
    adds what is its family's own."""
    sd = _normalize_state_dict(src)
    pfx = "model." if any(k.startswith("model.") for k in sd) else ""
    L = cfg.num_layers if cfg is not None else \
        _count_indices(sd, rf"{re.escape(pfx)}layers\.(\d+)\.")
    lay = pfx + "layers.{i}."
    E = cfg.num_experts if cfg is not None else \
        _count_indices(sd, rf"{re.escape(pfx)}layers\.0\.mlp\.experts"
                           rf"\.(\d+)\.")

    def stack_expert(w: str) -> np.ndarray:
        return np.stack([
            np.stack([sd[lay.format(i=i) + f"mlp.experts.{e}.{w}.weight"].T
                      for e in range(E)]) for i in range(L)])

    params: Params = {
        "embed": sd[pfx + "embed_tokens.weight"],
        "layers": {
            "attn_norm": _stack(sd, lay + "input_layernorm.weight", L),
            "wq": _stack(sd, lay + "self_attn.q_proj.weight", L, transpose=True),
            "wk": _stack(sd, lay + "self_attn.k_proj.weight", L, transpose=True),
            "wv": _stack(sd, lay + "self_attn.v_proj.weight", L, transpose=True),
            "wo": _stack(sd, lay + "self_attn.o_proj.weight", L, transpose=True),
            "mlp_norm": _stack(sd, lay + "post_attention_layernorm.weight", L),
            "moe": {
                "router": _stack(sd, lay + "mlp.gate.weight", L,
                                 transpose=True),
                "w_gate": stack_expert("gate_proj"),
                "w_up": stack_expert("up_proj"),
                "w_down": stack_expert("down_proj"),
            },
        },
        "final_norm": sd[pfx + "norm.weight"],
        "lm_head": (sd["lm_head.weight"].T if "lm_head.weight" in sd
                    else sd[pfx + "embed_tokens.weight"].T.copy()),
    }
    return params, sd, lay, L, E


def qwen2_moe_params_from_hf(src, cfg=None) -> Params:
    """HF Qwen2MoeForCausalLM → ``models/mixtral`` pytree (+ shared expert
    and QKV biases)."""
    params, sd, lay, L, E = _mlp_experts_params_from_hf(src, cfg)
    layers = params["layers"]
    for ours, theirs in (("bq", "q_proj"), ("bk", "k_proj"), ("bv", "v_proj")):
        layers[ours] = _stack(sd, lay + f"self_attn.{theirs}.bias", L)
    for ours, theirs in (("shared_w_gate", "shared_expert.gate_proj"),
                         ("shared_w_up", "shared_expert.up_proj"),
                         ("shared_w_down", "shared_expert.down_proj"),
                         ("shared_gate", "shared_expert_gate")):
        layers["moe"][ours] = _stack(sd, lay + f"mlp.{theirs}.weight", L,
                                     transpose=True)
    log_dist(f"imported HF qwen2_moe weights: {L} layers x {E} experts "
             f"+ shared expert")
    return params


def olmoe_config_from_hf(hf_config) -> "Any":
    """Map a transformers OlmoeConfig: ``intermediate_size`` is the width of
    ONE expert, the gates stay unnormalised (``norm_topk_prob`` false as
    published) and q / k pass an RMSNorm over the whole projection."""
    from .mixtral import MixtralConfig

    for key in ("clip_qkv", "attention_bias", "rope_scaling"):
        if getattr(hf_config, key, None):
            raise ValueError(f"OLMoE with {key} is not supported - "
                             f"models/mixtral.py has no such path")
    return MixtralConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        num_experts=hf_config.num_experts,
        top_k=hf_config.num_experts_per_tok,
        drop_tokens=False,
        norm_topk_prob=bool(getattr(hf_config, "norm_topk_prob", False)),
        qk_proj_norm=True,
        max_seq_len=getattr(hf_config, "max_position_embeddings", 4096),
        rope_theta=float(getattr(hf_config, "rope_theta", 1e4)),
        rms_norm_eps=float(getattr(hf_config, "rms_norm_eps", 1e-5)),
        aux_loss_coef=float(getattr(hf_config, "router_aux_loss_coef", 0.01)),
    )


def olmoe_params_from_hf(src, cfg=None) -> Params:
    """HF OlmoeForCausalLM -> ``models/mixtral`` pytree (+ the two norms over
    the whole q and k projections, ``self_attn.{q,k}_norm.weight``)."""
    params, sd, lay, L, E = _mlp_experts_params_from_hf(src, cfg)
    for name in ("q_norm", "k_norm"):
        params["layers"][name] = _stack(sd, lay + f"self_attn.{name}.weight", L)
    log_dist(f"imported HF olmoe weights: {L} layers x {E} experts")
    return params


def falcon_config_from_hf(hf_config) -> "Any":
    from .falcon import FalconConfig

    if getattr(hf_config, "alibi", False):
        # models/falcon.py applies rotary embeddings; running an ALiBi
        # checkpoint through RoPE would give silently wrong logits
        raise ValueError("alibi=True falcon checkpoints are not supported — "
                         "models/falcon.py implements the RoPE variants "
                         "(7B/40B/180B); ALiBi (rw-*) needs an ALiBi "
                         "attention path")
    return FalconConfig(
        max_seq_len=int(getattr(hf_config, "max_position_embeddings", 2048)),
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=(hf_config.num_kv_heads
                      if getattr(hf_config, "new_decoder_architecture", False)
                      else (1 if getattr(hf_config, "multi_query", True)
                            else hf_config.num_attention_heads)),
        parallel_attn=bool(getattr(hf_config, "parallel_attn", True)),
        new_decoder_architecture=bool(getattr(hf_config,
                                              "new_decoder_architecture", False)),
        layer_norm_eps=float(getattr(hf_config, "layer_norm_epsilon", 1e-5)),
        rope_theta=float(getattr(hf_config, "rope_theta", 10000.0)),
        attention_bias=bool(getattr(hf_config, "bias", False)),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", True)),
    )


def falcon_params_from_hf(src, cfg) -> Params:
    """HF FalconForCausalLM → ``models/falcon`` pytree (reference
    ``inference/v2/model_implementations/falcon``). Fused-QKV layouts (HF
    ``FalconAttention._split_heads``): new decoder architecture =
    [nkv groups of (q*g | k | v)]; classic multi_query = [q-block | k | v];
    classic multi-head (rw-1b) = per-head interleaved [nh, (q | k | v)].

    ``cfg`` is required (head split depends on it) — build via
    ``falcon_config_from_hf``."""
    if cfg is None:
        raise ValueError("falcon_params_from_hf requires cfg (the fused-QKV "
                         "split depends on head counts) — build it with "
                         "falcon_config_from_hf")
    sd = _normalize_state_dict(src)
    pfx = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
    L = cfg.num_layers
    lay = pfx + "h.{i}."
    if (lay.format(i=0) + "self_attention.query_key_value.bias") in sd:
        raise ValueError("falcon checkpoints with linear biases (bias=True) "
                         "are not supported — models/falcon.py has no bias "
                         "params (classic 7B/40B/180B are bias-free)")
    qkv = _stack(sd, lay + "self_attention.query_key_value.weight", L,
                 transpose=True)  # [L, h, (nh + 2*nkv) * hd]
    h = qkv.shape[1]
    nh = cfg.num_heads
    nkv = cfg.num_kv_heads
    hd = cfg.head_size
    if cfg.new_decoder_architecture:
        # interleaved [nkv groups of (q*g | k | v)]
        g = nh // nkv
        fused = qkv.reshape(L, h, nkv, g + 2, hd)
        wq = fused[:, :, :, :g].reshape(L, h, nh * hd)
        wk = fused[:, :, :, g].reshape(L, h, nkv * hd)
        wv = fused[:, :, :, g + 1].reshape(L, h, nkv * hd)
    elif nkv == nh:
        # classic multi-head (multi_query=False, e.g. rw-1b): per-head
        # interleave view(.., nh, 3, hd)
        fused = qkv.reshape(L, h, nh, 3, hd)
        wq = fused[:, :, :, 0].reshape(L, h, nh * hd)
        wk = fused[:, :, :, 1].reshape(L, h, nh * hd)
        wv = fused[:, :, :, 2].reshape(L, h, nh * hd)
    else:
        # classic multi_query (7B): [q-block | k | v]
        wq = qkv[:, :, :nh * hd]
        wk = qkv[:, :, nh * hd:(nh + nkv) * hd]
        wv = qkv[:, :, (nh + nkv) * hd:]
    params: Params = {
        "embed": sd[pfx + "word_embeddings.weight"],
        "layers": {
            "ln_attn_scale": _stack(
                sd, lay + ("ln_attn.weight" if cfg.new_decoder_architecture
                           else "input_layernorm.weight"), L),
            "ln_attn_bias": _stack(
                sd, lay + ("ln_attn.bias" if cfg.new_decoder_architecture
                           else "input_layernorm.bias"), L),
            "wq": wq, "wk": wk, "wv": wv,
            "wo": _stack(sd, lay + "self_attention.dense.weight", L,
                         transpose=True),
            "w_up": _stack(sd, lay + "mlp.dense_h_to_4h.weight", L,
                           transpose=True),
            "w_down": _stack(sd, lay + "mlp.dense_4h_to_h.weight", L,
                             transpose=True),
        },
        "final_ln_scale": sd[pfx + "ln_f.weight"],
        "final_ln_bias": sd[pfx + "ln_f.bias"],
    }
    if cfg.new_decoder_architecture:
        params["layers"]["ln_mlp_scale"] = _stack(sd, lay + "ln_mlp.weight", L)
        params["layers"]["ln_mlp_bias"] = _stack(sd, lay + "ln_mlp.bias", L)
    elif not cfg.parallel_attn:
        # sequential classic blocks carry a distinct second norm
        params["layers"]["ln_mlp_scale"] = _stack(
            sd, lay + "post_attention_layernorm.weight", L)
        params["layers"]["ln_mlp_bias"] = _stack(
            sd, lay + "post_attention_layernorm.bias", L)
    if "lm_head.weight" in sd and not cfg.tie_embeddings:
        params["lm_head"] = sd["lm_head.weight"].T  # tied ckpts alias it
    log_dist(f"imported HF falcon weights: {L} layers (nkv={nkv})")
    return params


def _split_fused_qkv(w: np.ndarray, nh: int, hd: int):
    """De-interleave an HF fused query_key_value projection whose output rows
    are grouped per head as [q(hd); k(hd); v(hd)] (GPT-NeoX views the fused
    tensor as (nh, 3*hd), BLOOM as (nh, 3, hd) — the same row layout).
    w: [3*nh*hd, in] or bias [3*nh*hd] → (q, k, v) each [nh*hd(, in)]."""
    shape = (nh, 3, hd) + w.shape[1:]
    grouped = w.reshape(shape)
    return tuple(grouped[:, j].reshape((nh * hd,) + w.shape[1:])
                 for j in range(3))


def gptneox_config_from_hf(hf_config) -> "Any":
    from .gptneox import GPTNeoXConfig

    return GPTNeoXConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        max_seq_len=hf_config.max_position_embeddings,
        rotary_pct=float(getattr(hf_config, "rotary_pct", 1.0)),
        rope_theta=float(getattr(hf_config, "rotary_emb_base", 10000.0)),
        parallel_residual=bool(getattr(hf_config, "use_parallel_residual",
                                       True)),
        gelu_approx=getattr(hf_config, "hidden_act", "gelu") in
        ("gelu_new", "gelu_pytorch_tanh", "gelu_fast"),
        layer_norm_eps=float(getattr(hf_config, "layer_norm_eps", 1e-5)),
    )


def gptneox_params_from_hf(src, cfg=None) -> Params:
    """HF GPTNeoXForCausalLM → ``models/gptneox`` pytree (fused QKV is
    de-interleaved per head so TP can shard the heads axis)."""
    sd = _normalize_state_dict(src)
    L = cfg.num_layers
    nh, hd = cfg.num_heads, cfg.head_size
    lay = "gpt_neox.layers.{i}."
    qkv_w = _stack(sd, lay + "attention.query_key_value.weight", L)
    qkv_b = _stack(sd, lay + "attention.query_key_value.bias", L)
    wq, wk, wv = zip(*(_split_fused_qkv(w, nh, hd) for w in qkv_w))
    bq, bk, bv = zip(*(_split_fused_qkv(b, nh, hd) for b in qkv_b))
    params: Params = {
        "embed": sd["gpt_neox.embed_in.weight"],
        "layers": {
            "ln1_scale": _stack(sd, lay + "input_layernorm.weight", L),
            "ln1_bias": _stack(sd, lay + "input_layernorm.bias", L),
            "ln2_scale": _stack(sd, lay + "post_attention_layernorm.weight", L),
            "ln2_bias": _stack(sd, lay + "post_attention_layernorm.bias", L),
            "wq": np.stack([w.T for w in wq]),
            "wk": np.stack([w.T for w in wk]),
            "wv": np.stack([w.T for w in wv]),
            "bq": np.stack(bq), "bk": np.stack(bk), "bv": np.stack(bv),
            "wo": _stack(sd, lay + "attention.dense.weight", L, transpose=True),
            "bo": _stack(sd, lay + "attention.dense.bias", L),
            "w_up": _stack(sd, lay + "mlp.dense_h_to_4h.weight", L,
                           transpose=True),
            "b_up": _stack(sd, lay + "mlp.dense_h_to_4h.bias", L),
            "w_down": _stack(sd, lay + "mlp.dense_4h_to_h.weight", L,
                             transpose=True),
            "b_down": _stack(sd, lay + "mlp.dense_4h_to_h.bias", L),
        },
        "final_ln_scale": sd["gpt_neox.final_layer_norm.weight"],
        "final_ln_bias": sd["gpt_neox.final_layer_norm.bias"],
        "lm_head": sd["embed_out.weight"].T,
    }
    log_dist(f"imported HF gpt_neox weights: {L} layers")
    return params


def gptj_config_from_hf(hf_config) -> "Any":
    from .gptneox import GPTNeoXConfig

    inner = getattr(hf_config, "n_inner", None) or 4 * hf_config.n_embd
    # HF GPT-J rotates the FULL head dim when rotary_dim is None
    rotary_dim = getattr(hf_config, "rotary_dim", None)
    if rotary_dim is None:
        rotary_dim = hf_config.n_embd // hf_config.n_head
    return GPTNeoXConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.n_embd,
        intermediate_size=inner,
        num_layers=hf_config.n_layer,
        num_heads=hf_config.n_head,
        max_seq_len=hf_config.n_positions,
        rotary_dim=rotary_dim,
        rotary_interleaved=True,
        shared_ln=True,
        qkv_bias=False,
        attn_out_bias=False,
        lm_head_bias=True,
        gelu_approx=True,   # 'gelu_new'
        layer_norm_eps=float(getattr(hf_config, "layer_norm_epsilon", 1e-5)),
    )


def gptj_params_from_hf(src, cfg=None) -> Params:
    """HF GPTJForCausalLM → ``models/gptneox`` pytree (shared-ln variant)."""
    sd = _normalize_state_dict(src)
    L = cfg.num_layers
    lay = "transformer.h.{i}."
    params: Params = {
        "embed": sd["transformer.wte.weight"],
        "layers": {
            "ln1_scale": _stack(sd, lay + "ln_1.weight", L),
            "ln1_bias": _stack(sd, lay + "ln_1.bias", L),
            "wq": _stack(sd, lay + "attn.q_proj.weight", L, transpose=True),
            "wk": _stack(sd, lay + "attn.k_proj.weight", L, transpose=True),
            "wv": _stack(sd, lay + "attn.v_proj.weight", L, transpose=True),
            "wo": _stack(sd, lay + "attn.out_proj.weight", L, transpose=True),
            "w_up": _stack(sd, lay + "mlp.fc_in.weight", L, transpose=True),
            "b_up": _stack(sd, lay + "mlp.fc_in.bias", L),
            "w_down": _stack(sd, lay + "mlp.fc_out.weight", L, transpose=True),
            "b_down": _stack(sd, lay + "mlp.fc_out.bias", L),
        },
        "final_ln_scale": sd["transformer.ln_f.weight"],
        "final_ln_bias": sd["transformer.ln_f.bias"],
        "lm_head": sd["lm_head.weight"].T,
        "lm_head_bias": sd["lm_head.bias"],
    }
    log_dist(f"imported HF gptj weights: {L} layers")
    return params


def bloom_config_from_hf(hf_config) -> "Any":
    from .bloom import BloomConfig

    return BloomConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        num_layers=hf_config.n_layer,
        num_heads=hf_config.n_head,
        max_seq_len=getattr(hf_config, "seq_length", 2048),
        layer_norm_eps=float(getattr(hf_config, "layer_norm_epsilon", 1e-5)),
    )


def bloom_params_from_hf(src, cfg=None) -> Params:
    """HF BloomForCausalLM → ``models/bloom`` pytree. The fused
    query_key_value rows are per-head [q;k;v] groups — same layout as
    GPT-NeoX — de-interleaved here so the TP rules shard heads."""
    sd = _normalize_state_dict(src)
    pfx = "transformer." if any(k.startswith("transformer.") for k in sd) else ""
    L = cfg.num_layers
    nh, hd = cfg.num_heads, cfg.head_size
    lay = pfx + "h.{i}."
    qkv_w = _stack(sd, lay + "self_attention.query_key_value.weight", L)
    qkv_b = _stack(sd, lay + "self_attention.query_key_value.bias", L)
    wq, wk, wv = zip(*(_split_fused_qkv(w, nh, hd) for w in qkv_w))
    bq, bk, bv = zip(*(_split_fused_qkv(b, nh, hd) for b in qkv_b))
    params: Params = {
        "embed": sd[pfx + "word_embeddings.weight"],
        "embed_ln_scale": sd[pfx + "word_embeddings_layernorm.weight"],
        "embed_ln_bias": sd[pfx + "word_embeddings_layernorm.bias"],
        "layers": {
            "ln1_scale": _stack(sd, lay + "input_layernorm.weight", L),
            "ln1_bias": _stack(sd, lay + "input_layernorm.bias", L),
            "wq": np.stack([w.T for w in wq]),
            "wk": np.stack([w.T for w in wk]),
            "wv": np.stack([w.T for w in wv]),
            "bq": np.stack(bq), "bk": np.stack(bk), "bv": np.stack(bv),
            "wo": _stack(sd, lay + "self_attention.dense.weight", L,
                         transpose=True),
            "bo": _stack(sd, lay + "self_attention.dense.bias", L),
            "ln2_scale": _stack(sd, lay + "post_attention_layernorm.weight", L),
            "ln2_bias": _stack(sd, lay + "post_attention_layernorm.bias", L),
            "w_up": _stack(sd, lay + "mlp.dense_h_to_4h.weight", L,
                           transpose=True),
            "b_up": _stack(sd, lay + "mlp.dense_h_to_4h.bias", L),
            "w_down": _stack(sd, lay + "mlp.dense_4h_to_h.weight", L,
                             transpose=True),
            "b_down": _stack(sd, lay + "mlp.dense_4h_to_h.bias", L),
        },
        "final_ln_scale": sd[pfx + "ln_f.weight"],
        "final_ln_bias": sd[pfx + "ln_f.bias"],
    }
    log_dist(f"imported HF bloom weights: {L} layers (alibi heads={nh})")
    return params




def bert_config_from_hf(hf_config) -> "Any":
    from .bert import BertConfig

    return BertConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        max_seq_len=hf_config.max_position_embeddings,
        type_vocab_size=getattr(hf_config, "type_vocab_size", 2),
        layer_norm_eps=float(getattr(hf_config, "layer_norm_eps", 1e-12)),
        gelu_approx=getattr(hf_config, "hidden_act", "gelu") in
        ("gelu_new", "gelu_pytorch_tanh", "gelu_fast"),
    )


def bert_params_from_hf(src, cfg=None) -> Params:
    """HF BertModel / BertFor* → ``models/bert`` pytree (q/k/v fused into
    one [h, 3h] block column-wise; the MLM head stays the tied embedding)."""
    sd = _normalize_state_dict(src)
    pfx = "bert." if any(k.startswith("bert.") for k in sd) else ""
    L = cfg.num_layers
    lay = pfx + "encoder.layer.{i}."

    def qkv_w(i):
        return np.concatenate(
            [sd[lay.format(i=i) + f"attention.self.{n}.weight"].T
             for n in ("query", "key", "value")], axis=1)

    def qkv_b(i):
        return np.concatenate(
            [sd[lay.format(i=i) + f"attention.self.{n}.bias"]
             for n in ("query", "key", "value")])

    emb = pfx + "embeddings."
    params: Params = {
        "embed": sd[emb + "word_embeddings.weight"],
        "pos_embed": sd[emb + "position_embeddings.weight"],
        "type_embed": sd[emb + "token_type_embeddings.weight"],
        "embed_ln_scale": sd[emb + "LayerNorm.weight"],
        "embed_ln_bias": sd[emb + "LayerNorm.bias"],
        "layers": {
            "wqkv": np.stack([qkv_w(i) for i in range(L)]),
            "bqkv": np.stack([qkv_b(i) for i in range(L)]),
            "wo": _stack(sd, lay + "attention.output.dense.weight", L,
                         transpose=True),
            "bo": _stack(sd, lay + "attention.output.dense.bias", L),
            "attn_ln_scale": _stack(sd, lay + "attention.output.LayerNorm.weight", L),
            "attn_ln_bias": _stack(sd, lay + "attention.output.LayerNorm.bias", L),
            "w_up": _stack(sd, lay + "intermediate.dense.weight", L,
                           transpose=True),
            "b_up": _stack(sd, lay + "intermediate.dense.bias", L),
            "w_down": _stack(sd, lay + "output.dense.weight", L,
                             transpose=True),
            "b_down": _stack(sd, lay + "output.dense.bias", L),
            "mlp_ln_scale": _stack(sd, lay + "output.LayerNorm.weight", L),
            "mlp_ln_bias": _stack(sd, lay + "output.LayerNorm.bias", L),
        },
    }
    h = cfg.hidden_size
    if pfx + "pooler.dense.weight" in sd:
        params["pooler_w"] = sd[pfx + "pooler.dense.weight"].T
        params["pooler_b"] = sd[pfx + "pooler.dense.bias"]
    else:
        params["pooler_w"] = np.zeros((h, h), np.float32)
        params["pooler_b"] = np.zeros((h,), np.float32)
    log_dist(f"imported HF bert weights: {L} layers")
    return params


def distilbert_config_from_hf(hf_config) -> "Any":
    from .bert import BertConfig

    return BertConfig(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.dim,
        intermediate_size=hf_config.hidden_dim,
        num_layers=hf_config.n_layers,
        num_heads=hf_config.n_heads,
        max_seq_len=hf_config.max_position_embeddings,
        type_vocab_size=1,   # DistilBERT drops token-type embeddings
        layer_norm_eps=1e-12,
        gelu_approx=getattr(hf_config, "activation", "gelu") in
        ("gelu_new", "gelu_pytorch_tanh", "gelu_fast"),
    )


def distilbert_params_from_hf(src, cfg=None) -> Params:
    """HF DistilBertModel / DistilBertFor* → ``models/bert`` pytree
    (reference policy ``module_inject/containers/distil_bert.py``). The
    missing token-type table becomes a zero row; the missing pooler becomes
    zeros (pooled output is then a constant — DistilBERT has none)."""
    sd = _normalize_state_dict(src)
    pfx = "distilbert." if any(k.startswith("distilbert.") for k in sd) else ""
    L, h = cfg.num_layers, cfg.hidden_size
    lay = pfx + "transformer.layer.{i}."

    def qkv_w(i):
        return np.concatenate(
            [sd[lay.format(i=i) + f"attention.{n}.weight"].T
             for n in ("q_lin", "k_lin", "v_lin")], axis=1)

    def qkv_b(i):
        return np.concatenate(
            [sd[lay.format(i=i) + f"attention.{n}.bias"]
             for n in ("q_lin", "k_lin", "v_lin")])

    emb = pfx + "embeddings."
    params: Params = {
        "embed": sd[emb + "word_embeddings.weight"],
        "pos_embed": sd[emb + "position_embeddings.weight"],
        "type_embed": np.zeros((1, h), np.float32),
        "embed_ln_scale": sd[emb + "LayerNorm.weight"],
        "embed_ln_bias": sd[emb + "LayerNorm.bias"],
        "layers": {
            "wqkv": np.stack([qkv_w(i) for i in range(L)]),
            "bqkv": np.stack([qkv_b(i) for i in range(L)]),
            "wo": _stack(sd, lay + "attention.out_lin.weight", L,
                         transpose=True),
            "bo": _stack(sd, lay + "attention.out_lin.bias", L),
            "attn_ln_scale": _stack(sd, lay + "sa_layer_norm.weight", L),
            "attn_ln_bias": _stack(sd, lay + "sa_layer_norm.bias", L),
            "w_up": _stack(sd, lay + "ffn.lin1.weight", L, transpose=True),
            "b_up": _stack(sd, lay + "ffn.lin1.bias", L),
            "w_down": _stack(sd, lay + "ffn.lin2.weight", L, transpose=True),
            "b_down": _stack(sd, lay + "ffn.lin2.bias", L),
            "mlp_ln_scale": _stack(sd, lay + "output_layer_norm.weight", L),
            "mlp_ln_bias": _stack(sd, lay + "output_layer_norm.bias", L),
        },
        "pooler_w": np.zeros((h, h), np.float32),
        "pooler_b": np.zeros((h,), np.float32),
    }
    log_dist(f"imported HF distilbert weights: {L} layers")
    return params


def megatron_gpt_params_from_sd(sd, cfg=None, ckpt_ver=None) -> Params:
    """Megatron-GPT state dict (merged to TP=1 via ``SDLoaderFactory``) →
    ``models/gpt`` pytree (reference policy
    ``module_inject/containers/megatron_gpt.py`` + ``MegatronSDLoader``).

    The fused query_key_value layouts by checkpoint version (reference
    ``state_dict_factory.py:220``): v0 = whole-tensor [q;k;v] blocks (the
    GPT-2 layout our model uses directly); v2 = per-head [q;k;v] groups,
    de-interleaved here. v1.0's (np·hn·3) ordering is rejected."""
    if ckpt_ver is None:
        # read the version BEFORE unwrapping 'module' (it lives at the top
        # level of Megatron checkpoints); default 0 matches
        # SDLoaderBase.get_checkpoint_version — defaulting to 2 would
        # silently scramble v0 whole-block QKV tensors as per-head groups
        ckpt_ver = sd.get("checkpoint_version",
                          sd.get("module", {}).get("checkpoint_version", 0))
    sd = {k: _to_numpy(v) for k, v in (sd.get("module", sd)).items()
          if k != "checkpoint_version"}
    # strip megatron prefixes down to the transformer block names
    def find(suffix):
        hits = [k for k in sd if k.endswith(suffix)]
        if len(hits) != 1:
            raise KeyError(f"expected exactly one key ending {suffix!r}, "
                           f"got {hits}")
        return sd[hits[0]]

    L = _count_indices(sd, r".*?layers\.(\d+)\.")
    nh, hd = (cfg.num_heads, cfg.head_size) if cfg is not None else (None, None)

    def layer(i, suffix):
        return find(f"layers.{i}.{suffix}")

    def qkv_to_gpt2(w):
        """[3h(, h)] megatron fused → [q|k|v] blocks (transposed for weights)."""
        if ckpt_ver in (0, 0.0):
            out = w  # already [q;k;v] whole blocks
        elif ckpt_ver in (2, 2.0):
            assert nh is not None, "cfg (num_heads) required for v2 layout"
            grouped = w.reshape((nh, 3, hd) + w.shape[1:])
            out = np.concatenate(
                [grouped[:, j].reshape((nh * hd,) + w.shape[1:])
                 for j in range(3)], axis=0)
        else:
            raise ValueError(f"unsupported megatron checkpoint_version "
                             f"{ckpt_ver} (v0 and v2 layouts supported)")
        return out.T if out.ndim == 2 else out

    params: Params = {
        "embed": find("word_embeddings.weight"),
        "pos_embed": find("position_embeddings.weight"),
        "layers": {
            "ln1_scale": np.stack([layer(i, "input_layernorm.weight")
                                   for i in range(L)]),
            "ln1_bias": np.stack([layer(i, "input_layernorm.bias")
                                  for i in range(L)]),
            "wqkv": np.stack([qkv_to_gpt2(
                layer(i, "attention.query_key_value.weight"))
                for i in range(L)]),
            "bqkv": np.stack([qkv_to_gpt2(
                layer(i, "attention.query_key_value.bias"))
                for i in range(L)]),
            "wo": np.stack([layer(i, "attention.dense.weight").T
                            for i in range(L)]),
            "bo": np.stack([layer(i, "attention.dense.bias")
                            for i in range(L)]),
            "ln2_scale": np.stack([layer(i, "post_attention_layernorm.weight")
                                   for i in range(L)]),
            "ln2_bias": np.stack([layer(i, "post_attention_layernorm.bias")
                                  for i in range(L)]),
            "w_up": np.stack([layer(i, "mlp.dense_h_to_4h.weight").T
                              for i in range(L)]),
            "b_up": np.stack([layer(i, "mlp.dense_h_to_4h.bias")
                              for i in range(L)]),
            "w_down": np.stack([layer(i, "mlp.dense_4h_to_h.weight").T
                                for i in range(L)]),
            "b_down": np.stack([layer(i, "mlp.dense_4h_to_h.bias")
                                for i in range(L)]),
        },
        "final_ln_scale": find("final_layernorm.weight"),
        "final_ln_bias": find("final_layernorm.bias"),
    }
    log_dist(f"imported megatron-gpt weights: {L} layers "
             f"(ckpt_ver={ckpt_ver})")
    return params


def clip_config_from_hf(hf_config) -> "Any":
    from .clip import CLIPConfig, CLIPTowerConfig

    t, v = hf_config.text_config, hf_config.vision_config
    return CLIPConfig(
        vocab_size=t.vocab_size,
        max_seq_len=t.max_position_embeddings,
        eos_token_id=t.eos_token_id,
        text=CLIPTowerConfig(hidden_size=t.hidden_size,
                             intermediate_size=t.intermediate_size,
                             num_layers=t.num_hidden_layers,
                             num_heads=t.num_attention_heads,
                             layer_norm_eps=float(t.layer_norm_eps),
                             hidden_act=getattr(t, "hidden_act",
                                                "quick_gelu")),
        image_size=v.image_size,
        patch_size=v.patch_size,
        num_channels=getattr(v, "num_channels", 3),
        vision=CLIPTowerConfig(hidden_size=v.hidden_size,
                               intermediate_size=v.intermediate_size,
                               num_layers=v.num_hidden_layers,
                               num_heads=v.num_attention_heads,
                               layer_norm_eps=float(v.layer_norm_eps),
                               hidden_act=getattr(v, "hidden_act",
                                                  "quick_gelu")),
        projection_dim=hf_config.projection_dim,
    )


def _clip_tower_from_hf(sd, prefix: str, L: int) -> Params:
    lay = prefix + "encoder.layers.{i}."
    return {
        "ln1_scale": _stack(sd, lay + "layer_norm1.weight", L),
        "ln1_bias": _stack(sd, lay + "layer_norm1.bias", L),
        "wq": _stack(sd, lay + "self_attn.q_proj.weight", L, transpose=True),
        "bq": _stack(sd, lay + "self_attn.q_proj.bias", L),
        "wk": _stack(sd, lay + "self_attn.k_proj.weight", L, transpose=True),
        "bk": _stack(sd, lay + "self_attn.k_proj.bias", L),
        "wv": _stack(sd, lay + "self_attn.v_proj.weight", L, transpose=True),
        "bv": _stack(sd, lay + "self_attn.v_proj.bias", L),
        "wo": _stack(sd, lay + "self_attn.out_proj.weight", L, transpose=True),
        "bo": _stack(sd, lay + "self_attn.out_proj.bias", L),
        "ln2_scale": _stack(sd, lay + "layer_norm2.weight", L),
        "ln2_bias": _stack(sd, lay + "layer_norm2.bias", L),
        "w_up": _stack(sd, lay + "mlp.fc1.weight", L, transpose=True),
        "b_up": _stack(sd, lay + "mlp.fc1.bias", L),
        "w_down": _stack(sd, lay + "mlp.fc2.weight", L, transpose=True),
        "b_down": _stack(sd, lay + "mlp.fc2.bias", L),
    }


def clip_params_from_hf(src, cfg=None) -> Params:
    """HF CLIPModel → ``models/clip`` pytree. The vision conv patch embed
    (out, c, p, p) flattens to the unfold+matmul layout [c·p·p, out]."""
    if cfg is None:
        if not hasattr(src, "config"):
            raise ValueError("clip_params_from_hf needs cfg= when given a "
                             "bare state dict (no .config to derive it from)")
        cfg = clip_config_from_hf(src.config)
    sd = _normalize_state_dict(src)
    h_v = cfg.vision.hidden_size
    params: Params = {
        "text": {
            "embed": sd["text_model.embeddings.token_embedding.weight"],
            "pos_embed": sd["text_model.embeddings.position_embedding.weight"],
            "layers": _clip_tower_from_hf(sd, "text_model.",
                                          cfg.text.num_layers),
            "final_ln_scale": sd["text_model.final_layer_norm.weight"],
            "final_ln_bias": sd["text_model.final_layer_norm.bias"],
        },
        "vision": {
            "class_embed": sd["vision_model.embeddings.class_embedding"],
            "patch_embed": sd["vision_model.embeddings.patch_embedding.weight"]
            .reshape(h_v, -1).T,
            "pos_embed": sd["vision_model.embeddings.position_embedding.weight"],
            "pre_ln_scale": sd["vision_model.pre_layrnorm.weight"],
            "pre_ln_bias": sd["vision_model.pre_layrnorm.bias"],
            "layers": _clip_tower_from_hf(sd, "vision_model.",
                                          cfg.vision.num_layers),
            "post_ln_scale": sd["vision_model.post_layernorm.weight"],
            "post_ln_bias": sd["vision_model.post_layernorm.bias"],
        },
        "text_projection": sd["text_projection.weight"].T,
        "visual_projection": sd["visual_projection.weight"].T,
        "logit_scale": sd["logit_scale"],
    }
    log_dist(f"imported HF clip weights: text {cfg.text.num_layers}L / "
             f"vision {cfg.vision.num_layers}L")
    return params


def exaone4_config_from_hf(hf_config) -> "Any":
    from .exaone4 import Exaone4Config

    if getattr(hf_config, "rope_scaling", None):
        # same hazard as the llama guard: silently applying plain RoPE to a
        # scaled-rope checkpoint gives wrong logits everywhere
        raise ValueError(
            "rope_scaling checkpoints are not supported yet — import the "
            "base (non-scaled) EXAONE-4 variant")
    return Exaone4Config(
        vocab_size=hf_config.vocab_size,
        hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.intermediate_size,
        num_layers=hf_config.num_hidden_layers,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=getattr(hf_config, "num_key_value_heads",
                             hf_config.num_attention_heads),
        head_dim=getattr(hf_config, "head_dim", None),
        max_seq_len=hf_config.max_position_embeddings,
        sliding_window=getattr(hf_config, "sliding_window", None),
        sliding_window_pattern=getattr(hf_config, "sliding_window_pattern",
                                       4) or 4,
        rope_theta=float(getattr(hf_config, "rope_theta", 1000000.0)),
        rms_norm_eps=float(getattr(hf_config, "rms_norm_eps", 1e-5)),
        tie_embeddings=bool(getattr(hf_config, "tie_word_embeddings", False)),
        layer_types=tuple(hf_config.layer_types)
        if getattr(hf_config, "layer_types", None) else None,
    )


def exaone4_params_from_hf(src, cfg=None) -> Params:
    """HF Exaone4ForCausalLM → ``models/exaone4`` pytree (post-norm +
    QK-norm + hybrid attention)."""
    sd = _normalize_state_dict(src)
    L = cfg.num_layers
    lay = "model.layers.{i}."
    params: Params = {
        "embed": sd["model.embed_tokens.weight"],
        "layers": {
            "wq": _stack(sd, lay + "self_attn.q_proj.weight", L,
                         transpose=True),
            "wk": _stack(sd, lay + "self_attn.k_proj.weight", L,
                         transpose=True),
            "wv": _stack(sd, lay + "self_attn.v_proj.weight", L,
                         transpose=True),
            "wo": _stack(sd, lay + "self_attn.o_proj.weight", L,
                         transpose=True),
            "q_norm": _stack(sd, lay + "self_attn.q_norm.weight", L),
            "k_norm": _stack(sd, lay + "self_attn.k_norm.weight", L),
            "post_attn_norm": _stack(
                sd, lay + "post_attention_layernorm.weight", L),
            "w_gate": _stack(sd, lay + "mlp.gate_proj.weight", L,
                             transpose=True),
            "w_up": _stack(sd, lay + "mlp.up_proj.weight", L, transpose=True),
            "w_down": _stack(sd, lay + "mlp.down_proj.weight", L,
                             transpose=True),
            "post_mlp_norm": _stack(
                sd, lay + "post_feedforward_layernorm.weight", L),
        },
        "final_norm": sd["model.norm.weight"],
    }
    if "lm_head.weight" in sd and not cfg.tie_embeddings:
        params["lm_head"] = sd["lm_head.weight"].T
    log_dist(f"imported HF exaone4 weights: {L} layers "
             f"(types={cfg.resolved_layer_types()[:4]}...)")
    return params


def granitemoehybrid_config_from_hf(hf_config) -> "Any":
    """HF ``GraniteMoeHybridConfig`` (the dense variant: no sparse branch) →
    ``models/granite_hybrid.GraniteHybridConfig``. What the module does not
    have is refused, never dropped."""
    from .granite_hybrid import GraniteHybridConfig

    get = lambda key, default=None: getattr(hf_config, key, default)
    if get("num_local_experts", 0) \
            or get("attention_bias") or get("mamba_proj_bias") \
            or get("position_embedding_type", "nope") != "nope" \
            or not get("tie_word_embeddings", True):
        raise ValueError(
            "models/granite_hybrid.py runs the dense hybrid as Granite-4.0-H-"
            "Micro publishes it: no sparse branch, no biases on the "
            "projections, no positional embedding, a tied head")
    return GraniteHybridConfig(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        intermediate_size=hf_config.shared_intermediate_size,
        layer_types=tuple(hf_config.layer_types),
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        max_seq_len=hf_config.max_position_embeddings,
        rms_norm_eps=float(hf_config.rms_norm_eps),
        embedding_multiplier=float(hf_config.embedding_multiplier),
        attention_multiplier=float(hf_config.attention_multiplier),
        residual_multiplier=float(hf_config.residual_multiplier),
        logits_scaling=float(hf_config.logits_scaling),
        mamba_heads=hf_config.mamba_n_heads,
        mamba_head_dim=hf_config.mamba_d_head,
        mamba_state=hf_config.mamba_d_state,
        mamba_groups=get("mamba_n_groups", 1),
        mamba_conv=hf_config.mamba_d_conv,
        mamba_chunk=hf_config.mamba_chunk_size)


def granitemoehybrid_params_from_hf(src, cfg) -> Params:
    """HF ``GraniteMoeHybridForCausalLM`` → ``models/granite_hybrid`` pytree:
    the layers stacked BY KIND in stack order, ``mamba.in_proj`` split into
    its ``[z | xBC]`` and ``dt`` columns, the depthwise ``conv1d`` as ``[K,
    channels]`` taps, ``shared_mlp``'s fused gate-and-up kept fused, the
    tied table once."""
    sd = _normalize_state_dict(src)
    lay = "model.layers.{i}."
    by_kind = {kind: [i for i, t in enumerate(cfg.layer_types) if t == kind]
               for kind in ("mamba", "attention")}

    def stack(kind, suffix, transpose=False):
        mats = []
        for i in by_kind[kind]:
            key = lay.format(i=i) + suffix
            if key not in sd:
                raise KeyError(f"missing weight {key}")
            mats.append(sd[key].T if transpose else sd[key])
        return np.stack(mats)

    def block(kind):
        return {"norm": stack(kind, "input_layernorm.weight"),
                "mlp_norm": stack(kind, "post_attention_layernorm.weight"),
                "w_in": stack(kind, "shared_mlp.input_linear.weight", True),
                "w_out": stack(kind, "shared_mlp.output_linear.weight", True)}

    in_proj = stack("mamba", "mamba.in_proj.weight", transpose=True)
    split = cfg.d_inner + cfg.conv_dim
    params: Params = {
        "embed": sd["model.embed_tokens.weight"],
        "final_norm": sd["model.norm.weight"],
        "mamba": {
            **block("mamba"),
            "in_proj": in_proj[:, :, :split], "dt_proj": in_proj[:, :, split:],
            # conv1d.weight [channels, 1, K] -> taps [K, channels]
            "conv_w": stack("mamba", "mamba.conv1d.weight")[:, :, 0, :]
            .transpose(0, 2, 1),
            "conv_b": stack("mamba", "mamba.conv1d.bias"),
            "dt_bias": stack("mamba", "mamba.dt_bias"),
            "A_log": stack("mamba", "mamba.A_log"),
            "D": stack("mamba", "mamba.D"),
            "gate_norm": stack("mamba", "mamba.norm.weight"),
            "out_proj": stack("mamba", "mamba.out_proj.weight", True)},
        "attn": {
            **block("attention"),
            **{ours: stack("attention", f"self_attn.{theirs}.weight", True)
               for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                    ("wv", "v_proj"), ("wo", "o_proj"))}},
    }
    log_dist(f"imported HF granitemoehybrid weights: "
             f"{len(by_kind['mamba'])} mamba + {len(by_kind['attention'])} "
             f"attention layers")
    return params


def nemotron_h_config_from_hf(hf_config) -> "Any":
    """HF ``NemotronHConfig`` -> ``models/nemotron_h.NemotronHConfig``. What
    the module does not have is refused, never dropped."""
    from .nemotron_h import NemotronHConfig

    get = lambda key, default=None: getattr(hf_config, key, default)
    if get("attention_bias") or get("mamba_proj_bias") or get("mlp_bias") \
            or get("use_bias") or get("tie_word_embeddings") \
            or get("mlp_hidden_act", "relu2") != "relu2" \
            or get("n_group", 1) != 1 or get("topk_group", 1) != 1 \
            or get("n_shared_experts", 1) != 1 \
            or not get("norm_topk_prob", True) \
            or not get("use_conv_bias", True):
        raise ValueError(
            "models/nemotron_h.py runs the hybrid as Nemotron-3-Nano "
            "publishes it: no biases but the convolution's, relu2 experts of "
            "two matrices, one group of experts, one shared expert, "
            "normalised gates, an untied head")
    return NemotronHConfig(
        vocab_size=hf_config.vocab_size, hidden_size=hf_config.hidden_size,
        pattern=hf_config.hybrid_override_pattern,
        num_heads=hf_config.num_attention_heads,
        num_kv_heads=hf_config.num_key_value_heads,
        head_dim=hf_config.head_dim,
        mamba_heads=hf_config.mamba_num_heads,
        mamba_head_dim=hf_config.mamba_head_dim,
        mamba_state=hf_config.ssm_state_size,
        mamba_groups=hf_config.n_groups,
        mamba_conv=hf_config.conv_kernel, mamba_chunk=hf_config.chunk_size,
        intermediate_size=hf_config.moe_intermediate_size,
        shared_intermediate_size=hf_config.
        moe_shared_expert_intermediate_size,
        num_experts=hf_config.n_routed_experts,
        top_k=hf_config.num_experts_per_tok,
        route_scale=float(hf_config.routed_scaling_factor),
        max_seq_len=hf_config.max_position_embeddings,
        rms_norm_eps=float(hf_config.layer_norm_epsilon))


def nemotron_h_params_from_hf(src, cfg) -> Params:
    """HF ``NemotronHForCausalLM`` -> ``models/nemotron_h`` pytree: the
    layers stacked BY KIND in stack order (every layer's module is
    ``backbone.layers.N.mixer``, its one norm ``backbone.layers.N.norm``),
    ``in_proj`` split into its ``[z | xBC]`` and ``dt`` columns, the
    depthwise ``conv1d`` as ``[K, channels]`` taps, the experts' ``up_proj``
    / ``down_proj`` stacked into the two-matrix bank (in the program's
    layout: ``nemotron_h.pad_bank``), the router
    (``gate.weight``) and its choice bias (``gate.e_score_correction_bias``)
    in float32."""
    from .nemotron_h import pad_bank

    sd = _normalize_state_dict(src)
    lay = "backbone.layers.{i}."
    by_kind = {kind: [i for i, t in enumerate(cfg.pattern) if t == kind]
               for kind in "ME*"}

    def one(key, transpose=False):
        if key not in sd:
            raise KeyError(f"missing weight {key}")
        return sd[key].T if transpose else sd[key]

    def stack(kind, suffix, transpose=False):
        return np.stack([one(lay.format(i=i) + suffix, transpose)
                         for i in by_kind[kind]])

    def bank(suffix):
        return np.stack([np.stack([
            one(lay.format(i=i) + f"mixer.experts.{e}.{suffix}.weight", True)
            for e in range(cfg.num_experts)]) for i in by_kind["E"]])

    in_proj = stack("M", "mixer.in_proj.weight", transpose=True)
    split = cfg.d_inner + cfg.conv_dim
    params: Params = {
        "embed": one("backbone.embeddings.weight"),
        "final_norm": one("backbone.norm_f.weight"),
        "lm_head": one("lm_head.weight", True),
        "mamba": {
            "norm": stack("M", "norm.weight"),
            "in_proj": in_proj[:, :, :split], "dt_proj": in_proj[:, :, split:],
            # conv1d.weight [channels, 1, K] -> taps [K, channels]
            "conv_w": stack("M", "mixer.conv1d.weight")[:, :, 0, :]
            .transpose(0, 2, 1),
            "conv_b": stack("M", "mixer.conv1d.bias"),
            "dt_bias": stack("M", "mixer.dt_bias"),
            "A_log": stack("M", "mixer.A_log"),
            "D": stack("M", "mixer.D"),
            "gate_norm": stack("M", "mixer.norm.weight"),
            "out_proj": stack("M", "mixer.out_proj.weight", True)},
        "moe": {
            "norm": stack("E", "norm.weight"),
            "router": stack("E", "mixer.gate.weight", True)
            .astype(np.float32),
            "router_bias": stack("E", "mixer.gate.e_score_correction_bias")
            .astype(np.float32),
            **{k: np.asarray(v) for k, v in pad_bank(
                cfg, bank("up_proj"), bank("down_proj")).items()},
            "shared_w_up": stack(
                "E", "mixer.shared_experts.up_proj.weight", True),
            "shared_w_down": stack(
                "E", "mixer.shared_experts.down_proj.weight", True)},
        "attn": {
            "norm": stack("*", "norm.weight"),
            **{ours: stack("*", f"mixer.{theirs}.weight", True)
               for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                    ("wv", "v_proj"), ("wo", "o_proj"))}},
    }
    log_dist(f"imported HF nemotron_h weights: "
             + ", ".join(f"{len(v)} {k!r}" for k, v in by_kind.items())
             + " layers")
    return params


def resolve_module(family: str):
    """Family name → the ``deepspeed_tpu.models`` module that executes it."""
    from . import bloom, falcon, gpt, gptneox, llama, mixtral

    from . import bert as bert_mod
    from . import clip as clip_mod
    from . import exaone4 as exaone4_mod

    if family == "granitemoehybrid":     # loaded when asked for, not before
        from . import granite_hybrid

        return granite_hybrid
    if family == "nemotron_h":
        from . import nemotron_h

        return nemotron_h
    modules = {
        "llama": llama, "mistral": llama, "qwen2": llama, "qwen3": llama,
        "phi3": llama,
        "gpt2": gpt, "opt": gpt,
        "mixtral": mixtral, "qwen2_moe": mixtral, "olmoe": mixtral,
        "falcon": falcon,
        "gpt_neox": gptneox, "gptj": gptneox,
        "bloom": bloom,
        "bert": bert_mod, "distilbert": bert_mod,
        "clip": clip_mod,
        "exaone4": exaone4_mod,
    }
    if family not in modules:
        raise ValueError(f"unsupported HF family '{family}' "
                         f"(supported: {sorted(modules)})")
    return modules[family]


def is_hf_model(model) -> bool:
    """True for a live transformers/torch model (as opposed to a ModelSpec
    or one of our model modules)."""
    return (hasattr(model, "state_dict") and callable(model.state_dict)
            and hasattr(model, "config")
            and hasattr(model.config, "model_type"))


def spec_from_hf(model, family: Optional[str] = None,
                 compute_dtype=None):
    """Live transformers model → a ``ModelSpec`` carrying the imported
    weights — makes ``deepspeed_tpu.initialize(model=hf_model, ...)`` work
    exactly like the reference's ``deepspeed.initialize(model=hf_model)``
    (engine selection ``deepspeed/__init__.py:198-241``)."""
    import dataclasses

    import jax.numpy as jnp

    family = family or getattr(model.config, "model_type", None)
    module = resolve_module(family)
    cfg, params = from_hf(model, family)
    spec = module.model_spec(
        cfg, compute_dtype=compute_dtype or jnp.bfloat16)
    return dataclasses.replace(spec, params=params)


_FAMILIES = {
    "llama": (llama_config_from_hf, llama_params_from_hf),
    "mistral": (llama_config_from_hf, llama_params_from_hf),
    "qwen2": (llama_config_from_hf, llama_params_from_hf),
    "qwen3": (llama_config_from_hf, llama_params_from_hf),
    "phi3": (llama_config_from_hf, phi3_params_from_hf),
    "gpt2": (gpt2_config_from_hf, gpt2_params_from_hf),
    "opt": (opt_config_from_hf, opt_params_from_hf),
    "mixtral": (mixtral_config_from_hf, mixtral_params_from_hf),
    "qwen2_moe": (qwen2_moe_config_from_hf, qwen2_moe_params_from_hf),
    "olmoe": (olmoe_config_from_hf, olmoe_params_from_hf),
    "falcon": (falcon_config_from_hf, falcon_params_from_hf),
    "gpt_neox": (gptneox_config_from_hf, gptneox_params_from_hf),
    "gptj": (gptj_config_from_hf, gptj_params_from_hf),
    "bloom": (bloom_config_from_hf, bloom_params_from_hf),
    "bert": (bert_config_from_hf, bert_params_from_hf),
    "distilbert": (distilbert_config_from_hf, distilbert_params_from_hf),
    "clip": (clip_config_from_hf, clip_params_from_hf),
    "exaone4": (exaone4_config_from_hf, exaone4_params_from_hf),
    "granitemoehybrid": (granitemoehybrid_config_from_hf,
                         granitemoehybrid_params_from_hf),
    "nemotron_h": (nemotron_h_config_from_hf, nemotron_h_params_from_hf),
}


def from_hf(model, family: Optional[str] = None):
    """One-stop conversion: (our_config, our_params) from a transformers
    model instance. Family is sniffed from ``model.config.model_type``."""
    family = family or getattr(model.config, "model_type", None)
    if family not in _FAMILIES:
        raise ValueError(f"unsupported HF family '{family}' "
                         f"(supported: {sorted(_FAMILIES)})")
    cfg_fn, params_fn = _FAMILIES[family]
    cfg = cfg_fn(model.config)
    return cfg, params_fn(model, cfg)


def load_hf_checkpoint_with_family(path: str,
                                   family: Optional[str] = None):
    """Load a LOCAL HF checkpoint directory (no network) → (family_name,
    our_config, our_params). Causal-LM head classes are tried first; encoder
    and contrastive families (bert/distilbert/clip) fall back to the base
    AutoModel class."""
    import transformers

    try:
        model = transformers.AutoModelForCausalLM.from_pretrained(
            path, local_files_only=True, torch_dtype="float32")
    except ValueError:
        model = transformers.AutoModel.from_pretrained(
            path, local_files_only=True, torch_dtype="float32")
    family = family or model.config.model_type
    cfg, params = from_hf(model, family)
    return family, cfg, params


def load_hf_checkpoint(path: str, family: Optional[str] = None):
    """Load a LOCAL HF checkpoint directory (no network) and convert."""
    _, cfg, params = load_hf_checkpoint_with_family(path, family)
    return cfg, params


def load_checkpoint_dir_module(path: str):
    """Checkpoint directory → (family_name, model_module, our_config,
    our_params) — the shared resolution step behind
    ``init_inference(checkpoint=)`` and the v2 ``build_hf_engine``; callers
    gate on the module capability they need (``apply_cached`` for v1 decode,
    ``apply_paged`` for the paged v2 path). The family name is kept separate
    from the module name for error messages (aliases: distilbert → bert)."""
    fam_name, cfg, params = load_hf_checkpoint_with_family(path)
    return fam_name, resolve_module(fam_name), cfg, params
