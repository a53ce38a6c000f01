"""HF-weight import parity tests: our forward must match transformers' logits
on the same weights (reference model: checkpoint-loading tests under
``tests/unit/inference`` / ``module_inject``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

transformers = pytest.importorskip("transformers")
torch = pytest.importorskip("torch")

from deepspeed_tpu.models import gpt, llama
from deepspeed_tpu.models.hf_import import (from_hf, gpt2_params_from_hf,
                                            llama_params_from_hf)


@pytest.fixture(scope="module")
def hf_llama():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    torch.manual_seed(0)
    return transformers.LlamaForCausalLM(hf_cfg).eval()


@pytest.fixture(scope="module")
def hf_gpt2():
    hf_cfg = transformers.GPT2Config(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64)
    torch.manual_seed(1)
    return transformers.GPT2LMHeadModel(hf_cfg).eval()


def test_llama_logit_parity(hf_llama):
    cfg, params = from_hf(hf_llama)
    assert cfg.num_kv_heads == 2 and cfg.num_layers == 2
    tokens = np.random.RandomState(0).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_llama(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.apply(cfg, params, jnp.asarray(tokens),
                                  compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_llama_generation_parity(hf_llama):
    """Greedy decode through OUR inference engine matches HF generate."""
    cfg, params = from_hf(hf_llama)
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.inference import init_inference

    mesh_lib.set_mesh(None)
    eng = init_inference(llama, model_cfg=cfg, params=params,
                         config={"dtype": "float32", "prefill_bucket": 8})
    prompt = np.array([[5, 9, 17]], np.int32)
    ours = eng.generate(prompt, max_new_tokens=6)
    with torch.no_grad():
        ref = hf_llama.generate(torch.tensor(prompt), max_new_tokens=6,
                                do_sample=False).numpy()[:, 3:]
    np.testing.assert_array_equal(ours, ref)


def test_gpt2_logit_parity(hf_gpt2):
    cfg, params = from_hf(hf_gpt2)
    tokens = np.random.RandomState(2).randint(0, 128, (2, 12))
    with torch.no_grad():
        ref = hf_gpt2(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(gpt.apply(cfg, params, jnp.asarray(tokens),
                                compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_state_dict_mapping_inputs(hf_llama):
    """Importer accepts raw state-dict mappings, not just modules."""
    cfg, _ = from_hf(hf_llama)
    sd = {k: v.numpy() for k, v in hf_llama.state_dict().items()}
    params = llama_params_from_hf(sd, cfg)
    assert params["layers"]["wq"].shape == (2, 64, 64)
    assert params["layers"]["wk"].shape == (2, 64, 32)  # GQA: 2 kv heads


def test_unsupported_family_raises(hf_gpt2):
    with pytest.raises(ValueError):
        from_hf(hf_gpt2, family="rwkv")


@pytest.fixture(scope="module")
def hf_qwen2():
    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    torch.manual_seed(3)
    m = transformers.Qwen2ForCausalLM(hf_cfg).eval()
    # make the (zero-init-adjacent) biases matter for the parity check
    with torch.no_grad():
        for layer in m.model.layers:
            for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                         layer.self_attn.v_proj):
                proj.bias.normal_(0.0, 0.5)
    return m


def test_qwen2_logit_parity(hf_qwen2):
    """ADVICE r1 (high): qwen2 QKV biases were silently dropped."""
    cfg, params = from_hf(hf_qwen2)
    assert cfg.attention_bias and "bq" in params["layers"]
    tokens = np.random.RandomState(4).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_qwen2(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.apply(cfg, params, jnp.asarray(tokens),
                                  compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_bias_mismatch_raises(hf_llama):
    """Importer refuses configs whose attention_bias contradicts the ckpt."""
    import dataclasses

    cfg, _ = from_hf(hf_llama)
    bad = dataclasses.replace(cfg, attention_bias=True)
    with pytest.raises(ValueError, match="attention_bias"):
        llama_params_from_hf(hf_llama, bad)


@pytest.fixture(scope="module")
def hf_phi3():
    hf_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False, pad_token_id=0, bos_token_id=1,
        eos_token_id=2)
    torch.manual_seed(5)
    return transformers.Phi3ForCausalLM(hf_cfg).eval()


@pytest.fixture(scope="module")
def hf_falcon():
    hf_cfg = transformers.FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=True, parallel_attn=True,
        new_decoder_architecture=False, bias=False, rope_theta=10000.0,
        max_position_embeddings=64, alibi=False)
    torch.manual_seed(6)
    return transformers.FalconForCausalLM(hf_cfg).eval()


@pytest.fixture(scope="module")
def hf_mixtral():
    hf_cfg = transformers.MixtralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    torch.manual_seed(7)
    return transformers.MixtralForCausalLM(hf_cfg).eval()


def test_phi3_logit_parity(hf_phi3):
    """Fused qkv_proj / gate_up_proj split (reference .../phi3)."""
    cfg, params = from_hf(hf_phi3)
    tokens = np.random.RandomState(5).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_phi3(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.apply(cfg, params, jnp.asarray(tokens),
                                  compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_falcon_logit_parity(hf_falcon):
    """Parallel-attention MQA block (reference .../falcon)."""
    from deepspeed_tpu.models import falcon

    cfg, params = from_hf(hf_falcon)
    assert cfg.num_kv_heads == 1 and cfg.parallel_attn
    tokens = np.random.RandomState(6).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_falcon(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(falcon.apply(cfg, params, jnp.asarray(tokens),
                                   compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_mixtral_logit_parity(hf_mixtral):
    """Expert-bank stacking (reference .../mixtral)."""
    from deepspeed_tpu.models import mixtral

    cfg, params = from_hf(hf_mixtral)
    tokens = np.random.RandomState(7).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_mixtral(torch.tensor(tokens)).logits.numpy()
    logits, _aux = mixtral.apply(cfg, params, jnp.asarray(tokens),
                                 compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), ref, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("family", ["mistral", "qwen2", "phi3", "falcon",
                                    "mixtral"])
def test_family_tp_sharded_generate(family, hf_qwen2, hf_phi3, hf_falcon,
                                    hf_mixtral, devices8):
    """VERDICT r1 #4: import + TP-sharded greedy generate per family on the
    8-device mesh, matching HF generate."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.inference import init_inference
    from deepspeed_tpu.models import falcon, mixtral

    if family == "mistral":
        hf_cfg = transformers.MistralConfig(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
            max_position_embeddings=64, rope_theta=10000.0,
            tie_word_embeddings=False)
        torch.manual_seed(8)
        hf_model = transformers.MistralForCausalLM(hf_cfg).eval()
    else:
        hf_model = {"qwen2": hf_qwen2, "phi3": hf_phi3, "falcon": hf_falcon,
                    "mixtral": hf_mixtral}[family]
    module = {"falcon": falcon, "mixtral": mixtral}.get(family, llama)
    cfg, params = from_hf(hf_model)

    mesh_lib.set_mesh(None)
    eng = init_inference(module, model_cfg=cfg, params=params,
                         config={"dtype": "float32", "prefill_bucket": 8,
                                 "tensor_parallel": {"tp_size": 2}})
    assert eng.mesh_mgr.tp_world_size == 2
    # spot-check an actual TP shard (wq out-dim split over 'tensor')
    wq = eng.params["layers"]["wq"]
    assert wq.addressable_shards[0].data.shape[-1] == wq.shape[-1] // 2
    prompt = np.array([[5, 9, 17, 23]], np.int32)
    ours = eng.generate(prompt, max_new_tokens=6)
    with torch.no_grad():
        ref = hf_model.generate(torch.tensor(prompt), max_new_tokens=6,
                                do_sample=False).numpy()[:, 4:]
    np.testing.assert_array_equal(ours, ref)


@pytest.mark.parametrize("mq,par,tie", [(False, False, False),
                                        (False, True, True),
                                        (True, False, True)])
def test_falcon_variant_logit_parity(mq, par, tie):
    """Falcon config variants: multi_query=False uses the per-head
    interleaved fused-QKV layout; parallel_attn=False has a distinct
    post-attention norm; untied checkpoints keep their lm_head."""
    from deepspeed_tpu.models import falcon

    hf_cfg = transformers.FalconConfig(
        vocab_size=128, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, multi_query=mq, parallel_attn=par,
        new_decoder_architecture=False, bias=False, rope_theta=10000.0,
        max_position_embeddings=64, alibi=False, tie_word_embeddings=tie)
    torch.manual_seed(9)
    hf_model = transformers.FalconForCausalLM(hf_cfg).eval()
    cfg, params = from_hf(hf_model)
    assert cfg.tie_embeddings == tie and ("lm_head" in params) == (not tie)
    tokens = np.random.RandomState(9).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(falcon.apply(cfg, params, jnp.asarray(tokens),
                                   compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_rope_scaling_rejected():
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4,
        rope_scaling={"rope_type": "linear", "factor": 2.0})
    from deepspeed_tpu.models.hf_import import llama_config_from_hf

    with pytest.raises(ValueError, match="rope_scaling"):
        llama_config_from_hf(hf_cfg)


def test_opt_logit_parity():
    """OPT → GPT family (pre-LN, ReLU, +2 position offset, fused QKV)."""
    from deepspeed_tpu.models import gpt

    hf_cfg = transformers.OPTConfig(
        vocab_size=128, hidden_size=64, ffn_dim=256, num_hidden_layers=2,
        num_attention_heads=4, max_position_embeddings=64,
        do_layer_norm_before=True, activation_function="relu",
        word_embed_proj_dim=64)
    torch.manual_seed(10)
    hf_model = transformers.OPTForCausalLM(hf_cfg).eval()
    cfg, params = from_hf(hf_model)
    assert cfg.activation == "relu"
    tokens = np.random.RandomState(10).randint(4, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(gpt.apply(cfg, params, jnp.asarray(tokens),
                                compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_qwen2_moe_logit_parity():
    """Qwen2-MoE → mixtral family: shared sigmoid-gated expert, QKV biases,
    unnormalized top-k gates (reference .../qwen_v2_moe)."""
    from deepspeed_tpu.models import mixtral

    hf_cfg = transformers.Qwen2MoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=48, shared_expert_intermediate_size=80,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_experts=4, num_experts_per_tok=2, norm_topk_prob=False,
        decoder_sparse_step=1, mlp_only_layers=[],
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-6,
        tie_word_embeddings=False)
    torch.manual_seed(11)
    hf_model = transformers.Qwen2MoeForCausalLM(hf_cfg).eval()
    cfg, params = from_hf(hf_model)
    assert cfg.attention_bias and not cfg.norm_topk_prob
    assert "shared_w_gate" in params["layers"]["moe"]
    tokens = np.random.RandomState(11).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    logits, _aux = mixtral.apply(cfg, params, jnp.asarray(tokens),
                                 compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), ref, rtol=2e-3, atol=2e-3)


def test_olmoe_logit_parity():
    """OLMoE -> mixtral family: an RMSNorm over the WHOLE q and k projections
    before rope, raw (unnormalised) top-k gates, no shared expert; against
    transformers' own ``OlmoeForCausalLM`` and against the benchmark's plain
    reference on the same imported weights."""
    from benchmark.families import olmoe as family
    from benchmark.reference import olmoe as reference
    from deepspeed_tpu.models import mixtral

    hf_cfg = transformers.OlmoeConfig(
        vocab_size=128, hidden_size=64, intermediate_size=48,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_experts=8, num_experts_per_tok=4, norm_topk_prob=False,
        max_position_embeddings=64, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    torch.manual_seed(13)
    hf_model = transformers.OlmoeForCausalLM(hf_cfg).eval()
    with torch.no_grad():      # norms start at one: a weight unused would pass
        for name, p in hf_model.named_parameters():
            if "norm" in name:
                p.copy_(1.0 + 0.2 * torch.randn_like(p))
    cfg, params = from_hf(hf_model)
    assert cfg.qk_proj_norm and not cfg.norm_topk_prob and not cfg.drop_tokens
    assert params["layers"]["q_norm"].shape == (2, 64)
    assert params["layers"]["k_norm"].shape == (2, 32)
    assert params["layers"]["moe"]["w_gate"].shape == (2, 8, 64, 48)
    tokens = np.random.RandomState(13).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    logits, _aux = mixtral.apply(cfg, params, jnp.asarray(tokens),
                                 compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(logits), ref, rtol=2e-3, atol=2e-3)
    plain = reference.logits(hf_cfg.to_dict(), family.Weights(params),
                             tokens[0])
    np.testing.assert_allclose(np.asarray(plain), ref[0], rtol=2e-3,
                               atol=2e-3)


def test_gptneox_logit_parity():
    """GPT-NeoX: fused per-head QKV de-interleave, partial rotary
    (rotary_pct), parallel residual with separate norms."""
    from deepspeed_tpu.models import gptneox

    hf_cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=0.25,
        use_parallel_residual=True, hidden_act="gelu")
    torch.manual_seed(12)
    hf_model = transformers.GPTNeoXForCausalLM(hf_cfg).eval()
    cfg, params = from_hf(hf_model)
    assert cfg.rot_dim == 4 and cfg.parallel_residual and not cfg.gelu_approx
    tokens = np.random.RandomState(12).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(gptneox.apply(cfg, params, jnp.asarray(tokens),
                                    compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_gptneox_sequential_variant():
    """use_parallel_residual=False checkpoints run the sequential ordering."""
    from deepspeed_tpu.models import gptneox

    hf_cfg = transformers.GPTNeoXConfig(
        vocab_size=128, hidden_size=64, intermediate_size=256,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, rotary_pct=1.0,
        use_parallel_residual=False)
    torch.manual_seed(13)
    hf_model = transformers.GPTNeoXForCausalLM(hf_cfg).eval()
    cfg, params = from_hf(hf_model)
    assert not cfg.parallel_residual
    tokens = np.random.RandomState(13).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(gptneox.apply(cfg, params, jnp.asarray(tokens),
                                    compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_gptj_logit_parity():
    """GPT-J: interleaved (rotate-every-two) partial rotary, shared ln,
    bias-free attention, lm_head bias."""
    from deepspeed_tpu.models import gptneox

    hf_cfg = transformers.GPTJConfig(
        vocab_size=128, n_embd=64, n_layer=2, n_head=4, n_positions=64,
        rotary_dim=8, n_inner=None, activation_function="gelu_new")
    torch.manual_seed(14)
    hf_model = transformers.GPTJForCausalLM(hf_cfg).eval()
    cfg, params = from_hf(hf_model, family="gptj")
    assert cfg.rotary_interleaved and cfg.shared_ln and cfg.lm_head_bias
    tokens = np.random.RandomState(14).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(gptneox.apply(cfg, params, jnp.asarray(tokens),
                                    compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_bloom_logit_parity():
    """BLOOM: ALiBi bias, embedding layernorm, fused QKV de-interleave
    ((nh, 3, hd) row grouping), tied head."""
    from deepspeed_tpu.models import bloom as bloom_mod

    hf_cfg = transformers.BloomConfig(
        vocab_size=128, hidden_size=64, n_layer=2, n_head=4,
        layer_norm_epsilon=1e-5)
    torch.manual_seed(15)
    hf_model = transformers.BloomForCausalLM(hf_cfg).eval()
    cfg, params = from_hf(hf_model)
    tokens = np.random.RandomState(15).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(bloom_mod.apply(cfg, params, jnp.asarray(tokens),
                                      compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_bloom_cached_matches_full():
    from deepspeed_tpu.models import bloom as bloom_mod

    cfg = bloom_mod.BloomConfig.tiny()
    params = bloom_mod.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.RandomState(16).randint(0, 256, (2, 12)))
    full = bloom_mod.apply(cfg, params, tokens, compute_dtype=jnp.float32)
    cache = bloom_mod.init_cache(cfg, 2, 32, dtype=jnp.float32)
    logits1, cache = bloom_mod.apply_cached(
        cfg, params, tokens[:, :8], cache, jnp.int32(0),
        compute_dtype=jnp.float32)
    logits2, _ = bloom_mod.apply_cached(
        cfg, params, tokens[:, 8:], cache, jnp.int32(8),
        compute_dtype=jnp.float32)
    got = np.concatenate([np.asarray(logits1), np.asarray(logits2)], axis=1)
    np.testing.assert_allclose(got, np.asarray(full), rtol=2e-4, atol=2e-4)


def test_gptj_cached_matches_full():
    from deepspeed_tpu.models import gptneox

    cfg = gptneox.GPTNeoXConfig.tiny(rotary_dim=8, rotary_interleaved=True,
                                     shared_ln=True, qkv_bias=False,
                                     attn_out_bias=False, lm_head_bias=True,
                                     gelu_approx=True)
    params = gptneox.init(cfg, jax.random.PRNGKey(1))
    tokens = jnp.asarray(np.random.RandomState(17).randint(0, 256, (2, 12)))
    full = gptneox.apply(cfg, params, tokens, compute_dtype=jnp.float32)
    cache = gptneox.init_cache(cfg, 2, 32, dtype=jnp.float32)
    logits1, cache = gptneox.apply_cached(
        cfg, params, tokens[:, :8], cache, jnp.int32(0),
        compute_dtype=jnp.float32)
    logits2, _ = gptneox.apply_cached(
        cfg, params, tokens[:, 8:], cache, jnp.int32(8),
        compute_dtype=jnp.float32)
    got = np.concatenate([np.asarray(logits1), np.asarray(logits2)], axis=1)
    np.testing.assert_allclose(got, np.asarray(full), rtol=2e-4, atol=2e-4)


def test_initialize_accepts_hf_model(hf_llama, devices8):
    """Reference UX parity: deepspeed.initialize(model=<transformers model>)
    — weights import automatically and the engine trains on them."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib

    mesh_lib.set_mesh(None)
    engine, _, _, _ = dst.initialize(
        model=hf_llama,
        config={"train_batch_size": 8, "bf16": {"enabled": False},
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 2}})
    rng = np.random.RandomState(20)
    losses = [float(engine.train_batch(
        {"tokens": rng.randint(0, 128, (8, 17)).astype(np.int32)}).loss)
        for _ in range(5)]
    assert losses[-1] < losses[0]


def test_initialize_rejects_non_model():
    import deepspeed_tpu as dst

    with pytest.raises(TypeError, match="ModelSpec or a transformers"):
        dst.initialize(model=object(), config={"train_batch_size": 1})


def test_init_inference_accepts_hf_model(hf_gpt2):
    """Reference UX parity: init_inference(<transformers model>) — the
    kernel-injection entry routes to the family's fused implementation."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib

    mesh_lib.set_mesh(None)
    eng = dst.init_inference(hf_gpt2, config={"dtype": "float32"})
    tokens = np.random.RandomState(21).randint(0, 128, (2, 8))
    out = eng.generate(tokens, max_new_tokens=4, temperature=0.0)
    assert out.shape == (2, 4)
    with torch.no_grad():
        ref = hf_gpt2.generate(
            torch.tensor(tokens), max_new_tokens=4, do_sample=False,
            pad_token_id=0).numpy()
    np.testing.assert_array_equal(out, ref[:, 8:])


def test_bert_hidden_state_parity():
    """BERT encoder: our hidden states must match transformers BertModel
    (validates post-LN ordering, exact-gelu, fused QKV mapping)."""
    from deepspeed_tpu.models import bert as bert_mod

    hf_cfg = transformers.BertConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4,
        max_position_embeddings=64, type_vocab_size=2)
    torch.manual_seed(22)
    hf_model = transformers.BertModel(hf_cfg).eval()
    cfg, params = from_hf(hf_model)
    assert not cfg.gelu_approx
    tokens = np.random.RandomState(22).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).last_hidden_state.numpy()
    out = bert_mod.apply(cfg, params, jnp.asarray(tokens),
                         compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out["hidden"]), ref,
                               rtol=2e-3, atol=2e-3)
    with torch.no_grad():
        ref_pooled = hf_model(torch.tensor(tokens)).pooler_output.numpy()
    np.testing.assert_allclose(np.asarray(out["pooled"]), ref_pooled,
                               rtol=2e-3, atol=2e-3)


def test_distilbert_hidden_state_parity():
    from deepspeed_tpu.models import bert as bert_mod

    hf_cfg = transformers.DistilBertConfig(
        vocab_size=128, dim=64, hidden_dim=128, n_layers=2, n_heads=4,
        max_position_embeddings=64)
    torch.manual_seed(23)
    hf_model = transformers.DistilBertModel(hf_cfg).eval()
    cfg, params = from_hf(hf_model)
    assert cfg.type_vocab_size == 1
    tokens = np.random.RandomState(23).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).last_hidden_state.numpy()
    out = bert_mod.apply(cfg, params, jnp.asarray(tokens),
                         compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out["hidden"]), ref,
                               rtol=2e-3, atol=2e-3)


def _megatron_sd(rng, L=2, h=16, nh=4, v=64, ckpt_ver=2.0):
    sd = {"checkpoint_version": ckpt_ver,
          "word_embeddings.weight": rng.randn(v, h),
          "position_embeddings.weight": rng.randn(32, h),
          "final_layernorm.weight": rng.randn(h),
          "final_layernorm.bias": rng.randn(h)}
    for i in range(L):
        p = f"transformer.layers.{i}."
        sd[p + "input_layernorm.weight"] = rng.randn(h)
        sd[p + "input_layernorm.bias"] = rng.randn(h)
        sd[p + "attention.query_key_value.weight"] = rng.randn(3 * h, h)
        sd[p + "attention.query_key_value.bias"] = rng.randn(3 * h)
        sd[p + "attention.dense.weight"] = rng.randn(h, h)
        sd[p + "attention.dense.bias"] = rng.randn(h)
        sd[p + "post_attention_layernorm.weight"] = rng.randn(h)
        sd[p + "post_attention_layernorm.bias"] = rng.randn(h)
        sd[p + "mlp.dense_h_to_4h.weight"] = rng.randn(4 * h, h)
        sd[p + "mlp.dense_h_to_4h.bias"] = rng.randn(4 * h)
        sd[p + "mlp.dense_4h_to_h.weight"] = rng.randn(h, 4 * h)
        sd[p + "mlp.dense_4h_to_h.bias"] = rng.randn(h)
    return sd


def test_megatron_gpt_import_v2_deinterleave():
    """Megatron-GPT checkpoint import: v2 per-head [q;k;v] rows land in the
    GPT-2 [q|k|v] block layout; the model runs."""
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.models.hf_import import megatron_gpt_params_from_sd

    rng = np.random.RandomState(30)
    sd = _megatron_sd(rng)
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=16, intermediate_size=64,
                        num_layers=2, num_heads=4, max_seq_len=32)
    params = megatron_gpt_params_from_sd(dict(sd), cfg=cfg)
    w = sd["transformer.layers.0.attention.query_key_value.weight"]
    hd = 4
    q_rows = np.concatenate([w[hh * 12:hh * 12 + hd] for hh in range(4)])
    np.testing.assert_allclose(params["layers"]["wqkv"][0][:, :16], q_rows.T)
    logits = gpt.apply(cfg, params, jnp.asarray([[1, 2, 3]]),
                       compute_dtype=jnp.float32)
    assert np.isfinite(np.asarray(logits)).all()


def test_megatron_gpt_via_sd_loader_roundtrip():
    """Full path: megatron sd → 2-way TP split (SDLoaderFactory) → merge →
    import equals the direct import."""
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.models.hf_import import megatron_gpt_params_from_sd
    from deepspeed_tpu.runtime.state_dict_factory import MegatronSDLoader

    rng = np.random.RandomState(31)
    sd = {"checkpoint_version": 2.0, "module": _megatron_sd(rng)}
    del sd["module"]["checkpoint_version"]
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=16, intermediate_size=64,
                        num_layers=2, num_heads=4, max_seq_len=32)
    direct = megatron_gpt_params_from_sd(sd, cfg=cfg)
    loader = MegatronSDLoader([sd], version=2.0)
    shards = [loader.split_state_dict(2, r)[0] for r in range(2)]
    merged, _ = MegatronSDLoader(shards, version=2.0).merge_state_dict(1, 0)
    roundtrip = megatron_gpt_params_from_sd(merged, cfg=cfg)
    jax.tree.map(np.testing.assert_allclose, direct, roundtrip)


def test_megatron_gpt_v0_and_v1_versions():
    """Version handling: a module-wrapped UNVERSIONED checkpoint defaults to
    v0 (whole-block QKV used as-is, matching SDLoaderBase); v1.0 is rejected."""
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.models.hf_import import megatron_gpt_params_from_sd

    rng = np.random.RandomState(32)
    inner = _megatron_sd(rng)
    del inner["checkpoint_version"]
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=16, intermediate_size=64,
                        num_layers=2, num_heads=4, max_seq_len=32)
    params = megatron_gpt_params_from_sd({"module": dict(inner)}, cfg=cfg)
    w = inner["transformer.layers.0.attention.query_key_value.weight"]
    # v0: [q;k;v] whole blocks pass through untouched (transposed)
    np.testing.assert_allclose(params["layers"]["wqkv"][0], w.T)
    with pytest.raises(ValueError, match="checkpoint_version"):
        megatron_gpt_params_from_sd(
            {"checkpoint_version": 1.0, "module": dict(inner)}, cfg=cfg)


def test_clip_feature_parity():
    """CLIP: both towers + projections + logit scale must match transformers
    CLIPModel (the reference's clip injection policy, minus diffusers)."""
    from deepspeed_tpu.models import clip as clip_mod

    hf_cfg = transformers.CLIPConfig(
        text_config={"vocab_size": 64, "hidden_size": 32,
                     "intermediate_size": 64, "num_hidden_layers": 2,
                     "num_attention_heads": 2,
                     "max_position_embeddings": 16, "eos_token_id": 63},
        vision_config={"hidden_size": 32, "intermediate_size": 64,
                       "num_hidden_layers": 2, "num_attention_heads": 2,
                       "image_size": 32, "patch_size": 8},
        projection_dim=24)
    torch.manual_seed(33)
    hf = transformers.CLIPModel(hf_cfg).eval()
    cfg, params = from_hf(hf)
    assert cfg.num_patches == 16 and cfg.projection_dim == 24

    rs = np.random.RandomState(33)
    tokens = rs.randint(0, 62, (3, 10))
    tokens[:, -1] = 63  # eot
    images = rs.randn(2, 3, 32, 32).astype(np.float32)
    with torch.no_grad():
        ref = hf(input_ids=torch.tensor(tokens),
                 pixel_values=torch.tensor(images))
    lt, li = clip_mod.apply(cfg, params, jnp.asarray(tokens),
                            jnp.asarray(images))
    np.testing.assert_allclose(np.asarray(lt), ref.logits_per_text.numpy(),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(li), ref.logits_per_image.numpy(),
                               rtol=2e-3, atol=2e-3)
    # CLIPModel.forward returns NORMALIZED embeds; encode_* return raw
    t_feat = np.array(clip_mod.encode_text(cfg, params, jnp.asarray(tokens)))
    t_feat /= np.linalg.norm(t_feat, axis=-1, keepdims=True)
    np.testing.assert_allclose(t_feat, ref.text_embeds.numpy(),
                               rtol=2e-3, atol=2e-3)
    v_feat = np.array(clip_mod.encode_image(cfg, params,
                                            jnp.asarray(images)))
    v_feat /= np.linalg.norm(v_feat, axis=-1, keepdims=True)
    np.testing.assert_allclose(v_feat, ref.image_embeds.numpy(),
                               rtol=2e-3, atol=2e-3)


def test_clip_contrastive_training(devices8):
    """CLIP trains end to end through the engine on the InfoNCE loss."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import clip as clip_mod

    mesh_lib.set_mesh(None)
    cfg = clip_mod.CLIPConfig.tiny()
    engine, *_ = dst.initialize(
        model=clip_mod.model_spec(cfg),
        config={"train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 3e-3}},
                "zero_optimization": {"stage": 2}})
    rs = np.random.RandomState(34)
    tokens = rs.randint(0, 62, (8, 12)).astype(np.int32)
    tokens[:, -1] = 63
    batch = {"tokens": tokens,
             "images": rs.randn(8, 3, 32, 32).astype(np.float32)}
    losses = [float(engine.train_batch(batch).loss) for _ in range(6)]
    assert losses[-1] < losses[0] - 0.3, losses


def test_clip_legacy_eos_pooling():
    """OpenAI checkpoints carry eos_token_id=2 while the real EOT is the
    vocab max — parity with HF's legacy special case."""
    from deepspeed_tpu.models import clip as clip_mod

    hf_cfg = transformers.CLIPConfig(
        text_config={"vocab_size": 64, "hidden_size": 32,
                     "intermediate_size": 64, "num_hidden_layers": 2,
                     "num_attention_heads": 2,
                     "max_position_embeddings": 16, "eos_token_id": 2},
        vision_config={"hidden_size": 32, "intermediate_size": 64,
                       "num_hidden_layers": 1, "num_attention_heads": 2,
                       "image_size": 16, "patch_size": 8},
        projection_dim=16)
    torch.manual_seed(35)
    hf = transformers.CLIPModel(hf_cfg).eval()
    cfg, params = from_hf(hf)
    assert cfg.eos_token_id == 2
    rs = np.random.RandomState(35)
    tokens = rs.randint(3, 60, (2, 10))
    tokens[:, -2] = 63  # EOT = vocab max, NOT at the last position
    with torch.no_grad():
        ref = hf.get_text_features(torch.tensor(tokens)).numpy()
    ours = np.asarray(clip_mod.encode_text(cfg, params,
                                           jnp.asarray(tokens)))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_qwen3_logit_parity():
    """Qwen3: per-head q/k RMSNorm + head_dim decoupled from hidden/heads
    (head_dim=32 with hidden=64/4 heads → q_proj out 128 ≠ hidden, and the
    norm is a real parity risk if skipped)."""
    hf_cfg = transformers.Qwen3Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, max_position_embeddings=64, rope_theta=10000.0,
        rms_norm_eps=1e-5, tie_word_embeddings=False)
    torch.manual_seed(36)
    hf_model = transformers.Qwen3ForCausalLM(hf_cfg).eval()
    cfg, params = from_hf(hf_model)
    assert cfg.qk_norm and cfg.head_size == 32 and not cfg.attention_bias
    assert "q_norm" in params["layers"]
    tokens = np.random.RandomState(36).randint(0, 128, (2, 10))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(llama.apply(cfg, params, jnp.asarray(tokens),
                                  compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_qwen3_cached_decode_matches_full():
    hf_cfg = transformers.Qwen3Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=32, max_position_embeddings=64, tie_word_embeddings=False)
    torch.manual_seed(37)
    cfg, params = from_hf(transformers.Qwen3ForCausalLM(hf_cfg).eval())
    tokens = jnp.asarray(np.random.RandomState(37).randint(0, 128, (2, 12)))
    full = llama.apply(cfg, params, tokens, compute_dtype=jnp.float32)
    cache = llama.init_cache(cfg, 2, 32, dtype=jnp.float32)
    l1, cache = llama.apply_cached(cfg, params, tokens[:, :8], cache,
                                   jnp.int32(0), compute_dtype=jnp.float32)
    l2, _ = llama.apply_cached(cfg, params, tokens[:, 8:], cache,
                               jnp.int32(8), compute_dtype=jnp.float32)
    got = np.concatenate([np.asarray(l1), np.asarray(l2)], axis=1)
    np.testing.assert_allclose(got, np.asarray(full), rtol=2e-4, atol=2e-4)


def test_exaone4_logit_parity():
    """EXAONE-4: post-norm blocks, QK-norm, hybrid sliding/global layers
    with global-NoPE — all three must match transformers to pass."""
    from deepspeed_tpu.models import exaone4 as ex4

    hf_cfg = transformers.Exaone4Config(
        vocab_size=128, hidden_size=64, intermediate_size=112,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, sliding_window=8,
        sliding_window_pattern=2, rope_theta=10000.0, rms_norm_eps=1e-5,
        tie_word_embeddings=False)
    torch.manual_seed(38)
    hf_model = transformers.Exaone4ForCausalLM(hf_cfg).eval()
    cfg, params = from_hf(hf_model)
    types = cfg.resolved_layer_types()
    assert "sliding_attention" in types and "full_attention" in types
    tokens = np.random.RandomState(38).randint(0, 128, (2, 24))
    with torch.no_grad():
        ref = hf_model(torch.tensor(tokens)).logits.numpy()
    ours = np.asarray(ex4.apply(cfg, params, jnp.asarray(tokens),
                                compute_dtype=jnp.float32))
    np.testing.assert_allclose(ours, ref, rtol=2e-3, atol=2e-3)


def test_exaone4_cached_matches_full():
    from deepspeed_tpu.models import exaone4 as ex4

    cfg = ex4.Exaone4Config.tiny()
    params = ex4.init(cfg, jax.random.PRNGKey(5))
    tokens = jnp.asarray(np.random.RandomState(39).randint(0, 256, (2, 24)))
    full = ex4.apply(cfg, params, tokens, compute_dtype=jnp.float32)
    cache = ex4.init_cache(cfg, 2, 48, dtype=jnp.float32)
    l1, cache = ex4.apply_cached(cfg, params, tokens[:, :16], cache,
                                 jnp.int32(0), compute_dtype=jnp.float32)
    l2, _ = ex4.apply_cached(cfg, params, tokens[:, 16:], cache,
                             jnp.int32(16), compute_dtype=jnp.float32)
    got = np.concatenate([np.asarray(l1), np.asarray(l2)], axis=1)
    np.testing.assert_allclose(got, np.asarray(full), rtol=2e-4, atol=2e-4)
