"""Inference engine tests: KV-cache parity, v1 generation, TP sharding,
ragged/paged v2 parity with v1 (reference test model: tests/unit/inference)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference import (InferenceConfig, SamplingParams,
                                     build_engine_v2, init_inference)
from deepspeed_tpu.inference.ragged import BlockedAllocator, StateManager
from deepspeed_tpu.models import llama


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(max_seq_len=256)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_cached_matches_full_forward(tiny):
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size)
    full = llama.apply(cfg, params, tokens, compute_dtype=jnp.float32)

    cache = llama.init_cache(cfg, 2, 32, dtype=jnp.float32)
    logits, cache = llama.apply_cached(cfg, params, tokens, cache,
                                       jnp.zeros((2,), jnp.int32),
                                       compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(full), np.asarray(logits),
                               rtol=2e-4, atol=2e-4)
    # decode one more token and compare against the longer full forward
    nxt = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    step_logits, _ = llama.apply_cached(cfg, params, nxt, cache,
                                        jnp.full((2,), 17, jnp.int32),
                                        compute_dtype=jnp.float32)
    full2 = llama.apply(cfg, params, jnp.concatenate([tokens, nxt], axis=1),
                        compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(full2[:, -1]),
                               np.asarray(step_logits[:, 0]),
                               rtol=2e-4, atol=2e-4)


def test_v1_generate_greedy_matches_stepwise_full(tiny):
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    engine = init_inference(llama, model_cfg=cfg, params=params,
                            config={"dtype": "float32", "prefill_bucket": 16})
    prompts = np.array([[5, 7, 11, 13], [2, 3, 0, 0]], np.int32)
    lens = np.array([4, 2], np.int32)
    out = engine.generate(prompts, prompt_lengths=lens, max_new_tokens=5)
    assert out.shape == (2, 5)

    # oracle: greedy decode by rerunning the full forward each step
    for b in range(2):
        seq = list(prompts[b, :lens[b]])
        for i in range(5):
            logits = llama.apply(cfg, params, jnp.asarray([seq]),
                                 compute_dtype=jnp.float32)
            tok = int(jnp.argmax(logits[0, -1]))
            assert tok == out[b, i], f"seq {b} step {i}"
            seq.append(tok)


def test_v1_generate_eos_and_sampling(tiny):
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    engine = init_inference(llama, model_cfg=cfg, params=params,
                            config={"dtype": "float32"})
    prompts = np.array([[1, 2, 3]], np.int32)
    greedy_first = engine.generate(prompts, max_new_tokens=2)[0, 0]
    out = engine.generate(prompts, max_new_tokens=4,
                          eos_token_id=int(greedy_first))
    assert (out[0] == greedy_first).all()  # EOS fills the remainder
    sampled = engine.generate(prompts, max_new_tokens=4, temperature=0.8,
                              top_k=8, top_p=0.9, seed=3)
    assert sampled.shape == (1, 4)
    assert ((sampled >= 0) & (sampled < cfg.vocab_size)).all()


def test_top_p_sampling_not_degenerate():
    """Regression: top-p cutoff must be the SMALLEST kept logit — a max-based
    cutoff silently degenerates every top_p run to greedy."""
    from deepspeed_tpu.inference.sampling import SamplingParams, sample

    logits = jnp.log(jnp.asarray([[0.4, 0.35, 0.2, 0.05]]))
    sp = SamplingParams(temperature=1.0, top_p=0.9)
    toks = {int(sample(jax.random.PRNGKey(s), logits, sp)[0])
            for s in range(40)}
    assert len(toks) > 1          # not greedy
    assert 3 not in toks          # the 5% tail is cut


def test_v2_rejects_oversized_prompt(tiny):
    cfg, params = tiny
    from deepspeed_tpu.comm import mesh as mesh_lib

    mesh_lib.set_mesh(None)
    v2 = build_engine_v2(llama, cfg, params,
                         config={"dtype": "float32",
                                 "ragged": {"max_tracked_sequences": 2,
                                            "memory_config_blocks": 4,
                                            "block_size": 16}})
    with pytest.raises(MemoryError):
        v2.generate([np.arange(100, dtype=np.int32) % cfg.vocab_size],
                    max_new_tokens=2)


def test_blocked_allocator():
    alloc = BlockedAllocator(8)
    a = alloc.allocate(3)
    assert len(set(a)) == 3 and 0 not in a
    assert alloc.free_blocks == 4
    with pytest.raises(MemoryError):
        alloc.allocate(5)
    alloc.free(a)
    assert alloc.free_blocks == 7
    with pytest.raises(ValueError):
        alloc.free([0])


def test_state_manager_slots_and_tables():
    sm = StateManager(max_sequences=2, num_blocks=16, block_size=4,
                      max_blocks_per_seq=4)
    d1 = sm.admit(10, prompt_len=6)  # needs ceil(6/4)+1 = 3 blocks
    assert len(d1.blocks) == 3
    table = sm.block_table(d1)
    assert table.shape == (4,) and (table[3:] == 0).all()
    d2 = sm.admit(11, prompt_len=1)
    assert not sm.can_admit(1)  # no slots left
    sm.retire(10)
    assert sm.can_admit(1)
    d1b = sm.admit(12, prompt_len=2)
    assert d1b.slot == d1.slot  # slot reused


def test_paged_matches_dense_cache(tiny):
    cfg, params = tiny
    num_blocks, bs = 16, 8
    cache = llama.init_paged_cache(cfg, num_blocks, bs, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 11), 0, cfg.vocab_size)
    pad = jnp.pad(tokens, ((0, 0), (0, 5)))  # pad to 16
    table = jnp.asarray([[1, 2, 3, 0]], jnp.int32)
    valid = jnp.arange(16)[None, :] < 11
    logits, cache = llama.apply_paged(cfg, params, pad, cache, table,
                                      jnp.zeros((1,), jnp.int32), valid=valid,
                                      compute_dtype=jnp.float32)
    full = llama.apply(cfg, params, tokens, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(full[:, -1]),
                               np.asarray(logits[:, 10]), rtol=2e-4, atol=2e-4)
    # decode step
    nxt = jnp.argmax(logits[:, 10], axis=-1)[:, None]
    step_logits, _ = llama.apply_paged(cfg, params, nxt, cache, table,
                                       jnp.full((1,), 11, jnp.int32),
                                       compute_dtype=jnp.float32)
    full2 = llama.apply(cfg, params, jnp.concatenate([tokens, nxt], axis=1),
                        compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(full2[:, -1]),
                               np.asarray(step_logits[:, 0]),
                               rtol=2e-4, atol=2e-4)


def test_v2_continuous_batching_matches_v1(tiny):
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    v1 = init_inference(llama, model_cfg=cfg, params=params,
                        config={"dtype": "float32", "prefill_bucket": 16})
    v2 = build_engine_v2(llama, cfg, params,
                         config={"dtype": "float32", "prefill_bucket": 16,
                                 "ragged": {"max_tracked_sequences": 4,
                                            "max_ragged_batch_size": 4,
                                            "memory_config_blocks": 64,
                                            "block_size": 16}})
    prompts = [np.array([5, 7, 11, 13], np.int32),
               np.array([2, 3], np.int32),
               np.array([9, 1, 4], np.int32)]
    got = v2.generate(prompts, max_new_tokens=5)
    for i, p in enumerate(prompts):
        ref = v1.generate(p[None, :], max_new_tokens=5)[0]
        assert got[i] == list(ref), f"prompt {i}: {got[i]} vs {list(ref)}"


def test_v2_split_prefill_matches_and_never_starves(tiny):
    """Dynamic-SplitFuse analog (reference blogs/deepspeed-fastgen): a long
    prompt admitted via put_split enters the cache one chunk per step, so
    (a) generated tokens are IDENTICAL to the one-shot prefill path, and
    (b) live decodes keep producing a token on every step while the long
    prompt is still prefilling — no head-of-line blocking."""
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    base = {"dtype": "float32", "prefill_bucket": 16,
            "ragged": {"max_tracked_sequences": 4,
                       "max_ragged_batch_size": 4,
                       "memory_config_blocks": 64, "block_size": 16}}
    rng = np.random.default_rng(0)
    long_prompt = rng.integers(0, cfg.vocab_size, (100,), dtype=np.int32)
    short = rng.integers(0, cfg.vocab_size, (8,), dtype=np.int32)
    sp = SamplingParams(greedy=True)

    # reference: one-shot prefill path
    ref = build_engine_v2(llama, cfg, params, config=dict(base))
    ref.put(1, short.tolist(), sp)
    ref.put(2, long_prompt.tolist(), sp)
    for _ in range(6):
        ref.step(sp)
    ref_short, ref_long = ref.finish(1), ref.finish(2)

    # split path: chunk=32 → 100-token prompt needs 4 chunks
    eng = build_engine_v2(llama, cfg, params,
                          config=dict(base, split_prefill_chunk=32))
    eng.put(1, short.tolist(), sp)
    eng.put_split(2, long_prompt.tolist(), sp)
    per_step = []
    first_long = None
    steps = 0
    while len(eng.state.seqs[2].generated) < 7 and steps < 20:
        out = eng.step(sp)
        per_step.append(out)
        if first_long is None and 2 in out:
            first_long = steps
        steps += 1
    # (b) the short sequence got a token on EVERY step, including the four
    # chunk-prefill steps; the long prompt's first token arrived on the
    # step its 4th chunk completed
    assert all(1 in out for out in per_step[:6])
    assert first_long == 3, f"first long token at step {first_long}"
    got_short = eng.finish(1)[:len(ref_short)]
    got_long = eng.finish(2)[:len(ref_long)]
    # (a) greedy tokens identical to the one-shot path
    assert got_long == ref_long[:len(got_long)] and len(got_long) >= 7
    assert got_short == ref_short

    # generate() end-to-end: split engine output == one-shot engine output
    ref2 = build_engine_v2(llama, cfg, params, config=dict(base))
    want = ref2.generate([long_prompt, short], max_new_tokens=5)
    eng2 = build_engine_v2(llama, cfg, params,
                           config=dict(base, split_prefill_chunk=32))
    got = eng2.generate([long_prompt, short], max_new_tokens=5)
    assert got == want


def test_a_slot_seated_by_a_steps_final_chunk_is_not_decoded_in_that_step(
        tiny):
    """A decode-shaped call is active on the slots of the sequences it
    DECODES and no other (``engine_v2._slots``): the step whose final chunk
    seats a sequence runs its decode beside it, and that decode writes
    nothing for the new sequence - the row at its ``seen_tokens`` stays
    unwritten until its first real decode (it used to be written twice; a
    recurrent state would have been advanced twice)."""
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(llama, cfg, params, config={
        "dtype": "float32", "prefill_bucket": 16, "split_prefill_chunk": 32,
        "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                   "memory_config_blocks": 64, "block_size": 16}})
    rng = np.random.default_rng(3)
    a, b = (rng.integers(0, cfg.vocab_size, (n,)).tolist() for n in (8, 11))
    eng.put(1, a)
    eng.put_split(2, b)
    out = eng.step()                 # b's only chunk, and a decode of 1
    assert set(out) == {1, 2}
    desc = eng.state.seqs[2]
    assert desc.seen_tokens == 11 and not desc.prefilling

    def row(pos):                    # layer 0's keys of sequence 2 at ``pos``
        return np.asarray(eng.cache["k"][0, desc.blocks[0], :, pos])

    assert np.abs(row(10)).max() > 0 and np.abs(row(11)).max() == 0
    live = [d for d in eng.state.seqs.values()]
    assert eng._slots(live[:1])[3].tolist() == [
        s == live[0].slot for s in range(4)]
    eng.step()                       # its first real decode writes the row
    assert np.abs(row(11)).max() > 0


def test_v1_tensor_parallel_sharding(tiny):
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    n = len(jax.devices())
    tp = 2 if n % 2 == 0 else 1
    engine = init_inference(llama, model_cfg=cfg, params=params,
                            config={"dtype": "float32",
                                    "tensor_parallel": {"tp_size": tp}})
    if tp > 1:
        spec = engine.params["layers"]["wq"].sharding.spec
        assert "tensor" in str(spec)
    out = engine.generate(np.array([[1, 2, 3]], np.int32), max_new_tokens=3)
    mesh_lib.set_mesh(None)
    single = init_inference(llama, model_cfg=cfg, params=params,
                            config={"dtype": "float32"})
    ref = single.generate(np.array([[1, 2, 3]], np.int32), max_new_tokens=3)
    np.testing.assert_array_equal(out, ref)


def test_init_inference_from_engine_checkpoint(tmp_path, devices8):
    """checkpoint= pointing at an engine save dir loads the weights
    (reference inference/engine.py:303 checkpoint loading)."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama

    mesh_lib.set_mesh(None)
    cfg = llama.LlamaConfig.tiny()
    engine, *_ = dst.initialize(
        model=llama.model_spec(cfg, compute_dtype=jnp.float32),
        config={"train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}})
    engine.train_batch({"tokens": np.zeros((8, 17), np.int32)})
    engine.save_checkpoint(str(tmp_path), tag="serve")
    trained_w = np.asarray(engine.state.params["layers"]["wq"])

    mesh_lib.set_mesh(None)
    eng = dst.init_inference(llama, model_cfg=cfg,
                             checkpoint=str(tmp_path),
                             config={"dtype": "float32"})
    np.testing.assert_allclose(np.asarray(eng.params["layers"]["wq"]),
                               trained_w, rtol=1e-6)
    out = eng.generate(np.array([[1, 2, 3]], np.int32), max_new_tokens=3)
    assert out.shape == (1, 3)


def test_init_inference_from_hf_checkpoint_dir(tmp_path):
    """checkpoint= pointing at a local HF save_pretrained dir."""
    import deepspeed_tpu as dst
    import torch
    import transformers
    from deepspeed_tpu.comm import mesh as mesh_lib

    hf_cfg = transformers.GPT2Config(vocab_size=64, n_embd=32, n_layer=1,
                                     n_head=2, n_positions=32)
    torch.manual_seed(42)
    hf = transformers.GPT2LMHeadModel(hf_cfg).eval()
    hf.save_pretrained(str(tmp_path / "gpt2"))

    mesh_lib.set_mesh(None)
    eng = dst.init_inference(checkpoint=str(tmp_path / "gpt2"),
                             config={"dtype": "float32"})
    prompt = np.array([[5, 9]], np.int32)
    ours = eng.generate(prompt, max_new_tokens=4, temperature=0.0)
    with torch.no_grad():
        ref = hf.generate(torch.tensor(prompt), max_new_tokens=4,
                          do_sample=False, pad_token_id=0).numpy()
    np.testing.assert_array_equal(ours, ref[:, 2:])


def test_init_inference_from_universal_checkpoint(tmp_path, devices8):
    """checkpoint= prefers the topology-free universal fragments when
    present (multi-host-safe path)."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.runtime.checkpoint.universal import ds_to_universal

    mesh_lib.set_mesh(None)
    cfg = llama.LlamaConfig.tiny()
    engine, *_ = dst.initialize(
        model=llama.model_spec(cfg, compute_dtype=jnp.float32),
        config={"train_batch_size": 8,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 3}})
    engine.train_batch({"tokens": np.zeros((8, 17), np.int32)})
    engine.save_checkpoint(str(tmp_path), tag="u")
    ds_to_universal(str(tmp_path), tag="u")
    trained_w = np.asarray(engine.state.params["layers"]["wq"])

    mesh_lib.set_mesh(None)
    eng = dst.init_inference(llama, model_cfg=cfg,
                             checkpoint=str(tmp_path),
                             config={"dtype": "float32"})
    np.testing.assert_allclose(np.asarray(eng.params["layers"]["wq"]),
                               trained_w, rtol=1e-6)


def test_init_inference_rejects_non_generative_family(tmp_path):
    """A CLIP checkpoint dir resolves but is refused with a clear message
    (no KV-cached decode path)."""
    import deepspeed_tpu as dst
    import torch
    import transformers

    hf_cfg = transformers.CLIPConfig(
        text_config={"vocab_size": 64, "hidden_size": 32,
                     "intermediate_size": 64, "num_hidden_layers": 1,
                     "num_attention_heads": 2,
                     "max_position_embeddings": 16, "eos_token_id": 63},
        vision_config={"hidden_size": 32, "intermediate_size": 64,
                       "num_hidden_layers": 1, "num_attention_heads": 2,
                       "image_size": 16, "patch_size": 8},
        projection_dim=16)
    torch.manual_seed(44)
    transformers.CLIPModel(hf_cfg).save_pretrained(str(tmp_path / "clip"))
    with pytest.raises(ValueError, match="not generative"):
        dst.init_inference(checkpoint=str(tmp_path / "clip"), config={})


def test_build_hf_engine_v2_from_checkpoint_dir(tmp_path):
    """engine_factory parity: one call from an HF save dir to a serving
    continuous-batching engine."""
    import torch
    import transformers
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.inference.engine_v2 import build_hf_engine
    from deepspeed_tpu.inference.sampling import SamplingParams

    hf_cfg = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=1,
        max_position_embeddings=64, tie_word_embeddings=False)
    torch.manual_seed(45)
    transformers.LlamaForCausalLM(hf_cfg).save_pretrained(
        str(tmp_path / "llama"))

    mesh_lib.set_mesh(None)
    eng = build_hf_engine(
        str(tmp_path / "llama"),
        config={"dtype": "float32", "prefill_bucket": 8,
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 16, "block_size": 8}})
    sp = SamplingParams(greedy=True)
    eng.put(0, [3, 5, 7], sp)
    eng.put(1, [9, 2], sp)
    for _ in range(4):
        out = eng.step(sp)
    assert set(out) == {0, 1}
    assert all(0 <= t < 64 for d in eng.state.seqs.values()
               for t in d.generated)
    # prefill samples the first token; 4 decode steps add 4 more
    assert all(len(d.generated) == 5 for d in eng.state.seqs.values())

def _hf_factory(family):
    import transformers

    if family == "opt":
        return transformers.OPTForCausalLM(transformers.OPTConfig(
            vocab_size=64, hidden_size=32, ffn_dim=64, num_hidden_layers=2,
            num_attention_heads=2, max_position_embeddings=64,
            do_layer_norm_before=True, activation_function="relu",
            word_embed_proj_dim=32))
    if family == "mixtral":
        return transformers.MixtralForCausalLM(transformers.MixtralConfig(
            vocab_size=64, hidden_size=32, intermediate_size=48,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=1, num_local_experts=4,
            num_experts_per_tok=2, max_position_embeddings=64,
            tie_word_embeddings=False))
    if family == "falcon":
        return transformers.FalconForCausalLM(transformers.FalconConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=2,
            num_attention_heads=2, multi_query=True, parallel_attn=True,
            new_decoder_architecture=False, bias=False,
            max_position_embeddings=64, alibi=False))
    if family == "exaone4":
        return transformers.Exaone4ForCausalLM(transformers.Exaone4Config(
            vocab_size=64, hidden_size=32, intermediate_size=48,
            num_hidden_layers=2, num_attention_heads=2,
            num_key_value_heads=1, max_position_embeddings=64,
            sliding_window=8, sliding_window_pattern=2, rope_theta=10000.0,
            tie_word_embeddings=False))
    raise ValueError(family)


@pytest.mark.parametrize("family,seed", [("opt", 46), ("mixtral", 48),
                                         ("falcon", 49), ("exaone4", 51)])
def test_v2_paged_engine_matches_v1_per_family(family, seed, tmp_path):
    """Every reference-v2 family through the continuous-batching engine:
    greedy paged decode equals the v1 dense-cache decode."""
    import torch

    from deepspeed_tpu.inference.engine_v2 import build_hf_engine

    torch.manual_seed(seed)
    _hf_factory(family).save_pretrained(str(tmp_path / family))

    mesh_lib.set_mesh(None)
    eng = build_hf_engine(
        str(tmp_path / family),
        config={"dtype": "float32", "prefill_bucket": 8,
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 16, "block_size": 8}})
    sp = SamplingParams(greedy=True)
    prompt = [5, 9, 17]
    eng.put(0, prompt, sp)
    for _ in range(5):
        eng.step(sp)
    v2_tokens = list(eng.state.seqs[0].generated)

    mesh_lib.set_mesh(None)
    v1 = dst.init_inference(checkpoint=str(tmp_path / family),
                            config={"dtype": "float32", "prefill_bucket": 8})
    ref = v1.generate(np.asarray([prompt], np.int32), max_new_tokens=6,
                      temperature=0.0)[0].tolist()
    assert v2_tokens == ref, (family, v2_tokens, ref)


def test_v2_step_many_matches_per_step(tiny):
    """The fused k-step decode (ONE host sync per quantum, lax.scan over
    decode ticks) must produce exactly the per-step greedy tokens — the
    serving fast path cannot change results."""
    cfg, params = tiny
    mesh_lib.set_mesh(None)

    def make():
        return build_engine_v2(
            llama, cfg, params,
            config={"dtype": "float32", "prefill_bucket": 16,
                    "ragged": {"max_tracked_sequences": 4,
                               "max_ragged_batch_size": 4,
                               "memory_config_blocks": 64,
                               "block_size": 16}})

    prompts = [np.array([5, 7, 11, 13], np.int32),
               np.array([2, 3], np.int32),
               np.array([9, 1, 4], np.int32)]
    per_step = make().generate(prompts, max_new_tokens=6)
    fused = make().generate(prompts, max_new_tokens=6, steps_per_sync=3)
    assert fused == per_step

    # EOS inside a quantum: completion trimmed exactly at the first EOS
    eos = per_step[0][2]  # make the 3rd generated token the EOS
    ref_eos = make().generate(prompts, max_new_tokens=6, eos_token_id=eos)
    fused_eos = make().generate(prompts, max_new_tokens=6, eos_token_id=eos,
                                steps_per_sync=4)
    assert fused_eos == ref_eos
    assert fused_eos[0][-1] == eos and len(fused_eos[0]) == 3


def test_v2_step_many_direct_api(tiny):
    """step_many returns {uid: [k tokens]} and advances block tables /
    lengths exactly k; clamps at max_seq_len."""
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        llama, cfg, params,
        config={"dtype": "float32", "prefill_bucket": 16,
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 64,
                           "block_size": 16}})
    first = eng.put(0, [5, 7, 11], SamplingParams(greedy=True))
    d = eng.state.seqs[0]
    seen0 = d.seen_tokens
    out = eng.step_many(4)
    assert list(out) == [0] and len(out[0]) == 4
    assert d.seen_tokens == seen0 + 4
    # same tokens as four single steps on a fresh engine
    eng2 = build_engine_v2(
        llama, cfg, params,
        config={"dtype": "float32", "prefill_bucket": 16,
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 64,
                           "block_size": 16}})
    assert eng2.put(0, [5, 7, 11], SamplingParams(greedy=True)) == first
    singles = [eng2.step()[0] for _ in range(4)]
    assert out[0] == singles


def test_v2_step_many_context_boundary(tiny):
    """Fused and per-step paths agree at the max_seq_len boundary (the
    clamp must allow seen to reach exactly max_seq_len, like per-step)."""
    cfg, params = tiny
    mesh_lib.set_mesh(None)

    def make():
        return build_engine_v2(
            llama, cfg, params,
            config={"dtype": "float32", "prefill_bucket": 16,
                    "ragged": {"max_tracked_sequences": 2,
                               "max_ragged_batch_size": 2,
                               "memory_config_blocks": 96,
                               "block_size": 16}})

    prompt = np.arange(cfg.max_seq_len - 2, dtype=np.int32) % cfg.vocab_size
    ref = make().generate([prompt], max_new_tokens=10)
    fused = make().generate([prompt], max_new_tokens=10, steps_per_sync=8)
    assert fused == ref and len(ref[0]) >= 2, (len(ref[0]), len(fused[0]))


def test_v2_put_many_matches_sequential_put(tiny):
    """Batched admission (one compiled prefill for the burst) produces the
    same greedy first tokens and identical downstream decode as one-by-one
    put()."""
    cfg, params = tiny
    mesh_lib.set_mesh(None)

    def make():
        return build_engine_v2(
            llama, cfg, params,
            config={"dtype": "float32", "prefill_bucket": 16,
                    "ragged": {"max_tracked_sequences": 4,
                               "max_ragged_batch_size": 4,
                               "memory_config_blocks": 64,
                               "block_size": 16}})

    prompts = {0: [5, 7, 11, 13], 1: [2, 3], 2: [9, 1, 4]}
    sp = SamplingParams(greedy=True)
    a = make()
    seq_first = {u: a.put(u, p, sp) for u, p in prompts.items()}
    seq_next = a.step(sp)
    b = make()
    batch_first = b.put_many(list(prompts.items()), sp)
    batch_next = b.step(sp)
    assert batch_first == seq_first
    assert batch_next == seq_next


def test_v2_tensor_parallel_matches_single(tiny, devices8):
    """Continuous batching (incl. batched prefill + fused decode) under a
    tensor-parallel mesh produces exactly the single-device greedy tokens."""
    cfg, params = tiny
    prompts = [np.array([5, 7, 11, 13], np.int32),
               np.array([2, 3], np.int32)]
    rc = {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
          "memory_config_blocks": 64, "block_size": 16}
    mesh_lib.set_mesh(None)
    ref = build_engine_v2(
        llama, cfg, params,
        config={"dtype": "float32", "prefill_bucket": 16, "ragged": rc}
    ).generate(prompts, max_new_tokens=6)
    mesh_lib.set_mesh(None)
    got = build_engine_v2(
        llama, cfg, params,
        config={"dtype": "float32", "prefill_bucket": 16,
                "tensor_parallel": {"tp_size": 2}, "ragged": rc}
    ).generate(prompts, max_new_tokens=6, steps_per_sync=3)
    assert got == ref


def test_v2_per_sequence_sampling(tiny):
    """Per-request sampling params (reference v2 engine): a greedy sequence
    and a temperature/top-k sequence decode in the SAME batch — the greedy
    one matches its solo run token-for-token, and the stochastic one only
    ever emits tokens inside its own top-k set."""
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    base = {"dtype": "float32", "prefill_bucket": 16,
            "ragged": {"max_tracked_sequences": 4,
                       "max_ragged_batch_size": 4,
                       "memory_config_blocks": 64, "block_size": 16}}
    rng = np.random.default_rng(3)
    p_greedy = rng.integers(0, cfg.vocab_size, (6,), dtype=np.int32)
    p_hot = rng.integers(0, cfg.vocab_size, (9,), dtype=np.int32)
    sp_g = SamplingParams(greedy=True)
    sp_h = SamplingParams(temperature=0.8, top_k=5)

    solo = build_engine_v2(llama, cfg, params, config=dict(base))
    solo.put(0, p_greedy.tolist(), sp_g)
    for i in range(6):
        solo.step(sp_g, seed=100 + i)
    ref_greedy = solo.finish(0)

    eng = build_engine_v2(llama, cfg, params, config=dict(base))
    eng.put(0, p_greedy.tolist(), sp_g)
    eng.put(1, p_hot.tolist(), sp_h)
    for i in range(6):
        eng.step(seed=100 + i)
    got_greedy = eng.finish(0)
    got_hot = eng.finish(1)
    assert got_greedy == ref_greedy  # greedy row unaffected by the neighbor

    # every stochastic token must come from ITS OWN top-5 at that position.
    # The replay recomputes logits on the DENSE path; the engine sampled on
    # the paged path, so rank boundaries can flip within numeric noise —
    # check membership by logit margin, not exact rank (a filterless
    # sampler over vocab=256 would still fail this overwhelmingly).
    seq = list(p_hot)
    for tok in got_hot:
        logits = np.asarray(llama.apply(
            cfg, params, jnp.asarray([seq], jnp.int32),
            compute_dtype=jnp.float32))[0, -1]
        kth = np.sort(logits)[-5]
        assert logits[tok] >= kth - 0.05, (tok, logits[tok], kth)
        seq.append(tok)

    # fused quantum path: same mixed batch through step_many
    eng2 = build_engine_v2(llama, cfg, params, config=dict(base))
    eng2.put(0, p_greedy.tolist(), sp_g)
    eng2.put(1, p_hot.tolist(), sp_h)
    out = eng2.step_many(6, seed=100)
    assert out[0] == ref_greedy[1:7]


def test_v2_generate_per_prompt_sampling(tiny):
    """generate(sampling_params=[...]) mixes greedy and stochastic requests
    in one continuous batch; the greedy prompt's output matches an all-
    greedy generate exactly."""
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    base = {"dtype": "float32", "prefill_bucket": 16,
            "ragged": {"max_tracked_sequences": 4,
                       "max_ragged_batch_size": 4,
                       "memory_config_blocks": 64, "block_size": 16}}
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)
               for n in (7, 12)]
    ref = build_engine_v2(llama, cfg, params, config=dict(base)) \
        .generate(prompts, max_new_tokens=5)
    got = build_engine_v2(llama, cfg, params, config=dict(base)) \
        .generate(prompts, max_new_tokens=5, sampling_params=[
            SamplingParams(greedy=True),
            SamplingParams(temperature=0.9, top_k=4)])
    assert got[0] == ref[0]          # greedy row unaffected by the neighbor
    assert len(got[1]) == 5
    with pytest.raises(ValueError):
        build_engine_v2(llama, cfg, params, config=dict(base)).generate(
            prompts, sampling_params=[SamplingParams()])


def test_v2_split_prefill_drains_when_no_decodes_live(tiny):
    """ADVICE r4: with NO live decodes there is nothing for the
    one-chunk-per-step bound to protect — a split-admitted prompt must
    complete its whole prefill in one step() call (its KV blocks were
    reserved at admission and sat idle otherwise), and stop draining as
    soon as a sequence becomes decodable."""
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        llama, cfg, params,
        config={"dtype": "float32", "prefill_bucket": 16,
                "split_prefill_chunk": 32,
                "ragged": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 4,
                           "memory_config_blocks": 64, "block_size": 16}})
    rng = np.random.default_rng(1)
    long_prompt = rng.integers(0, cfg.vocab_size, (100,), dtype=np.int32)
    sp = SamplingParams(greedy=True)
    eng.put_split(7, long_prompt.tolist(), sp)
    out = eng.step()
    # 100 tokens / 32-chunk = 4 chunks, all in ONE step: first token arrives
    assert 7 in out and not eng._pending_prefill
    # parity with the one-shot path
    ref = build_engine_v2(
        llama, cfg, params,
        config={"dtype": "float32", "prefill_bucket": 16,
                "ragged": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 4,
                           "memory_config_blocks": 64, "block_size": 16}})
    assert out[7] == ref.put(7, long_prompt.tolist(), sp)


def test_v2_step_warns_on_ignored_sampling_params(tiny):
    """ADVICE r4: a non-default sp passed to step() (the pre-r4 contract)
    is ignored in favor of admission-time params — loudly, not silently."""
    import warnings

    cfg, params = tiny
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        llama, cfg, params,
        config={"dtype": "float32", "prefill_bucket": 16,
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 32, "block_size": 16}})
    eng.put(1, [3, 5, 7], SamplingParams(greedy=True))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        eng.step(SamplingParams(temperature=0.7, top_p=0.9))
    assert any(issubclass(x.category, DeprecationWarning) for x in w)
    with warnings.catch_warnings(record=True) as w:  # default sp: silent
        warnings.simplefilter("always")
        eng2 = build_engine_v2(
            llama, cfg, params,
            config={"dtype": "float32", "prefill_bucket": 16,
                    "ragged": {"max_tracked_sequences": 2,
                               "max_ragged_batch_size": 2,
                               "memory_config_blocks": 32,
                               "block_size": 16}})
        eng2.put(1, [3, 5, 7], SamplingParams(greedy=True))
        eng2.step()
    assert not any(issubclass(x.category, DeprecationWarning) for x in w)


def test_v2_final_chunks_share_one_program_across_sampling_params(tiny):
    """ADVICE r4, re-stated by ISSUE 46: a chunk is ``decode_chunk`` keyed
    on its width and on greedy-or-rows, never on a client's
    ``SamplingParams`` - two prompts whose final chunks sample with
    different configs compile ONE program (``chunk_prefill`` compiled one a
    config)."""
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        llama, cfg, params,
        config={"dtype": "float32", "prefill_bucket": 16,
                "split_prefill_chunk": 32,
                "ragged": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 4,
                           "memory_config_blocks": 64, "block_size": 16}})
    rng = np.random.default_rng(46)
    for uid, sp in ((1, SamplingParams(temperature=0.7)),
                    (2, SamplingParams(temperature=1.3, top_k=5))):
        eng.put_split(uid, rng.integers(0, cfg.vocab_size, (40,)).tolist(),
                      sp)
        assert set(eng.step(seed=uid)) == {uid}     # both chunks, one step
        eng.finish(uid)
    chunk = [k for k in eng._paged_fns if k[0].startswith("decode_chunk")]
    # the mid chunks sample greedily for nothing; the final ones by rows
    assert sorted(chunk) == [("decode_chunk", 32), ("decode_chunk_dyn", 32)]
    assert eng.compile_monitor.enabled is False     # plain jit objects:
    assert all(eng._paged_fns[k]._cache_size() == 1 for k in chunk)


def test_sample_batch_top_p_disabled_is_noop():
    """ADVICE r4: top_p=1.0 rows must match the static sample() path
    exactly (which skips the filter) — a rounding-up cumsum must not drop
    a valid tail column."""
    from deepspeed_tpu.inference.sampling import sample, sample_batch

    rng = jax.random.PRNGKey(0)
    V = 64
    logits = jnp.asarray(
        np.log(np.full((3, V), 1.0 / V, np.float32)))  # uniform: cumsum hits 1.0
    temp = jnp.asarray([1.0, 1.0, 0.7], jnp.float32)
    topk = jnp.zeros((3,), jnp.int32)
    topp = jnp.asarray([1.0, 1.0, 1.0], jnp.float32)
    greedy = jnp.zeros((3,), bool)
    # run many draws: with the filter a true no-op, every column stays
    # reachable; a dropped tail column shows up as that id never sampled
    keys = jax.random.split(rng, 512)
    toks = jax.vmap(
        lambda k: sample_batch(k, logits, temp, topk, topp, greedy))(keys)
    seen = np.unique(np.asarray(toks))
    assert len(seen) == V, f"only {len(seen)}/{V} ids reachable"
    del sample  # draw-level parity is ill-posed: categorical's uniforms
    # depend on batch shape, so only the keep-everything contract is pinned
