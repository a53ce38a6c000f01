"""Quantized KV-cache serving tests (docs/serving.md "Quantized KV cache"):
int8 KV block pools with per-block-per-group scales beside the block table,
fill-time quantization fused into the cache-update, dequant fused into the
paged-decode kernels (Pallas in-register + XLA score-folded fallback),
default-OFF byte-parity, block-lifecycle preservation (COW / fork /
spec-decode truncate / prefix hits / host spill) on quantized blocks, the
equal-bytes density win, and the Serving/kv_quant/* telemetry surface."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference import SamplingParams, build_engine_v2
from deepspeed_tpu.models import llama

SP = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(max_seq_len=256)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(scope="module")
def hd64():
    """The bench-shaped CPU model (head_size 64): the fp32 scale sidecar is
    4/hd of the code bytes, so hd >= 64 is where the density ratio and the
    greedy-identity acceptance are actually representative."""
    cfg = llama.LlamaConfig(vocab_size=512, hidden_size=128,
                            intermediate_size=256, num_layers=2,
                            num_heads=2, num_kv_heads=2, max_seq_len=512)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def build(model, quant=True, group_size=128, blocks=64, block_size=16,
          slots=8, **kw):
    cfg, params = model
    mesh_lib.set_mesh(None)
    return build_engine_v2(
        llama, cfg, params,
        config=dict({"dtype": "float32", "prefill_bucket": 16,
                     "kv_quant": {"enabled": quant,
                                  "group_size": group_size},
                     "ragged": {"max_tracked_sequences": slots,
                                "max_ragged_batch_size": slots,
                                "memory_config_blocks": blocks,
                                "block_size": block_size}}, **kw))


def prompts_for(cfg, n=4, lo=9, hi=33, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size,
                         int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


# --------------------------------------------------------------------------- #
# shared quantizer + pool constructor units
# --------------------------------------------------------------------------- #
def test_group_quantizer_is_the_comm_quantizer():
    """Satellite dedupe pin: comm/compressed's _group_quantize IS
    ops.quantization.group_quantize_int8 (one implementation for the
    ZeRO++ collectives AND the KV fill path)."""
    from deepspeed_tpu.comm import compressed as cc
    from deepspeed_tpu.ops.quantization import group_quantize_int8

    assert cc._group_quantize is group_quantize_int8


def test_kv_quantize_roundtrip_error_bound():
    """Dequant error of the KV quantizer is bounded by scale/2 per element
    (symmetric rounding), with per-token-per-group scales."""
    from deepspeed_tpu.ops.quantization import (kv_dequantize_int8,
                                                kv_quantize_int8)

    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.standard_normal((3, 5, 2, 64)), jnp.float32)
    for gs in (64, 32, 16):
        q, s = kv_quantize_int8(x, gs)
        assert q.shape == x.shape and q.dtype == jnp.int8
        assert s.shape == x.shape[:-1] + (64 // gs,)
        err = jnp.abs(kv_dequantize_int8(q, s) - x)
        bound = jnp.repeat(s, gs, axis=-1) * 0.5 + 1e-7
        assert bool(jnp.all(err <= bound))


def test_init_paged_pools_quant_layout(tiny):
    cfg, _ = tiny
    c = llama.init_paged_cache(cfg, 8, 16, kv_quant_group=128)
    hd = cfg.head_size
    assert c["k"].dtype == jnp.int8 and c["v"].dtype == jnp.int8
    # group_size clamps to head_size → one scale per (block, head, token)
    assert c["k_scale"].shape == c["k"].shape[:-1] + (1,)
    assert c["k_scale"].dtype == jnp.float32
    # scales init to zero: unwritten positions dequantize to the bf16
    # pool's exact zeros
    assert float(jnp.max(jnp.abs(c["k_scale"]))) == 0.0
    with pytest.raises(ValueError, match="group_size"):
        llama.init_paged_cache(cfg, 8, 16, kv_quant_group=torn_group(hd))


def torn_group(hd):
    """A group size that cannot divide head_size (hd is a power of two)."""
    return 3


# --------------------------------------------------------------------------- #
# kernel ↔ reference fallback agreement
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("ng", [1, 2, 4])
@pytest.mark.parametrize("window", [None, 20])
def test_quant_kernel_matches_xla_fallback(ng, window):
    """The Pallas fused-dequant decode kernel (interpret mode on CPU) and
    the XLA reference fallback (score-folded at ng=1, gathered dequant
    otherwise) agree to fp32 roundoff on random int8 pools."""
    from deepspeed_tpu.ops.pallas.paged_attention import (
        paged_decode_attention, paged_decode_attention_xla)

    rng = np.random.default_rng(1)
    nb, nkv, bs, hd, B, nh, mb = 12, 2, 16, 64, 3, 4, 5
    q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
    kp = jnp.asarray(rng.integers(-127, 128, (nb, nkv, bs, hd)), jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (nb, nkv, bs, hd)), jnp.int8)
    ks = jnp.asarray(rng.random((nb, nkv, bs, ng)) * 0.02, jnp.float32)
    vs = jnp.asarray(rng.random((nb, nkv, bs, ng)) * 0.02, jnp.float32)
    bt = jnp.asarray(rng.integers(1, nb, (B, mb)), jnp.int32)
    cl = jnp.asarray([13, 37, 70], jnp.int32)
    kw = dict(k_scale=ks, v_scale=vs)
    if window is not None:
        kw["window"] = window
    got = paged_decode_attention(q, kp, vp, bt, cl, **kw)
    want = paged_decode_attention_xla(q, kp, vp, bt, cl, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("op", ["decode", "prefill"])
def test_quant_kernels_read_the_layer_they_are_given(op):
    """int8 ``[L, ...]`` code pools with the LAYER's scale pools beside them
    (what ``paged_attention_step`` hands the ops): kernel and reference
    agree at every layer, and the layers differ."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    rng = np.random.default_rng(2)
    L, nb, nkv, bs, hd, B, nh, mb, t = 3, 12, 2, 16, 64, 2, 4, 5, 4
    kp = jnp.asarray(rng.integers(-127, 128, (L, nb, nkv, bs, hd)), jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (L, nb, nkv, bs, hd)), jnp.int8)
    ks = jnp.asarray(rng.random((L, nb, nkv, bs, 2)) * 0.02, jnp.float32)
    vs = jnp.asarray(rng.random((L, nb, nkv, bs, 2)) * 0.02, jnp.float32)
    bt = jnp.asarray(rng.integers(1, nb, (B, mb)), jnp.int32)
    cl = jnp.asarray([13, 37], jnp.int32)
    if op == "decode":
        q = jnp.asarray(rng.standard_normal((B, nh, hd)), jnp.float32)
        kernel, ref, args = (pa.paged_decode_attention,
                             pa.paged_decode_attention_xla, (bt, cl))
    else:
        q = jnp.asarray(rng.standard_normal((B, t, nh, hd)), jnp.float32)
        kernel, ref, args = (pa.paged_prefill_attention,
                             pa.paged_prefill_attention_xla, (bt, cl))
    outs = []
    for layer in range(L):
        kw = dict(k_scale=ks[layer], v_scale=vs[layer], layer=layer)
        got = np.asarray(kernel(q, kp, vp, *args, **kw))
        np.testing.assert_allclose(
            got, np.asarray(ref(q, kp, vp, *args, **kw)),
            rtol=2e-5, atol=2e-5)
        outs.append(got)
    assert np.abs(outs[0] - outs[1]).max() > 1e-3
    assert np.abs(outs[1] - outs[2]).max() > 1e-3


@pytest.mark.parametrize("ng", [1, 2])
def test_quant_kv_write_writes_codes_and_scales_in_one_call(ng):
    """Fill-time quantisation stays in the write: ``paged_kv_write`` on int8
    ``[L, ...]`` code pools and the layer's scale pools equals the scatter
    reference bit for bit, for a chunk that starts and ends mid-page beside
    a dummy sequence; the written rows dequantize to the step's K/V within
    the quantiser's bound and no other slot moves."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.quantization import kv_dequantize_int8

    rng = np.random.default_rng(4)
    L, nb, nkv, bs, hd, t = 3, 12, 2, 16, 64, 21
    kp = jnp.asarray(rng.integers(-127, 128, (L, nb, nkv, bs, hd)), jnp.int8)
    vp = jnp.asarray(rng.integers(-127, 128, (L, nb, nkv, bs, hd)), jnp.int8)
    ks = jnp.asarray(rng.random((nb, nkv, bs, ng)) * 0.02, jnp.float32)
    vs = jnp.asarray(rng.random((nb, nkv, bs, ng)) * 0.02, jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, t, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, t, nkv, hd)), jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, nb))[:8].reshape(2, 4),
                     jnp.int32)
    ctx, lens = jnp.asarray([11, 0], jnp.int32), jnp.asarray([t, 0], jnp.int32)
    kw = dict(layer=2, k_scale=ks, v_scale=vs)
    got = pa.paged_kv_write(k, v, kp, vp, bt, ctx, lens, **kw)
    want = pa.paged_kv_write_xla(k, v, kp, vp, bt, ctx, lens, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    pos = 11 + np.arange(t)
    blocks = np.asarray(bt)[0, pos // bs]
    back = kv_dequantize_int8(got[0][2, blocks, :, pos % bs],
                              got[2][blocks, :, pos % bs], jnp.float32)
    assert np.abs(np.asarray(back) - np.asarray(k[0])).max() < 0.05
    changed = np.any(np.asarray(got[0]) != np.asarray(kp), axis=(2, 4))
    assert changed.sum() <= t and not changed[:2].any()
    assert not np.any(np.asarray(got[2])[0] != np.asarray(ks)[0])


def test_quant_scales_required_together():
    from deepspeed_tpu.ops.pallas.paged_attention import \
        paged_decode_attention

    q = jnp.zeros((1, 2, 16), jnp.float32)
    kp = jnp.zeros((4, 2, 8, 16), jnp.int8)
    ks = jnp.zeros((4, 2, 8, 1), jnp.float32)
    bt = jnp.ones((1, 2), jnp.int32)
    cl = jnp.ones((1,), jnp.int32)
    with pytest.raises(AssertionError, match="together"):
        paged_decode_attention(q, kp, kp, bt, cl, k_scale=ks)


# --------------------------------------------------------------------------- #
# default-OFF parity + config validation
# --------------------------------------------------------------------------- #
def test_default_off_parity(tiny):
    """kv_quant.enabled=False is byte-identical to an engine built before
    the feature existed: same cache pytree (leaf names AND dtypes), same
    compiled program keys, same token streams."""
    from deepspeed_tpu.inference import InferenceConfig

    cfg, params = tiny
    prompts = prompts_for(cfg)
    legacy_cfg = InferenceConfig.from_dict(
        {"dtype": "float32", "prefill_bucket": 16,
         "ragged": {"max_tracked_sequences": 8, "max_ragged_batch_size": 8,
                    "memory_config_blocks": 64, "block_size": 16}})
    del legacy_cfg.__dict__["kv_quant"]     # the pre-PR config surface
    mesh_lib.set_mesh(None)
    legacy = build_engine_v2(llama, cfg, params, config=legacy_cfg)
    out_legacy = legacy.generate(prompts, max_new_tokens=8)
    off = build(tiny, quant=False)
    assert set(off.cache.keys()) == {"k", "v"}
    assert off.cache["k"].dtype == legacy.cache["k"].dtype
    out_off = off.generate(prompts, max_new_tokens=8)
    assert out_off == out_legacy
    assert sorted(k[0] for k in off._paged_fns) == \
        sorted(k[0] for k in legacy._paged_fns)
    off.debug_check_cache()


def test_kv_quant_config_validation(tiny):
    with pytest.raises(ValueError, match="dtype"):
        build(tiny, quant=True, kv_quant={"enabled": True, "dtype": "fp8"})
    with pytest.raises(ValueError, match="group_size"):
        build(tiny, quant=True, group_size=3)
    # a custom init_paged_cache without the kv_quant_group seam fails
    # loudly at build, not silently at first decode
    from deepspeed_tpu.inference import InferenceConfig
    from deepspeed_tpu.inference.engine import ModelFamily
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    cfg, params = tiny
    mesh_lib.set_mesh(None)
    icfg = InferenceConfig.from_dict(
        {"dtype": "float32", "kv_quant": {"enabled": True},
         "ragged": {"max_tracked_sequences": 2, "max_ragged_batch_size": 2,
                    "memory_config_blocks": 16, "block_size": 16}})
    with pytest.raises(ValueError, match="kv_quant"):
        InferenceEngineV2(
            ModelFamily.from_module(llama, cfg), params, icfg,
            init_paged_cache=lambda cfg_, nb, bs: {
                "k": jnp.zeros((1,)), "v": jnp.zeros((1,))},
            apply_paged=llama.apply_paged)


# --------------------------------------------------------------------------- #
# accuracy: greedy identity on the bench-shaped model + logit error
# --------------------------------------------------------------------------- #
def test_greedy_token_identical_hd64(hd64):
    """The acceptance pin: greedy decode with quant ON is token-identical
    to bf16 on the bench-shaped workload at group_size <= 128."""
    cfg, _ = hd64
    rng = np.random.default_rng(11)   # pinned workload (seeded prompts)
    prompts = [rng.integers(0, cfg.vocab_size, 32).tolist()
               for _ in range(4)]
    out_bf = build(hd64, quant=False, blocks=48).generate(
        prompts, max_new_tokens=8, seed=0)
    out_q = build(hd64, quant=True, blocks=48).generate(
        prompts, max_new_tokens=8, seed=0)
    assert out_q == out_bf


def test_per_token_logit_error_bounded(hd64):
    """Statistical pin on the quantization error: per-token logit MAE of
    the quantized forward stays well under the logit scale (the serving
    bench reports the same number for the trajectory)."""
    cfg, params = hd64
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 32)), jnp.int32)
    tables = jnp.arange(1, 6, dtype=jnp.int32)[None]
    ctx = jnp.zeros((1,), jnp.int32)
    c_bf = llama.init_paged_cache(cfg, 8, 16, dtype=jnp.float32)
    c_q = llama.init_paged_cache(cfg, 8, 16, kv_quant_group=128)
    lo_bf, _ = llama.apply_paged(cfg, params, toks, c_bf, tables, ctx)
    lo_q, _ = llama.apply_paged(cfg, params, toks, c_q, tables, ctx)
    mae = float(jnp.mean(jnp.abs(lo_q - lo_bf)))
    scale = float(jnp.mean(jnp.abs(lo_bf)))
    assert mae < 0.05 * max(scale, 1.0), (mae, scale)
    agree = float(jnp.mean(jnp.argmax(lo_q, -1) == jnp.argmax(lo_bf, -1)))
    assert agree >= 0.9, agree


# --------------------------------------------------------------------------- #
# block lifecycle on quantized blocks: COW / fork / truncate / prefix /
# host spill — scales must ride every copy
# --------------------------------------------------------------------------- #
def test_fork_cow_on_quant_blocks(tiny):
    """fork() shares quantized blocks zero-copy; the first divergent append
    COWs codes AND scales, leaving the parent's stream exactly what an
    unforked run produces."""
    cfg, _ = tiny
    prompt = prompts_for(cfg, n=1, lo=20, hi=21)[0]
    solo = build(tiny, quant=True)
    solo.put(0, prompt, SP)
    want = [solo.step(SP)[0] for _ in range(6)]
    eng = build(tiny, quant=True)
    eng.put(0, prompt, SP)
    eng.fork(0, 1, sp=SamplingParams(temperature=0.9, top_k=7))
    got = []
    for i in range(6):
        out = eng.step(SP, seed=i * 31 + 7)
        got.append(out[0])
    assert eng.state.prefix_stats["cow_copies"] >= 1
    assert got == want
    eng.debug_check_cache()
    eng.state.debug_check()


def test_spec_decode_on_quant_blocks(tiny):
    """Speculative decoding composes with the quantized cache: greedy spec
    mode (draft → batched verify → truncate rollback on quantized blocks)
    is bit-identical to plain greedy quant decode."""
    cfg, _ = tiny
    rng = np.random.default_rng(3)
    pat = rng.integers(0, cfg.vocab_size, 5).tolist()
    prompts = [(pat * 8)[:36] for _ in range(3)]
    plain = build(tiny, quant=True).generate(prompts, max_new_tokens=12,
                                             seed=0)
    eng = build(tiny, quant=True,
                speculative={"enabled": True, "max_draft_tokens": 4})
    spec = eng.generate(prompts, max_new_tokens=12, seed=0)
    assert spec == plain
    assert eng.spec_stats["verify_steps"] >= 1  # speculation actually ran
    eng.debug_check_cache()
    eng.state.debug_check()


def test_prefix_cache_hits_on_quant_blocks(tiny):
    """Prefix-cache chain-hash matching resolves QUANTIZED shared blocks:
    the second admission of a shared prefix starts prefill at the first
    uncached token and streams exactly like an uncached run."""
    cfg, _ = tiny
    rng = np.random.default_rng(11)
    shared = rng.integers(0, cfg.vocab_size, 32).tolist()
    tails = [rng.integers(0, cfg.vocab_size, 6).tolist() for _ in range(2)]
    prompts = [shared + t for t in tails]
    # sequential admissions so the first prompt's blocks are indexed (and
    # retained after finish) before the second looks them up
    plain_eng = build(tiny, quant=True)
    plain = [plain_eng.generate([p], max_new_tokens=6, seed=0)[0]
             for p in prompts]
    eng = build(tiny, quant=True, prefix_cache={"enabled": True})
    cached = [eng.generate([p], max_new_tokens=6, seed=0)[0]
              for p in prompts]
    assert cached == plain
    assert eng.state.prefix_stats["hit_tokens"] >= 32
    eng.debug_check_cache()
    eng.state.debug_check()


def test_host_spill_on_quant_blocks(tiny):
    """Host-spill composes with quantization: evicted quantized blocks
    spill codes AND scales, restores are bit-exact (streams identical to
    spill-off), and the spilled bytes are under half the bf16 spill's
    (int8 codes + the fp32 scale sidecar vs fp32 test pools)."""
    cfg, _ = tiny
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, cfg.vocab_size, 48)) for _ in range(4)]

    def run(quant, spill):
        eng = build(tiny, quant=quant, blocks=40, slots=4,
                    prefix_cache={"enabled": True, "max_retained_blocks": 2,
                                  "host_spill": spill})
        # per-block host-spill footprint, straight from the spill reader
        # (codes halve vs the fp32 test pools; the scale sidecar rides too)
        per_block = sum(np.asarray(x).size * np.asarray(x).dtype.itemsize
                        for x in eng._spill_read_block(1))
        for r in range(2):          # second round re-admits spilled prefixes
            for i, p in enumerate(prompts):
                eng.put(100 * r + i, p, SP)
                for _ in range(3):
                    eng.step(SP)
                eng.finish(100 * r + i)
        stats = dict(eng.state.prefix_stats)
        if quant:
            eng.debug_check_cache()
        eng.state.debug_check()
        # deterministic greedy tail as the parity probe
        tail = eng.generate([prompts[0]], max_new_tokens=6, seed=0)
        del eng
        return tail, stats, per_block

    tail_off, _, _ = run(quant=True, spill=False)
    tail_on, stats_on, per_block_q = run(quant=True, spill=True)
    assert tail_on == tail_off
    assert stats_on["spills"] >= 1 and stats_on["restores"] >= 1
    _, stats_bf, per_block_bf = run(quant=False, spill=True)
    assert stats_bf["spills"] >= 1
    # fp32 test pools spill 4-byte elements; the quant pool spills 1-byte
    # codes + one fp32 scale per head-dim group. At tiny's hd=16 the scale
    # sidecar is 1/16 of the elements → 2560 vs 4096 B/block (0.625x); on
    # serving heads (hd >= 64) the same accounting gives < 0.5x vs bf16
    assert per_block_q <= 0.65 * per_block_bf, (per_block_q, per_block_bf)


def test_soak_quant_block_lifecycle(tiny):
    """Randomized admit/decode/fork/truncate/finish soak over the quantized
    pool: allocator + scale-table invariants hold at every checkpoint."""
    cfg, _ = tiny
    eng = build(tiny, quant=True, blocks=48, slots=6,
                prefix_cache={"enabled": True, "max_retained_blocks": 4})
    rng = np.random.default_rng(42)
    live, next_uid = [], 0
    for it in range(120):
        op = rng.random()
        if op < 0.35 and len(live) < 5:
            plen = int(rng.integers(5, 40))
            if eng.state.can_admit(plen):
                eng.put(next_uid,
                        rng.integers(0, cfg.vocab_size, plen).tolist(), SP)
                live.append(next_uid)
                next_uid += 1
        elif op < 0.55 and live and len(live) < 5 and eng.state.free_slots:
            parent = int(rng.choice(live))
            eng.fork(parent, next_uid)
            live.append(next_uid)
            next_uid += 1
        elif op < 0.7 and live:
            uid = int(rng.choice(live))
            d = eng.state.seqs[uid]
            if d.seen_tokens > 2:
                pairs = eng.state.truncate(d, int(rng.integers(
                    1, d.seen_tokens)))
                eng._copy_blocks(pairs)
                eng._slot_tables[d.slot] = eng.state.block_table(d)
                eng._slot_lens[d.slot] = d.seen_tokens
        elif op < 0.85 and live:
            uid = live.pop(int(rng.integers(len(live))))
            eng.finish(uid)
        elif live:
            eng.step(SP)
        if it % 20 == 19:
            eng.state.debug_check()
            eng.debug_check_cache()
    eng.state.debug_check()
    eng.debug_check_cache()


# --------------------------------------------------------------------------- #
# density + telemetry surface
# --------------------------------------------------------------------------- #
def test_density_at_equal_pool_bytes(hd64):
    """The headline: at MATCHED pool bytes, the int8 pool holds >= 1.8x the
    blocks (hd=64: scale sidecar is 1/16 of code bytes → 1.88x; hd=128 →
    1.94x), so ~2x sequences fit per chip."""
    cfg, _ = hd64

    def pool_bytes(cache):
        return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(cache))

    nb = 32
    per_bf16 = pool_bytes(llama.init_paged_cache(cfg, nb, 16,
                                                 dtype=jnp.bfloat16)) // nb
    per_q = pool_bytes(llama.init_paged_cache(
        cfg, nb, 16, kv_quant_group=128)) // nb
    assert per_bf16 / per_q >= 1.8, (per_bf16, per_q)


def test_kv_quant_events_and_schema(tiny):
    from deepspeed_tpu.telemetry.schema import validate_events

    eng = build(tiny, quant=True)
    assert eng.kv_quant_events() != []          # enabled → events exist
    eng.put(0, prompts_for(cfg := tiny[0], n=1)[0], SP)
    eng.step(SP)
    events = eng.kv_quant_events(3)
    assert validate_events(events) == []
    d = {n.split("/")[-1]: v for n, v, _ in events}
    assert d["dequant_fused"] == 1.0
    assert d["blocks_quantized"] >= 1
    assert d["bytes_saved"] > 0
    assert 0.0 < d["max_abs_err"] < 1.0
    # disabled engines emit NOTHING (zero-event parity)
    assert build(tiny, quant=False).kv_quant_events() == []


def test_kv_quant_hub_and_report(tiny, tmp_path, capsys):
    """publish_kv_quant_telemetry lands the gauges on the hub, and
    telemetry_report --serving renders the KV quantization section."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "_dstpu_telemetry_report",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", "telemetry_report.py"))
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)

    class Hub:
        def __init__(self):
            self.events = []

        def serving_event(self, name, value, step=0):
            self.events.append((name, value, step))

    cfg, params = tiny
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        llama, cfg, params, telemetry_hub=(hub := Hub()),
        config={"dtype": "float32", "prefill_bucket": 16,
                "kv_quant": {"enabled": True},
                "ragged": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 4,
                           "memory_config_blocks": 32, "block_size": 16}})
    eng.generate(prompts_for(cfg, n=2), max_new_tokens=4)
    names = {n for n, _, _ in hub.events}
    assert "Serving/kv_quant/blocks_quantized" in names
    assert "Serving/kv_quant/dequant_fused" in names
    txt = report.serving([
        {"name": n, "value": v, "step": s} for n, v, s in hub.events])
    assert "KV quantization report" in txt
    assert "dequant fused in-kernel: yes" in txt
