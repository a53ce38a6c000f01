"""A.X-K1 through ``models/axk1.py`` (ISSUE 47) against the benchmark's plain
reference (``benchmark/reference/axk1.py``) at toy widths on the CPU,
float32 and seeded: latent attention expanded and absorbed, a leading dense
layer ahead of the scanned sparse stack, along every path (the full forward,
the dense latent cache, chunked prefill then decode through the paged latent
pool, a mixed call, and ``ServingScheduler.tick`` with a split prompt beside
live rows), each deliberately wrong variant, one chip's share of the expert
bank tied to the whole layer, the group-limited scaled routing by hand,
YaRN's frequencies against the closed form, the walk's Pallas form against
its XLA twin at a key width that is not the value width, what a latent cache
refuses and what it serves (prefix reuse, ``fork``), and the configuration's
file.
"""

import dataclasses
import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import axk1 as family
from benchmark.harness import manifest
from benchmark.reference import axk1 as reference
from benchmark.reference import axk1_variants as variants
from deepspeed_tpu.inference.engine_v2 import LatentKVError, build_engine_v2
from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                             ServingScheduler)
from deepspeed_tpu.models import axk1
from deepspeed_tpu.models._paged import MixedCall
from deepspeed_tpu.moe.layer import MoELayer
from deepspeed_tpu.moe.sharded_moe import top_k_gating, top_k_gating_compact
from deepspeed_tpu.ops import registry
from deepspeed_tpu.ops.pallas import paged_attention as pa
from deepspeed_tpu.ops.rotary import (yarn_frequencies, yarn_inv_frequencies,
                                      yarn_mscale)

CONFIG = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "configs", family.CONFIG_FILE))
TINY = {**CONFIG["published"], **CONFIG["rehearsal"]["published"],
        "num_hidden_layers": 3, "num_experts": 8,
        "max_position_embeddings": 128}
HELD = {**TINY, "num_experts": 2, "experts_first": 4}   # one share of four
PROMPT, CHUNK, STEPS, BLOCK = 29, 8, 5, 4
PATHS = ("apply", "apply_cached", "apply_paged")
TOL = 1e-4      # float32 on both sides in another order: 1e-5 of unit logits
ROLE = {"program_options": {"drop_tokens": False}, "weights_dtype": "float32",
        "engine": {"split_prefill_chunk": CHUNK,
                   "ragged": {"block_size": BLOCK}},
        "held": {"why": "float32 on both sides",
                 "logits_mean_abs_diff": TOL}}


def build(hf=TINY):
    """The configuration, seeded random weights (the norms' too, which
    ``init`` leaves flat) and a row of tokens, prompt and answer. ``HELD``:
    the same model with this share's two experts cut out of the bank."""
    cfg = family.build_cfg(TINY, drop_tokens=False)
    params = axk1.init(cfg, jax.random.PRNGKey(0))
    for s, stack in enumerate(("dense_layers", "layers")):
        for n, name in enumerate(("attn_norm", "q_norm", "kv_norm",
                                  "ffn_norm")):
            w = params[stack][name]
            params[stack][name] = w * (1.0 + 0.2 * jax.random.normal(
                jax.random.PRNGKey(10 + 4 * s + n), w.shape))
    if hf is not TINY:
        first, count = reference.held_experts(hf)
        cfg = family.build_cfg(hf, drop_tokens=False)
        params["layers"]["moe"] = {
            k: v[:, first:first + count] if k.startswith("w_") else v
            for k, v in params["layers"]["moe"].items()}
    row = np.random.default_rng(0).integers(0, 256, PROMPT + STEPS)
    return cfg, params, row


def pieces(row):
    cuts = list(range(0, PROMPT, CHUNK)) + list(range(PROMPT, len(row)))
    return [(a, row[a:b]) for a, b in zip(cuts, cuts[1:] + [len(row)])]


def program_logits(path, cfg, params, row):
    """Logits ``[len(row), vocab]`` of the program along ``path``."""
    f32 = jnp.float32
    if path == "apply":
        return axk1.apply(cfg, params, jnp.asarray(row[None]),
                          compute_dtype=f32)[0][0]
    out = []
    # a jit of this call's own (the ops registry is read at trace time): one
    # compile a shape - the chunk's, the token's - not one a piece
    if path == "apply_cached":
        cached = jax.jit(functools.partial(axk1.apply_cached, cfg,
                                           compute_dtype=f32))
        cache = axk1.init_cache(cfg, 1, 64, dtype=f32)
        for start, piece in pieces(row):
            logits, cache = cached(
                params, jnp.asarray(piece[None]), cache,
                jnp.asarray([start], jnp.int32))
            out.append(logits[0])
        return jnp.concatenate(out)
    paged = jax.jit(functools.partial(axk1.apply_paged, cfg,
                                      compute_dtype=f32))
    cache = axk1.init_paged_cache(cfg, 40, BLOCK, dtype=f32)
    table = jnp.asarray(1 + np.arange(32, dtype=np.int32))[None]
    for start, piece in pieces(row):
        width = CHUNK if start < PROMPT else 1
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(piece)] = piece
        logits, cache = paged(
            params, jnp.asarray(padded), cache, table,
            jnp.asarray([start], jnp.int32),
            valid=jnp.arange(width)[None] < len(piece))
        out.append(logits[0, :len(piece)])
    return jnp.concatenate(out)


def gap(a, b):
    return float(jnp.abs(jnp.asarray(a) - jnp.asarray(b)).max())


@pytest.fixture(scope="module", params=["whole", "held"])
def f32(request):
    hf = TINY if request.param == "whole" else HELD
    cfg, params, row = build(hf)
    return hf, cfg, params, row, reference.logits(hf, family.Weights(params),
                                                  row)


@pytest.mark.parametrize("path", PATHS)
def test_program_agrees_with_the_plain_reference_in_float32(f32, path,
                                                            one_device):
    """The expanded form (``apply``), the absorbed form over the dense
    latent cache and over the paged latent pool - prefill in chunks, then
    decode - all give the reference's logits."""
    hf, cfg, params, row, want = f32
    with jax.default_matmul_precision("highest"):
        got = program_logits(path, cfg, params, row)
    assert got.shape == want.shape
    assert gap(got, want) < TOL


@pytest.mark.parametrize("variant", variants.NAMES)
def test_each_wrong_variant_fails_the_tolerance(f32, variant):
    hf, cfg, params, row, want = f32
    wrong = variants.logits(variant, hf, family.Weights(params), row)
    assert gap(wrong, want) > 50 * TOL


def test_absorbed_attention_is_the_expanded_form_in_another_order():
    """One layer's attention over dense rows: ``q_n W_uk^T`` against the
    latents and ``W_uv`` after the weighted sum are the keys and values
    rebuilt a head."""
    cfg, params, _ = build()
    w = jax.tree.map(lambda a: a[0], params["layers"])
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, cfg.hidden_size))
    cos, sin = axk1._rope(cfg)
    with jax.default_matmul_precision("highest"):
        q_n, q_r, row = axk1._latents(cfg, w, h, cos, sin, None)
        want = axk1._expanded(cfg, w, q_n, q_r, row)
        causal = jnp.tril(jnp.ones((24, 24), bool))[None, None]
        got = axk1._absorbed(cfg, w, q_n, q_r, row, causal)
    assert row.shape == (2, 24, 1, cfg.latent_width)
    assert gap(got, want) < 1e-5


@pytest.mark.parametrize("f32", ["whole"], indirect=True)   # one bank is enough
def test_a_mixed_call_is_its_two_segments(f32, one_device):
    """One chunk's rows beside two decode rows in ONE call of
    ``apply_paged``: every row's logits are the reference's."""
    hf, cfg, params, row, want = f32
    rng = np.random.default_rng(5)
    others = [rng.integers(0, 256, n) for n in (13, 27)]
    wants = [reference.logits(hf, family.Weights(params), o) for o in others]
    f = jnp.float32
    paged = jax.jit(functools.partial(axk1.apply_paged, cfg, compute_dtype=f))
    cache = axk1.init_paged_cache(cfg, 40, BLOCK, dtype=f)
    tables = np.zeros((4, 32), np.int32)
    for i in range(3):
        tables[i, :8] = 1 + 8 * i + np.arange(8)
    with jax.default_matmul_precision("highest"):
        for i, o in enumerate(others):       # the decode rows' contexts
            _, cache = paged(
                params, jnp.asarray(o[None, :-1]), cache,
                jnp.asarray(tables[i:i + 1]), jnp.zeros((1,), jnp.int32))
        _, cache = paged(                    # the chunk's first 16 tokens
            params, jnp.asarray(row[None, :16]), cache,
            jnp.asarray(tables[2:3]), jnp.zeros((1,), jnp.int32))
        call = MixedCall(
            tables=jnp.asarray(tables), lens=jnp.asarray([12, 26, 0, 0]),
            active=jnp.asarray([True, True, False, False]),
            chunk_table=jnp.asarray(tables[2]),
            chunk_ctx=jnp.asarray(16), chunk_valid=jnp.asarray(5))
        tokens = np.zeros((1, 4 + 8), np.int32)
        tokens[0, 0], tokens[0, 1] = others[0][-1], others[1][-1]
        tokens[0, 4:9] = row[16:21]
        got, _ = paged(params, jnp.asarray(tokens), cache, call, None,
                       valid=call.valid(12))
    assert gap(got[0, 0], wants[0][-1]) < TOL
    assert gap(got[0, 1], wants[1][-1]) < TOL
    assert gap(got[0, 4:9], want[16:21]) < TOL


def engine(cfg, params, **config):
    return build_engine_v2(axk1, cfg, params, config={
        "dtype": "float32", "prefill_bucket": 8, "split_prefill_chunk": CHUNK,
        "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                   "memory_config_blocks": 140, "block_size": BLOCK},
        **config})


@pytest.mark.parametrize("f32", ["held"], indirect=True)
def test_the_served_path_agrees_with_the_reference(f32, one_device):
    """Four requests through ``ServingScheduler.tick``, one mixed program a
    tick launched ahead: prompts enter chunk by chunk beside live decode
    rows, every served token is the top of the reference's logits (or
    within float32's noise of it), and the spans and events say what the
    latent pool did."""
    hf, cfg, params, _, _ = f32
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (50, 100, 37, 10)]
    eng = engine(cfg, params, trace={"enabled": True})
    sched = ServingScheduler(eng, SchedulerConfig(
        decode_quantum=1, max_admissions_per_tick=1))
    handles = [sched.submit(Request(prompt=list(p), max_new_tokens=6))
               for p in prompts]
    held_most = 0
    with jax.default_matmul_precision("highest"):
        for _ in range(400):
            if not sched.pending:
                break
            sched.tick()
            eng.state.debug_check()
            held_most = max(held_most, sched.last_tick["latent_blocks_live"])
            assert sched.last_tick["latent_blocks"] == 139
    assert not sched.pending and held_most > 0
    for prompt, h in zip(prompts, handles):
        toks = list(h.tokens)
        seq = np.asarray(prompt + toks[:-1])
        want = reference.logits(hf, family.Weights(params),
                                seq)[len(prompt) - 1:]
        gaps = want.max(-1) - want[np.arange(len(toks)), toks]
        assert len(toks) == 6 and gaps.max() < 0.05, gaps
    assert eng.mixed_steps > 0 and eng.overlapped_steps > 0
    assert dict((k, v) for k, v, _ in eng.kv_kind_events()) \
        == {"Serving/kv/latent_blocks_live": 0.0}
    spans = [e["args"] for e in eng.tracer.events()
             if e["ph"] == "X" and e["name"] == "decode_step"]
    assert any(a.get("kv_tokens_latent", 0) > 0
               and a.get("attn_tiles_grid", 0) > 0 for a in spans)
    assert any(a.get("chunk_kv_tokens_latent", 0) > 0
               and a.get("chunk_attn_tiles_live", 0) > 0 for a in spans)
    summary = eng.compile_monitor.summary()
    assert all(s.get("pool_copy_bytes", 0) == 0 for s in summary.values())


@pytest.mark.parametrize("feature, config", [
    ("inference.kv_quant", {"kv_quant": {"enabled": True}}),
    ("inference.speculative", {"speculative": {"enabled": True}}),
    ("inference.prefix_cache.host_spill",
     {"prefix_cache": {"enabled": True, "host_spill": True}}),
])
def test_a_latent_cache_refuses_by_name_at_configuration(feature, config):
    cfg, params, _ = build()
    with pytest.raises(LatentKVError, match=feature.replace(".", r"\.")):
        engine(cfg, params, **config)


@pytest.mark.parametrize("call", ["export_kv_blocks", "import_kv_blocks"])
def test_a_latent_cache_refuses_the_disagg_wire_by_name(call, one_device):
    cfg, params, row = build()
    eng = engine(cfg, params)
    eng.put(1, row[:10].tolist())
    args = (1,) if call == "export_kv_blocks" else (2, {})
    with pytest.raises(LatentKVError, match=call):
        getattr(eng, call)(*args)


def greedy(eng, uid, prompt, steps):
    out = [int(eng.put(uid, prompt))]
    while len(out) < steps:
        out.append(int(eng.step()[uid]))
    return out


def test_prefix_reuse_runs_over_the_latent_pool(one_device):
    """A second prompt that shares its first blocks with a finished one
    takes them from the prefix cache and is served the tokens a cold engine
    serves it."""
    cfg, params, row = build()
    first = row[:24].tolist()
    second = first[:16] + row[24:32].tolist()
    with jax.default_matmul_precision("highest"):
        cold = greedy(engine(cfg, params), 1, second, 4)
        eng = engine(cfg, params, prefix_cache={"enabled": True})
        greedy(eng, 1, first, 2)
        eng.finish(1)
        warm = greedy(eng, 2, second, 4)
    assert eng.state.prefix_stats["hit_tokens"] >= 16
    assert warm == cold


def test_fork_runs_over_the_latent_pool(one_device):
    """A child shares its parent's latent blocks (copy-on-write where it
    writes) and decodes the parent's tokens."""
    cfg, params, row = build()
    with jax.default_matmul_precision("highest"):
        eng = engine(cfg, params)
        greedy(eng, 1, row[:21].tolist(), 2)
        eng.fork(1, 2)
        both = [eng.step() for _ in range(3)]
    assert [b[1] for b in both] == [b[2] for b in both]
    eng.state.debug_check()


@pytest.mark.parametrize("share", range(4))
def test_the_shares_add_up_to_the_uncut_layer(share):
    """The guide's share test, one share a case: the routed part that share
    ``share`` of four computes - its two experts' terms, the router over all
    eight with its group limit and scale - is the reference's for that
    share; and (share 0) the four shares' routed parts plus ONE shared
    expert are the uncut layer."""
    cfg, params, _ = build()
    hf = {**TINY, "num_experts": 2, "experts_first": 2 * share}
    h = jax.random.normal(jax.random.PRNGKey(share), (24, 64), jnp.float32)
    moe = {k: v[0] for k, v in params["layers"]["moe"].items()}

    def routed(held, bank):
        mine = {k: v for k, v in bank.items() if not k.startswith("shared")}
        c = dataclasses.replace(cfg, experts_held=held)
        return axk1._moe(c, False)(mine, h[None])[0][0]

    cut = lambda first: {k: v[first:first + 2] if k.startswith("w_") else v
                         for k, v in moe.items()}
    w = family.Weights(params).layer(1)
    with jax.default_matmul_precision("highest"):
        got = routed((2 * share, 2), cut(2 * share))
        want_all = reference.experts(h, w, TINY)
        want = reference.experts(
            h, {**w, "experts": w["experts"][2 * share:2 * share + 2]}, hf)
        shared = sum(reference._expert(h, jnp.ones((24,)), *bank)
                     for bank in w["shared"])
        assert len(w["shared"]) == 1
        assert gap(got + shared, want) < TOL
        if share == 0:
            parts = sum(routed((2 * s, 2), cut(2 * s)) for s in range(4))
            whole = axk1._moe(cfg, False)(moe, h[None])[0][0]
            assert gap(parts + shared, want_all) < TOL
            assert gap(whole, want_all) < TOL


def by_hand(scores, k, n_group, topk_group, norm):
    """Group-limited top-k, a loop a token: ``[T, E]`` gates."""
    scores = np.asarray(scores, np.float64)
    out = np.zeros_like(scores)
    size = scores.shape[1] // n_group
    for t, s in enumerate(scores):
        best = [np.sort(s[g * size:(g + 1) * size])[-2:].sum()
                for g in range(n_group)]
        groups = np.argsort(-np.asarray(best), kind="stable")[:topk_group]
        allowed = [e for e in range(len(s)) if e // size in groups]
        chosen = sorted(allowed, key=lambda e: (-s[e], e))[:k]
        total = s[chosen].sum() if norm else 1.0
        out[t, chosen] = s[chosen] / total
    return out


@pytest.mark.parametrize("norm_topk", [True, False])
@pytest.mark.parametrize("k, groups", [(2, (4, 2)), (4, (8, 3)), (8, (4, 4)),
                                       (3, (1, 1))])
def test_group_limited_gating_is_the_hand_written_loop(k, groups, norm_topk):
    logits = jax.random.normal(jax.random.PRNGKey(k), (40, 32)) * 2.0
    cg = top_k_gating_compact(logits, k, drop_tokens=False, score="sigmoid",
                              norm_topk=norm_topk, groups=groups)
    got = np.zeros((40, 32))
    np.put_along_axis(got, np.asarray(cg.topk_idx), np.asarray(cg.gates), 1)
    want = by_hand(jax.nn.sigmoid(logits), k, *groups, norm_topk)
    np.testing.assert_allclose(got, want, atol=1e-6)
    dense = top_k_gating(logits, k, drop_tokens=False, score="sigmoid",
                         norm_topk=norm_topk, groups=groups)
    np.testing.assert_allclose(dense.combine_weights.sum(-1), want,
                               atol=1e-6)
    # every group allowed: the plain top-k
    plain = top_k_gating_compact(logits, k, drop_tokens=False,
                                 score="sigmoid", norm_topk=norm_topk)
    every = top_k_gating_compact(logits, k, drop_tokens=False,
                                 score="sigmoid", norm_topk=norm_topk,
                                 groups=(groups[0], groups[0]))
    assert (plain.topk_idx == every.topk_idx).all()


@pytest.mark.parametrize("held", [(0, 32), (8, 6)])
@pytest.mark.parametrize("groups", [(4, 4), (4, 2), (8, 3)])
def test_the_references_routing_margin_is_the_hand_written_one(groups, held):
    """Each token's margin (``reference.route_margin``, what the harness
    calls a routing decided by), a loop a token: over the HELD experts it
    may choose from, the logit gap to the boundary of its top k; and the
    gap between group scores that lets a held expert's group in or out (or,
    once one is in, exchanges any chosen group), as the logit gap that
    closes it."""
    k = 3
    z = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (30, 32))) * 1.5
    cfg = {"num_experts_per_tok": k, "n_group": groups[0],
           "topk_group": groups[1], "experts_first": held[0],
           "num_experts": held[1]}
    got = np.asarray(reference.route_margin(jnp.asarray(z), cfg))
    size = 32 // groups[0]
    mine = range(held[0], held[0] + held[1])
    for t, row in enumerate(z):
        s = 1 / (1 + np.exp(-row))
        best = [np.sort(s[g * size:(g + 1) * size])[-2:]
                for g in range(groups[0])]
        between = lambda a, b: 2 * (a.sum() - b.sum()) / (
            (a * (1 - a)).sum() + (b * (1 - b)).sum())
        order = list(np.argsort([-b.sum() for b in best], kind="stable"))
        chosen = order[:groups[1]]
        allowed = [e for e in range(32) if e // size in chosen]
        ranked = sorted((row[e] for e in allowed), reverse=True)
        want = min([row[e] - ranked[k] if row[e] >= ranked[k - 1]
                    else ranked[k - 1] - row[e]
                    for e in allowed if e in mine], default=np.inf)
        if groups[1] < groups[0]:
            last = best[chosen[-1]]
            for g in {e // size for e in mine}:
                want = min(want, between(last, best[order[groups[1]]])
                           if g in chosen else between(last, best[g]))
        assert got[t] == pytest.approx(want, rel=1e-4, abs=1e-5)
    # a token whose margin is wide keeps its held experts under a small push
    wide = got > 0.2
    pushed = z + 0.05 * np.sign(np.sin(np.arange(32) * 7.0))[None]
    gates = lambda a: np.asarray(reference.route(jnp.asarray(a), {
        **cfg, "norm_topk_prob": True, "routed_scaling_factor": 1.0}))[
            :, held[0]:held[0] + held[1]] > 0
    assert wide.any() and (gates(z) == gates(pushed))[wide].all()


def test_the_route_scale_scales_the_routed_sum_and_not_the_shared_expert():
    cfg, params, _ = build()
    moe = {k: v[0] for k, v in params["layers"]["moe"].items()}
    routed = {k: v for k, v in moe.items() if not k.startswith("shared")}
    h = jax.random.normal(jax.random.PRNGKey(2), (1, 24, 64), jnp.float32)
    kw = dict(drop_tokens=False, score="sigmoid", groups=(4, 2))
    plain, scaled = (MoELayer(8, 2, route_scale=s, **kw)
                     for s in (None, 2.5))
    with jax.default_matmul_precision("highest"):
        shared = plain(moe, h)[0] - plain(routed, h)[0]
        assert gap(scaled(routed, h)[0], 2.5 * plain(routed, h)[0]) < 1e-5
        assert gap(scaled(moe, h)[0],
                   2.5 * plain(routed, h)[0] + shared) < 1e-5


def test_a_group_count_that_does_not_divide_the_experts_is_refused():
    with pytest.raises(ValueError, match="groups"):
        top_k_gating_compact(jnp.zeros((4, 10)), 2, groups=(4, 2))


@pytest.mark.parametrize("d, theta, factor, length", [
    (64, 10000.0, 32.0, 4096), (8, 10000.0, 4.0, 128)])
def test_yarn_frequencies_are_the_closed_form(d, theta, factor, length):
    """Dimension by dimension against the published ramp, written out."""
    got = yarn_inv_frequencies(d, theta, factor, length, 32.0, 1.0)
    dim = lambda turns: d * math.log(length / (turns * 2 * math.pi)) \
        / (2 * math.log(theta))
    low, high = max(math.floor(dim(32.0)), 0), min(math.ceil(dim(1.0)), d - 1)
    for i in range(d // 2):
        f = theta ** (-2 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        assert got[i] == pytest.approx(f / factor * ramp + f * (1 - ramp),
                                       rel=1e-6)
    assert got[0] == pytest.approx(1.0) and \
        got[-1] == pytest.approx(theta ** (-(d - 2) / d) / factor, rel=1e-6)
    np.testing.assert_allclose(
        got, reference.yarn_inv_freq(
            {"qk_rope_head_dim": d, "rope_theta": theta, "rope_scaling": {
                "factor": factor, "original_max_position_embeddings": length,
                "beta_fast": 32, "beta_slow": 1}}), rtol=1e-6)
    cos, sin = yarn_frequencies(d, 16, theta, factor, length,
                                table_scale=0.5)
    np.testing.assert_allclose(cos[3], 0.5 * np.cos(3 * got), atol=1e-6)
    np.testing.assert_allclose(sin[3], 0.5 * np.sin(3 * got), atol=1e-6)


def test_the_softmax_scale_carries_yarns_temperature_squared():
    assert yarn_mscale(32.0) == pytest.approx(1.3466, abs=1e-4)
    assert yarn_mscale(1.0) == 1.0
    assert axk1.AxK1Config().softmax_scale == pytest.approx(
        192 ** -0.5 * 1.8133, rel=1e-4)


# --- the walk at a key width that is not the value width ------------------- #
def latent_case(seed, t, ctx, lengths, bs=8, width=256, vd=128, nh=8):
    rng = np.random.default_rng(seed)
    b = len(ctx)
    pool = rng.standard_normal((2, 12, 1, bs, width)).astype(np.float32)
    tables = np.stack([rng.permutation(np.arange(1, 12))[:5]
                       for _ in range(b)]).astype(np.int32)
    q = rng.standard_normal((b, t, nh, width)).astype(np.float32)
    return (jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
            jnp.asarray(ctx, jnp.int32), jnp.asarray(lengths, jnp.int32), vd)


@pytest.mark.parametrize("t, ctx, lengths", [
    (1, [0, 17, 39], [1, 1, 1]), (8, [0, 9, 24], [8, 5, 8]),
    (20, [3, 0, 11], [20, 1, 13]), (16, [24, 0, 5], [16, 1, 9]),
    (5, [35, 0, 8], [5, 5, 5])])
def test_the_latent_walk_in_interpret_mode_is_its_xla_twin(t, ctx, lengths):
    """Keys the whole 256-lane row, values its first 128 lanes, one KV head
    and a group of 8: decode and prefill - both walks fetch the latent
    pool's pages themselves -, Pallas (interpreted) against the gathered
    reference, on every real row: a context that fills the table, a
    one-row sequence beside a padded chunk, a verify window."""
    q, pool, tables, ctx, lengths, vd = latent_case(t, t, ctx, lengths)
    kw = dict(scale=0.11, layer=1, value_width=vd)
    if t == 1:
        got = pa.paged_decode_attention(q[:, 0], pool, None, tables, ctx,
                                        **kw)[:, None]
        want = pa.paged_decode_attention_xla(q[:, 0], pool, None, tables,
                                             ctx, **kw)[:, None]
    else:
        got = pa.paged_prefill_attention(q, pool, None, tables, ctx, lengths,
                                         **kw)
        want = pa.paged_prefill_attention_xla(q, pool, None, tables, ctx,
                                              lengths, **kw)
    assert got.shape == (3, t, 8, vd)
    for b, n in enumerate(np.asarray(lengths)):
        assert gap(got[b, :n], want[b, :n]) < 2e-5


def test_the_one_pool_write_in_interpret_mode_is_its_xla_twin():
    _, pool, tables, ctx, lengths, _ = latent_case(7, 8, [0, 9, 24],
                                                   [8, 5, 8])
    rows = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 1, 256))
    got = pa.paged_kv_write(rows, None, pool, None, tables, ctx, lengths,
                            layer=1)
    want = pa.paged_kv_write_xla(rows, None, pool, None, tables, ctx,
                                 lengths, layer=1)
    assert got[1:] == want[1:] == (None, None, None)
    assert gap(got[0], want[0]) == 0.0
    assert gap(got[0][0], pool[0]) == 0.0 and gap(got[0][1], pool[1]) > 0


def test_a_latent_pool_is_one_page_a_step_in_the_tile_counts():
    """One pool halves what a (head, page) pair keeps in VMEM, the walk
    fetches its 640-lane rows itself and widens to eight 128-token pages,
    and the counts follow the kernel's own tile sizes: each slot its own
    tiles, a slot at context 0 the one that writes its row."""
    assert pa._decode_tiles(1, 64, 640, 128, 256, 2, False, pools=1) \
        == (8, 1, 32)
    live, grid = pa.decode_tile_counts([100, 3000, 0], 64, (1, 128, 640), 2,
                                       256, False, pools=1)
    assert (live, grid) == (1 + 3 + 1,) * 2
    assert pa._prefill_tiles(512, 64, 640, 128, 256) == (16, 32, 2)


@pytest.mark.parametrize("f32", ["held"], indirect=True)    # one bank is enough
def test_the_paged_program_runs_the_pallas_walk_in_interpret_mode(f32):
    """``apply_paged`` with the three paged ops forced to their Pallas forms
    (interpreted off the chip): the logits the XLA twins give."""
    hf, cfg, params, row, want = f32
    names = ("paged_kv_write", "paged_decode_attention",
             "paged_prefill_attention")
    for n in names:
        registry.set_backend(n, "pallas")
    try:
        with jax.default_matmul_precision("highest"):
            got = program_logits("apply_paged", cfg, params, row)
    finally:
        for n in names:
            registry.set_backend(n, None)
    assert gap(got, want) < TOL


# --- the probes' comparison and the configuration's file ------------------- #
def test_a_probe_beyond_the_limit_raises(f32, capsys):
    hf, cfg, params, row, _ = f32
    weights = family.Weights(params, ROLE)
    _, margin = reference.logits_and_margin(hf, weights, row)
    assert '"why_not": []' in capsys.readouterr().out
    # a margin a position, finite (the harness then allows a model that
    # routes its one position a run): the sparse layers' least, in its units
    per_layer = []
    reference.logits(hf, weights, row, margins=per_layer)
    assert len(per_layer) == cfg.num_layers - cfg.first_k_dense
    assert margin.shape == (len(row),) and np.isfinite(margin).all()
    np.testing.assert_allclose(
        margin, reference.MARGIN_SCALE * np.min(per_layer, axis=0)[:len(row)])
    weights.program =family.Program(
        jax.tree.map(lambda p: p * 1.01, params), ROLE)
    with pytest.raises(reference.Disagreement, match="the limit is"):
        reference.logits_and_margin(hf, weights, row)


def test_the_configuration_file_is_the_catalogs_with_the_cut_laid_over():
    """Every published key stands at the top level with the serve role's cut
    laid over it, no width is cut, and the family builds the program's
    configuration from it: the router 192 wide, 12 experts held, one dense
    layer ahead of four sparse ones, 576 numbers a token a layer."""
    run = {**CONFIG["published"], **CONFIG["roles"]["serve"]["model"]}
    assert {k: CONFIG[k] for k in run} == run
    assert set(CONFIG["roles"]["serve"]["model"]) == set(CONFIG["reduced"])
    assert not set(CONFIG["reduced"]) & set(manifest.WIDTH_KEYS)
    for key, value in (("hidden_size", 7168), ("num_attention_heads", 64),
                       ("intermediate_size", 18432),
                       ("moe_intermediate_size", 2048),
                       ("n_routed_experts", 192), ("num_local_experts", 192),
                       ("num_experts_per_tok", 8), ("kv_lora_rank", 512),
                       ("q_lora_rank", 1536), ("qk_rope_head_dim", 64),
                       ("vocab_size", 163840)):
        assert run[key] == value
    cfg = family.build_cfg(run, **CONFIG["roles"]["serve"]["program_options"])
    assert cfg.experts_held == (0, 12) and cfg.num_experts == 192
    assert (cfg.first_k_dense, cfg.num_layers) == (1, 5)
    assert axk1.latent_kind(cfg) == {"key_width": 576, "value_width": 512}
    shapes = jax.eval_shape(lambda: axk1.init_paged_cache(cfg, 8, 128))
    assert shapes["latent"].shape == (5, 8, 1, 128, 640)
