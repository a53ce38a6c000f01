"""Command A+'s language model through ``models/cohere2_moe.py`` (ISSUE 42)
against the benchmark's plain reference (``benchmark/reference/
cohere2_moe.py``) at toy widths on the CPU, float32 and seeded: window and
full layers in one stack - contexts below, at and several times a toy
window - along every path (the full forward, the dense cache, chunked
prefill then decode through BOTH paged pools, a mixed call, and
``ServingScheduler.tick`` with a split prompt beside live rows), each
deliberately wrong variant, one chip's share of the expert bank tied to the
whole layer, the sigmoid gating by hand, and the configuration's file.
"""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import cohere2_moe as family
from benchmark.harness import manifest
from benchmark.reference import cohere2_moe as reference
from benchmark.reference import cohere2_moe_variants as variants
from deepspeed_tpu.inference.engine_v2 import build_engine_v2
from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                             ServingScheduler)
from deepspeed_tpu.models import cohere2_moe
from deepspeed_tpu.models import _paged
from deepspeed_tpu.models._paged import MixedCall
from deepspeed_tpu.moe.sharded_moe import top_k_gating, top_k_gating_compact

CONFIG = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "configs", family.CONFIG_FILE))
WINDOW = 16
TINY = {**CONFIG["published"], **CONFIG["rehearsal"]["published"],
        "sliding_window": WINDOW, "num_hidden_layers": 4, "num_experts": 8,
        "max_position_embeddings": 128}
HELD = {**TINY, "num_experts": 2, "experts_first": 4}   # one share of four
PROMPT, CHUNK, STEPS, BLOCK = 53, 8, 6, 4    # contexts to 3.6 x the window
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
    params = family.init(cfg, jax.random.PRNGKey(0))
    params["layers"]["norm"] = params["layers"]["norm"] * (
        1.0 + 0.2 * jax.random.normal(jax.random.PRNGKey(10),
                                      params["layers"]["norm"].shape))
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
        return cohere2_moe.apply(cfg, params, jnp.asarray(row[None]),
                                 compute_dtype=f32)[0][0]
    out = []
    # a jit of this call's own: one compile a shape, not one a piece
    if path == "apply_cached":
        cached = jax.jit(functools.partial(cohere2_moe.apply_cached, cfg,
                                           compute_dtype=f32))
        cache = cohere2_moe.init_cache(cfg, 1, 64, dtype=f32)
        for start, piece in pieces(row):
            logits, cache = cached(params, jnp.asarray(piece[None]), cache,
                                   jnp.asarray([start], jnp.int32))
            out.append(logits[0])
        return jnp.concatenate(out)
    # both pools through a manager's tables: the window kind's blocks are
    # given back on the way, its segment short, its lengths shifted
    paged = jax.jit(functools.partial(cohere2_moe.apply_paged, cfg,
                                      compute_dtype=f32))
    state = manager(cfg, slots=1)
    kind, = state.window_kinds
    cache = cohere2_moe.init_paged_cache(
        cfg, 40, BLOCK, dtype=f32, window_blocks={"window": kind.num_blocks})
    desc = state.admit(0, len(row))
    for start, piece in pieces(row):
        width = CHUNK if start < PROMPT else 1
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(piece)] = piece
        state.extend(desc, len(piece))
        logits, cache = paged(
            params, jnp.asarray(padded), cache,
            jnp.asarray(state.block_table(desc)[None]),
            jnp.asarray([start], jnp.int32),
            valid=jnp.arange(width)[None] < len(piece))
        desc.seen_tokens = start + len(piece)
        out.append(logits[0, :len(piece)])
    assert state.window_blocks_released > 0
    return jnp.concatenate(out)


def manager(cfg, slots):
    """A ``StateManager`` as the engine builds it for ``cfg`` at the tests'
    block and chunk sizes."""
    from deepspeed_tpu.inference.ragged import StateManager, WindowKind

    kinds = [WindowKind.sized(name, window, slots, CHUNK, BLOCK)
             for name, window in cohere2_moe.window_kinds(cfg).items()]
    return StateManager(slots, 40, BLOCK, cfg.max_seq_len // BLOCK,
                        window_kinds=kinds)


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
    """Every position of a 59-token row - contexts of 1-16 are inside the
    window, 17-59 up to 3.6 times it - along each path, LOGITS and not
    tokens; with one chip's share of the bank the partial sum is the
    reference's partial sum."""
    _, cfg, params, row, want = f32
    with jax.default_matmul_precision("highest"):
        got = program_logits(path, cfg, params, row)
    assert got.shape == want.shape and gap(got, want) < TOL


@pytest.mark.parametrize("variant", variants.NAMES)
def test_each_wrong_variant_fails_the_tolerance(f32, variant):
    """A window on the full layer, none on the window layers, rope on the
    full layers, half-split rope, a softmax router, shared experts summed,
    a sequential block and an RMSNorm each lie a hundred times beyond what
    the program, along every path, is held to."""
    hf, _, params, row, want = f32
    wrong = variants.logits(variant, hf, family.Weights(params), row)
    assert gap(wrong, want) > 100 * TOL


@pytest.mark.parametrize("name", ["right", "right_poisoned", "released_early",
                                  "fp8_weights"])
def test_the_probes_comparison_passes_the_right_program_alone(f32, name):
    """``reference.held`` on the program's ``apply_paged`` logits - 51 tokens
    in chunks of 8, then 8 single tokens, the window kind's blocks given back
    by a ``StateManager`` on the way: inside the limit as it is and with
    everything it gave back poisoned; beyond it where a block is given back
    one block early, and where the weights are rounded to fp8."""
    hf, _, params, row, want = f32
    kw = {"right": {}, "right_poisoned": {"poison": True},
          "released_early": {"poison": True, "release_early": 1},
          "fp8_weights": {"weights": "float8_e5m2"}}[name]
    program = family.Program(params, ROLE, **kw)
    with jax.default_matmul_precision("highest"):
        got = program.logits(hf, row, reference.HELD_DECODE)
    assert got.shape == (reference.HELD_DECODE + 1, 256)
    why = reference.disagreements(reference.held(got, want[-got.shape[0]:]),
                                  program.limits)
    assert bool(why) == (not name.startswith("right")), why


def test_a_probe_beyond_the_limit_raises(f32, capsys):
    """``logits_and_margin`` with the family's weights: the probe's reading
    is a line of the output, and a program that is not the reference's (its
    output projection negated) raises by name."""
    hf, _, params, row, want = f32
    weights = family.Weights(params, role=ROLE)
    with jax.default_matmul_precision("highest"):
        got, margin = reference.logits_and_margin(hf, weights, row)
    assert gap(got, want) == 0 and bool(jnp.isinf(margin).all())
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "held" and line["why_not"] == []
    assert line["limits"] == {"logits_mean_abs_diff": TOL}
    layers = {**params["layers"], "wo": -params["layers"]["wo"]}
    weights.program.params = {**params, "layers": layers}
    with jax.default_matmul_precision("highest"), \
            pytest.raises(reference.Disagreement, match="logits lie"):
        reference.logits_and_margin(hf, weights, row)


@pytest.mark.parametrize("f32", ["whole"], indirect=True)   # one bank is enough
def test_a_mixed_call_is_its_two_segments(f32, one_device):
    """One chunk's rows beside two decode rows in ONE call of
    ``apply_paged``, each kind's table its own segment of a manager's
    tables: every row's logits are the reference's, the chunk's and the
    decode rows' (one context inside the window, one past it - its window
    segment starts at an offset)."""
    hf, cfg, params, row, want = f32
    rng = np.random.default_rng(5)
    others = [rng.integers(0, 256, n) for n in (13, 27)]
    wants = [reference.logits(hf, family.Weights(params), o) for o in others]
    f = jnp.float32
    paged = jax.jit(functools.partial(cohere2_moe.apply_paged, cfg,
                                      compute_dtype=f))
    state = manager(cfg, slots=4)
    kind, = state.window_kinds
    cache = cohere2_moe.init_paged_cache(
        cfg, 40, BLOCK, dtype=f, window_blocks={"window": kind.num_blocks})
    descs = [state.admit(i, 32) for i in range(3)]

    def table(desc, n):
        state.extend(desc, n)
        return state.block_table(desc)

    with jax.default_matmul_precision("highest"):
        for d, o in zip(descs, others):      # the decode rows' contexts
            for start in range(0, len(o) - 1, CHUNK):
                piece = o[start:min(start + CHUNK, len(o) - 1)]
                pad = np.zeros((1, CHUNK), np.int32)
                pad[0, :len(piece)] = piece
                _, cache = paged(
                    params, jnp.asarray(pad), cache,
                    jnp.asarray(table(d, len(piece))[None]),
                    jnp.asarray([start], jnp.int32),
                    valid=jnp.arange(CHUNK)[None] < len(piece))
                d.seen_tokens = start + len(piece)
        for start in (0, 8):                 # the chunk's first 16 tokens
            _, cache = paged(
                params, jnp.asarray(row[None, start:start + 8]), cache,
                jnp.asarray(table(descs[2], 8)[None]),
                jnp.asarray([start], jnp.int32))
            descs[2].seen_tokens = start + 8
        tables = np.zeros((4, state.table_width), np.int32)
        tables[0], tables[1] = table(descs[0], 1), table(descs[1], 1)
        assert tables[1, state.max_blocks_per_seq] > 0   # an offset: 26 > 16
        call = MixedCall(
            tables=jnp.asarray(tables), lens=jnp.asarray([12, 26, 0, 0]),
            active=jnp.asarray([True, True, False, False]),
            chunk_table=jnp.asarray(table(descs[2], 5)),
            chunk_ctx=jnp.asarray(16), chunk_valid=jnp.asarray(5))
        tokens = np.zeros((1, 4 + 8), np.int32)
        tokens[0, 0], tokens[0, 1] = others[0][-1], others[1][-1]
        tokens[0, 4:9] = row[16:21]
        got, _ = paged(params, jnp.asarray(tokens), cache, call, None,
                       valid=call.valid(12))
    assert gap(got[0, 0], wants[0][-1]) < TOL
    assert gap(got[0, 1], wants[1][-1]) < TOL
    assert gap(got[0, 4:9], want[16:21]) < TOL


def serve(hf, cfg, params, prompts, steps, **engine):
    """``prompts`` through ``ServingScheduler.tick`` of a float32 engine;
    returns the engine, the scheduler and each request's tokens."""
    eng = build_engine_v2(family.module(), cfg, params, config={
        "dtype": "float32", "prefill_bucket": 8, "split_prefill_chunk": CHUNK,
        "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                   "memory_config_blocks": 140, "block_size": BLOCK},
        **engine})
    sched = ServingScheduler(eng, SchedulerConfig(
        decode_quantum=1, max_admissions_per_tick=1))
    handles = [sched.submit(Request(prompt=list(p), max_new_tokens=steps))
               for p in prompts]
    released = 0
    with jax.default_matmul_precision("highest"):
        for _ in range(400):
            if not sched.pending:
                break
            sched.tick()
            eng.state.debug_check()
            released += sched.last_tick["window_blocks_released"]
            for d in eng.state.seqs.values():     # never more than its share
                for kind in eng.state.window_kinds:
                    assert eng.state.window_held(d, kind) \
                        <= kind.blocks_per_seq
    assert not sched.pending
    return eng, sched, [list(h.tokens) for h in handles], released


@pytest.mark.parametrize("f32", ["held"], indirect=True)
def test_the_served_path_agrees_with_the_reference(f32, one_device):
    """Four requests through ``ServingScheduler.tick``, one mixed program a
    tick launched ahead: prompts of 0.6-6 windows enter chunk by chunk
    beside live decode rows, the window kind gives blocks back all the way,
    and every served token is the top of the reference's logits (or within
    float32's noise of it)."""
    hf, cfg, params, _, _ = f32
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).tolist() for n in (50, 100, 37, 10)]
    eng, sched, served, released = serve(hf, cfg, params, prompts, 6)
    for prompt, toks in zip(prompts, served):
        seq = np.asarray(prompt + toks[:-1])
        want = reference.logits(hf, family.Weights(params),
                                seq)[len(prompt) - 1:]
        gaps = want.max(-1) - want[np.arange(len(toks)), toks]
        assert len(toks) == 6 and gaps.max() < 0.05, gaps
    assert eng.mixed_steps > 0 and eng.overlapped_steps > 0
    assert released == eng.state.window_blocks_released > 0
    events = dict((k, v) for k, v, _ in eng.kv_kind_events())
    assert events == {"Serving/kv/full_blocks_live": 0.0,
                      "Serving/kv/window_blocks_live": 0.0,
                      "Serving/kv/window_blocks_released": float(released)}
    summary = eng.compile_monitor.summary()
    assert all(s.get("pool_copy_bytes", 0) == 0 for s in summary.values())


@pytest.mark.parametrize("share", range(4))
def test_the_four_shares_add_up_to_the_uncut_layer(share):
    """The guide's share test, one share a case: the routed part that share
    ``share`` of four computes - its two experts' terms, the router over all
    eight - is the reference's for that share; and (share 0) the four
    shares' routed parts plus the shared experts ONCE are the uncut
    layer."""
    cfg, params, row = build()
    hf = {**TINY, "num_experts": 2, "experts_first": 2 * share}
    h = jax.random.normal(jax.random.PRNGKey(share), (24, 64), jnp.float32)
    moe = {k: v[0] for k, v in params["layers"]["moe"].items()}
    held = dataclasses.replace(cfg, experts_held=(2 * share, 2))

    def routed(c, bank):
        mine = {k: v for k, v in bank.items() if not k.startswith("shared")}
        return cohere2_moe._moe(c, False)(mine, h[None])[0][0]

    cut = lambda first: {k: v[first:first + 2] if k.startswith("w_") else v
                         for k, v in moe.items()}
    w = family.Weights(params).layer(0)
    with jax.default_matmul_precision("highest"):
        got = routed(held, cut(2 * share))
        want_all = reference.experts(h, w, TINY, reference._freeze(TINY))
        want = reference.experts(
            h, {**w, "experts": w["experts"][2 * share:2 * share + 2],
                "shared": w["shared"]}, hf, reference._freeze(hf))
        shared = want_all - sum(
            reference._expert(h, reference._route(
                h, w["router"], reference._freeze(TINY),
                reference.RIGHT)[:, e], *w["experts"][e]) for e in range(8))
        assert gap(got + shared, want) < TOL
        if share == 0:
            parts = sum(routed(dataclasses.replace(
                cfg, experts_held=(2 * s, 2)), cut(2 * s)) for s in range(4))
            whole = cohere2_moe._moe(cfg, False)(moe, h[None])[0][0]
            assert gap(parts + shared, want_all) < TOL
            assert gap(whole, want_all) < TOL


@pytest.mark.parametrize("norm_topk", [True, False])
@pytest.mark.parametrize("k", [1, 2, 8])
def test_sigmoid_gating_is_the_hand_written_top_k(k, norm_topk):
    """``score="sigmoid"``: each expert's score by itself, the ``k`` largest
    (the lower index first among equals), their sum the divisor where
    ``norm_topk`` - against a loop over rows in numpy; softmax stays the
    default."""
    rng = np.random.default_rng(k)
    logits = np.round(rng.normal(size=(12, 16)) * 2, 1).astype(np.float32)
    cg = top_k_gating_compact(jnp.asarray(logits), k, drop_tokens=False,
                              norm_topk=norm_topk, score="sigmoid")
    for t, row in enumerate(logits):
        s = 1.0 / (1.0 + np.exp(-row.astype(np.float64)))
        order = sorted(range(16), key=lambda e: (-s[e], e))[:k]
        assert list(np.asarray(cg.topk_idx[t])) == order
        want = s[order] / (s[order].sum() if norm_topk else 1.0)
        np.testing.assert_allclose(np.asarray(cg.gates[t]), want, rtol=1e-5)
    dense = top_k_gating(jnp.asarray(logits), k, drop_tokens=False,
                         norm_topk=norm_topk, score="sigmoid")
    np.testing.assert_allclose(np.asarray(dense.combine_weights.sum((1, 2))),
                               np.asarray(cg.gates.sum(1)), rtol=1e-5)
    soft = top_k_gating_compact(jnp.asarray(logits), k, drop_tokens=False)
    np.testing.assert_allclose(np.asarray(soft.router_probs.sum(1)), 1.0,
                               rtol=1e-5)
    with pytest.raises(ValueError, match="score must be"):
        top_k_gating_compact(jnp.asarray(logits), k, score="tanh")


# --- the configuration's file ---------------------------------------------- #
def test_the_configuration_file_is_the_catalogs_with_the_cut_laid_over():
    """Every published key stands at the top level with the serve role's cut
    laid over it, no width is cut, and the family builds the program's
    configuration from it: the router 128 wide, 16 experts held, one whole
    period of 3 window layers and 1 full."""
    run = {**CONFIG["published"], **CONFIG["roles"]["serve"]["model"]}
    assert {k: CONFIG[k] for k in run} == run
    assert set(CONFIG["roles"]["serve"]["model"]) == set(CONFIG["reduced"])
    assert not set(CONFIG["reduced"]) & set(manifest.WIDTH_KEYS)
    for key, value in (("hidden_size", 4096), ("num_attention_heads", 128),
                       ("num_key_value_heads", 8), ("head_dim", 128),
                       ("intermediate_size", 4096), ("num_local_experts", 128),
                       ("num_experts_per_tok", 8), ("num_shared_experts", 4),
                       ("sliding_window", 4096), ("rope_theta", 50000),
                       ("vocab_size", 262144)):
        assert run[key] == value
    cfg = family.build_cfg(run, **CONFIG["roles"]["serve"]["program_options"])
    assert cfg.experts_held == (0, 16) and cfg.num_experts == 128
    assert cfg.resolved_layer_types() == ("sliding_attention",) * 3 \
        + ("full_attention",)
    assert cohere2_moe.window_kinds(cfg) == {"window": 4096}
    assert _paged.stack_plan(cfg.resolved_layer_types())[:2] == (1, 4)
