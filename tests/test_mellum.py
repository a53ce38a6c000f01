"""Mellum2-12B-A2.5B through ``models/mellum.py`` (ISSUE 61) against the
benchmark's plain reference (``benchmark/reference/mellum.py``) at toy widths
on the CPU, float32 and seeded: window and full layers in one SEQUENTIAL
stack, a rope table a kind (plain and YaRN), contexts inside, across and
several times a toy window, along every path (the full forward, the dense
cache, chunked prefill then decode through BOTH paged pools with the window
kind's blocks given back, a mixed call, and ``ServingScheduler.tick`` with
short and long sequences live together), each deliberately wrong variant,
the rope tables against the formula at the published widths, what the family
refuses, the configuration's file - and the lift that made room for it:
``models/cohere2_moe.py``'s programs and ``ops/rotary.py``'s YaRN tables are
the parent's, to the letter and to the bit.
"""

import dataclasses
import functools
import hashlib
import json
import math
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import mellum as family
from benchmark.harness import manifest
from benchmark.reference import mellum as reference
from benchmark.reference import mellum_variants as variants
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference.engine_v2 import KVKindError, build_engine_v2
from deepspeed_tpu.inference.ragged import StateManager, WindowKind
from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                             ServingScheduler)
from deepspeed_tpu.models import axk1, cohere2_moe, mellum
from deepspeed_tpu.models._paged import MixedCall

CONFIG = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "configs", family.CONFIG_FILE))
WINDOW = 16
TINY = {**CONFIG["published"], **CONFIG["rehearsal"]["published"],
        "sliding_window": WINDOW, "num_hidden_layers": 4,
        "max_position_embeddings": 128}
PROMPT, CHUNK, STEPS, BLOCK = 53, 8, 6, 4    # contexts to 3.6 x the window
PATHS = ("apply", "apply_cached", "apply_paged")
# float32 on both sides, the sums in another order: 1e-5 of unit logits is
# what the two forms differ by, ten times that is the limit
TOL = 1e-4


def build():
    """The configuration, seeded random weights (the norms' too, which
    ``init`` leaves flat) and a row of tokens, prompt and answer."""
    cfg = family.build_cfg(TINY)
    params = family.init(cfg, jax.random.PRNGKey(0))
    for i, name in enumerate(("attn_norm", "mlp_norm")):
        w = params["layers"][name]
        params["layers"][name] = w * (1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(10 + i), w.shape))
    row = np.random.default_rng(0).integers(0, 256, PROMPT + STEPS)
    return cfg, params, row


def pieces(row):
    cuts = list(range(0, PROMPT, CHUNK)) + list(range(PROMPT, len(row)))
    return [(a, row[a:b]) for a, b in zip(cuts, cuts[1:] + [len(row)])]


def manager(cfg, slots):
    """A ``StateManager`` as the engine builds it for ``cfg`` at the tests'
    block and chunk sizes."""
    kinds = [WindowKind.sized(name, window, slots, CHUNK, BLOCK)
             for name, window in mellum.window_kinds(cfg).items()]
    return StateManager(slots, 40, BLOCK, cfg.max_seq_len // BLOCK,
                        window_kinds=kinds)


def program_logits(path, cfg, params, row):
    """Logits ``[len(row), vocab]`` of the program along ``path``."""
    f32 = jnp.float32
    if path == "apply":
        return mellum.apply(cfg, params, jnp.asarray(row[None]),
                            compute_dtype=f32)[0]
    out = []
    if path == "apply_cached":
        cached = jax.jit(functools.partial(mellum.apply_cached, cfg,
                                           compute_dtype=f32))
        cache = mellum.init_cache(cfg, 1, 64, dtype=f32)
        for start, piece in pieces(row):
            logits, cache = cached(params, jnp.asarray(piece[None]), cache,
                                   jnp.asarray([start], jnp.int32))
            out.append(logits[0])
        return jnp.concatenate(out)
    # both pools through a manager's tables: the window kind's blocks are
    # given back on the way, its segment short, its lengths shifted - and
    # rope still turns by the TRUE positions
    paged = jax.jit(functools.partial(mellum.apply_paged, cfg,
                                      compute_dtype=f32))
    state = manager(cfg, slots=1)
    kind, = state.window_kinds
    cache = mellum.init_paged_cache(
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


def gap(a, b):
    return float(jnp.abs(jnp.asarray(a) - jnp.asarray(b)).max())


@pytest.fixture(scope="module")
def f32():
    cfg, params, row = build()
    return cfg, params, row, reference.logits(TINY, family.Weights(params),
                                              row)


@pytest.mark.parametrize("path", PATHS)
def test_program_agrees_with_the_plain_reference_in_float32(f32, path,
                                                            one_device):
    """Every position of a 59-token row - contexts of 1-16 are inside the
    window, 17-59 up to 3.6 times it, and YaRN's table (original length 64,
    factor 4) differs from the plain one from position 1 on - along each
    path, LOGITS and not tokens."""
    cfg, params, row, want = f32
    with jax.default_matmul_precision("highest"):
        got = program_logits(path, cfg, params, row)
    assert got.shape == want.shape and gap(got, want) < TOL


@pytest.mark.parametrize("variant", variants.NAMES)
def test_each_wrong_variant_fails_the_tolerance(f32, variant):
    """A window on the full layers, none on the window layers, either rope
    table on the other kind of layer, ``attention_factor`` left out,
    adjacent-pair rope, a window one token off either way, gates not
    normalised and a per-head q/k norm each lie a hundred times beyond what
    the program, along every path, is held to."""
    _, params, row, want = f32
    wrong = variants.logits(variant, TINY, family.Weights(params), row)
    assert gap(wrong, want) > 100 * TOL


# --- the served path -------------------------------------------------------- #
def serve(cfg, params, prompts, steps):
    """``prompts`` through ``ServingScheduler.tick`` of a float32 engine;
    returns the engine, each request's tokens, the blocks the window kind
    gave back and the window kind's free count before and after."""
    # (the engine's pools and its forward are bfloat16 whatever its
    # ``dtype``: the family's defaults. Float32 both here, so that a served
    # token is held to float32's noise and no router's near-tie is flipped
    # by a rounded row)
    module = types.SimpleNamespace(**{
        **vars(family.module()),
        "init_paged_cache": functools.partial(mellum.init_paged_cache,
                                              dtype=jnp.float32),
        "apply_paged": functools.partial(mellum.apply_paged,
                                         compute_dtype=jnp.float32)})
    eng = build_engine_v2(module, cfg, params, config={
        "dtype": "float32", "prefill_bucket": 8, "split_prefill_chunk": CHUNK,
        "trace": {"enabled": True},
        "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                   "memory_config_blocks": 140, "block_size": BLOCK}})
    sched = ServingScheduler(eng, SchedulerConfig(max_admissions_per_tick=1))
    handles = [sched.submit(Request(prompt=list(p), max_new_tokens=steps))
               for p in prompts]
    released, low = 0, None
    free = lambda: eng.state.window_allocators["window"].free_blocks
    with jax.default_matmul_precision("highest"):
        for _ in range(400):
            if not sched.pending:
                break
            sched.tick()
            eng.state.debug_check()
            released += sched.last_tick["window_blocks_released"]
            low = free() if low is None else min(low, free())
            for d in eng.state.seqs.values():     # never more than its share
                for kind in eng.state.window_kinds:
                    assert eng.state.window_held(d, kind) \
                        <= kind.blocks_per_seq
    assert not sched.pending
    return eng, [list(h.tokens) for h in handles], released, (low, free())


# (the prompts' lengths, the answers'): one sequence inside the window all
# its life; one that crosses it mid-decode; one several windows long; a short
# and a long one live in the same mixed calls beside two more
SERVED = {"inside_the_window": ((6,), 6), "crosses_mid_decode": ((12,), 10),
          "several_windows": ((75,), 6), "short_and_long": ((5, 90, 37, 9), 8)}


@pytest.mark.parametrize("case", sorted(SERVED))
def test_the_served_path_agrees_with_the_reference(f32, case, one_device):
    """Chunked prefill then paged decode through ``build_engine_v2`` +
    ``ServingScheduler`` against the reference's FULL forward: every served
    token is the top of the reference's logits (1e-3: float32's noise, where
    two logits tie), the window kind gives blocks back exactly where a
    sequence outgrows its window (its allocator's free count rises again),
    and ``decode_step`` says how many of its rows are past the window."""
    cfg, params, _, _ = f32
    lengths, steps = SERVED[case]
    rng = np.random.default_rng(sorted(SERVED).index(case))
    prompts = [rng.integers(0, 256, n).tolist() for n in lengths]
    eng, served, released, (low, free) = serve(cfg, params, prompts, steps)
    for prompt, toks in zip(prompts, served):
        seq = np.asarray(prompt + toks[:-1])
        want = reference.logits(TINY, family.Weights(params),
                                seq)[len(prompt) - 1:]
        gaps = want.max(-1) - want[np.arange(len(toks)), toks]
        assert len(toks) == steps and gaps.max() < 1e-3, gaps
    # a block is given back once its last token is a whole window behind
    # the row that reads: never for a sequence that ends inside the window
    longest = max(len(p) for p in prompts) + steps
    assert (released > 0) == (longest > WINDOW + BLOCK), (released, longest)
    assert released == eng.state.window_blocks_released
    assert free > low and free == eng.state.window_kinds[0].num_blocks - 1
    steps_args = [e["args"] for e in eng.tracer.events()
                  if e["ph"] == "X" and e["name"] == "decode_step"
                  and e["args"]["batch"]]
    past = [a["rows_past_window"] for a in steps_args]
    assert all(0 <= p <= a["batch"] for p, a in zip(past, steps_args))
    assert (max(past) > 0) == (longest > WINDOW)
    if case == "short_and_long":    # both regimes in ONE call
        assert any(0 < p < a["batch"] for p, a in zip(past, steps_args))
        assert eng.mixed_steps > 0 and eng.overlapped_steps > 0
        assert all(a["moe_rows_routed"] > 0 and a["kv_tokens_window"]
                   <= a["kv_tokens_full"] for a in steps_args)


def test_a_mixed_call_is_its_two_segments(f32, one_device):
    """One chunk's rows beside two decode rows in ONE call of
    ``apply_paged``, each kind's table its own segment of a manager's
    tables: every row's logits are the reference's - the chunk's and the
    decode rows' (one context inside the window, one past it: its window
    segment starts at an offset, and its rope does not)."""
    cfg, params, row, want = f32
    rng = np.random.default_rng(5)
    others = [rng.integers(0, 256, n) for n in (13, 27)]
    wants = [reference.logits(TINY, family.Weights(params), o)
             for o in others]
    f = jnp.float32
    paged = jax.jit(functools.partial(mellum.apply_paged, cfg,
                                      compute_dtype=f))
    state = manager(cfg, slots=4)
    kind, = state.window_kinds
    cache = mellum.init_paged_cache(
        cfg, 40, BLOCK, dtype=f, window_blocks={"window": kind.num_blocks})
    descs = [state.admit(i, 32) for i in range(3)]

    def table(desc, n):
        state.extend(desc, n)
        return state.block_table(desc)

    def chunks(desc, tokens):
        nonlocal cache
        for start in range(0, len(tokens), CHUNK):
            piece = tokens[start:start + CHUNK]
            pad = np.zeros((1, CHUNK), np.int32)
            pad[0, :len(piece)] = piece
            _, cache = paged(
                params, jnp.asarray(pad), cache,
                jnp.asarray(table(desc, len(piece))[None]),
                jnp.asarray([start], jnp.int32),
                valid=jnp.arange(CHUNK)[None] < len(piece))
            desc.seen_tokens = start + len(piece)

    with jax.default_matmul_precision("highest"):
        for d, o in zip(descs, others):      # the decode rows' contexts
            chunks(d, o[:-1])
        chunks(descs[2], row[:16])           # the chunk's first 16 tokens
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


# --- the cell's own comparison, as the window runs it ----------------------- #
ROLE = {"program_options": {}, "weights_dtype": "float32",
        "engine": {"split_prefill_chunk": CHUNK,
                   "ragged": {"block_size": BLOCK,
                              "max_tracked_sequences": 4}},
        "held": {"why": "float32 on both sides",
                 **{key: TOL for key, _, _ in reference.HELD}}}


@pytest.mark.parametrize("name", ["right", "negated_wo"])
def test_a_probe_is_held_through_the_mixed_program(f32, name, capsys,
                                                   one_device):
    """``logits_and_margin`` with the family's weights: the probe goes
    through ``families/mixed_program.py`` (every call a mixed call over four
    slots, neighbours live, ONE table a slot for both kinds), its reading is
    a line of the output, and a program that is not the reference's (its
    output projection negated) raises by name."""
    _, params, row, want = f32
    # (a sequence beside the judged one holds at most four chunks of
    # context, ``mixed_program.SPAN_CHUNKS``: 36 tokens, 18 of them decoded)
    row, want = row[:36], want[:36]
    weights = family.Weights(params, role=ROLE)
    if name == "right":
        with jax.default_matmul_precision("highest"):
            got, margin = reference.logits_and_margin(TINY, weights, row)
        assert gap(got, want) < TOL
        # a routing margin a position (Mixtral's rule, not the flat one)
        assert margin.shape == (36,) and bool((margin > 0).all())
        line = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert line["phase"] == "held" and line["why_not"] == []
        assert line["decode_rows"] == reference.decode_rows(len(row))
        return
    layers = {**params["layers"], "wo": -params["layers"]["wo"]}
    weights.program.params = {**params, "layers": layers}
    with jax.default_matmul_precision("highest"), \
            pytest.raises(reference.Disagreement, match="logits lie"):
        reference.logits_and_margin(TINY, weights, row)


# --- the two rope tables, at the PUBLISHED widths ---------------------------- #
PUBLISHED = {**CONFIG["published"], **CONFIG["roles"]["serve"]["model"]}
POSITIONS = (0, 1, 8191, 8192, 32767)


def test_yarns_ramp_runs_between_the_formulas_dimensions():
    """``low`` and ``high`` by hand: 128 ln(8192 / (32 * 2 pi)) / (2 ln
    500000) = 18.08 and 128 ln(8192 / (2 pi)) / (2 ln 500000) = 34.98, so
    the ramp runs over dimensions 18 .. 35; and ``attention_factor`` is 0.1
    ln 16 + 1."""
    assert reference.yarn_corrections(PUBLISHED) == (18, 35)
    factor = PUBLISHED["rope_parameters"]["full_attention"][
        "attention_factor"]
    assert factor == pytest.approx(0.1 * math.log(16) + 1, abs=1e-12)
    assert family.build_cfg(PUBLISHED).attention_factor == factor


@pytest.mark.parametrize("position", POSITIONS)
def test_the_rope_tables_are_the_formulas(position):
    """The program's two tables (``mellum._rope``) at one position against
    the formula in float64: the plain table ``cos(p f_j)``, YaRN's
    ``1.27726 cos(p f'_j)`` with ``f'`` the ramp's blend. The tables are
    float32 and so is the angle they are made from: at p = 32767 an angle of
    up to 3e4 rad carries 2e-3 rad of rounding, times the factor."""
    cfg = family.build_cfg(PUBLISHED)
    tables = mellum._rope(cfg)
    j = np.arange(64, dtype=np.float64)
    f = 500000.0 ** (-2 * j / 128)
    r = np.clip((j - 18) / (35 - 18), 0, 1)
    want = {"sliding_attention": (f, 1.0),
            "full_attention": (f / 16 * r + f * (1 - r), cfg.attention_factor)}
    for layer_type, (freq, scale) in want.items():
        cos, sin = (np.asarray(t[position], np.float64)
                    for t in tables[layer_type])
        assert np.abs(cos - scale * np.cos(position * freq)).max() < 5e-3
        assert np.abs(sin - scale * np.sin(position * freq)).max() < 5e-3
        # and the reference's own table, made without ``ops/rotary.py``
        inv, ref_scale = reference.rope_table(PUBLISHED, layer_type)
        assert ref_scale == scale
        np.testing.assert_allclose(inv, freq, rtol=1e-6)
    if position:    # the two kinds do turn differently
        assert not np.allclose(tables["full_attention"][0][position],
                               tables["sliding_attention"][0][position])


# --- refusals, each by name ------------------------------------------------- #
CHANGED = [(key, {"model_type": "llama", "hidden_act": "gelu",
                  "max_window_layers": 4}.get(key, not value))
           for key, value, _ in family.PUBLISHED_AS]


@pytest.mark.parametrize("key,value", CHANGED + [
    ("mlp_layer_types", ["dense"] + ["sparse"] * 27),
    ("rope_parameters", {**PUBLISHED["rope_parameters"], "full_attention": {
        **PUBLISHED["rope_parameters"]["full_attention"],
        "rope_type": "default"}})])
def test_build_cfg_refuses_a_changed_published_key_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        family.build_cfg({**PUBLISHED, key: value})


@pytest.mark.parametrize("feature", [
    {"prefix_cache": {"enabled": True}},
    {"speculative": {"enabled": True}},
    {"kv_quant": {"enabled": True}}])
def test_the_engine_refuses_what_window_kinds_refuse(feature):
    """A row of ``_REFUSALS["window_kinds"]`` is raised for this family too:
    it declares ``window_kinds`` and nothing else says so."""
    cfg, params, _ = build()
    mesh_lib.set_mesh(None)
    name = "inference." + next(iter(feature))
    with pytest.raises(KVKindError, match=name):
        build_engine_v2(family.module(), cfg, params, config={
            "dtype": "float32", "split_prefill_chunk": CHUNK,
            "ragged": {"max_tracked_sequences": 2, "block_size": BLOCK,
                       "memory_config_blocks": 40}, **feature})


def test_the_family_declares_what_the_engine_reads():
    cfg = family.build_cfg(PUBLISHED)
    assert mellum.window_kinds(cfg) == {"window": 1024}
    assert cfg.resolved_layer_types() == (("sliding_attention",) * 3
                                          + ("full_attention",)) * 2
    assert cfg.intermediate_size == 896 and cfg.experts_held is None
    assert mellum.FLOAT32_PARAMS == ("router",)
    assert mellum.moe_rows(cfg, 529)["moe_rows_routed"] == 529 * 8
    assert not hasattr(mellum, "loss_fn") and not hasattr(mellum,
                                                          "model_spec")
    shapes = jax.eval_shape(lambda k: mellum.init(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    assert shapes["layers"]["moe"]["router"].dtype == jnp.float32
    assert shapes["layers"]["moe"]["w_up"].shape == (8, 64, 2304, 896)
    assert shapes["lm_head"].shape == (2304, 98304)
    kind = WindowKind.sized("window", 1024, 32, 512, 32)
    assert (kind.blocks_per_seq, kind.num_blocks) == (49, 1569)


# --- the configuration's file ------------------------------------------------ #
def test_the_configuration_file_is_the_catalogs_with_the_cut_laid_over():
    """Every published key of the catalog's ``config`` is under
    ``published`` unchanged, the as-run keys at the top level are those with
    the serve role's cut laid over them, ``reduced`` names the cut and no
    width, and the role pins no ``decode_quantum``."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            entry = next(e for e in map(json.loads, f)
                         if e["name"] == "Mellum2-12B-A2.5B-Instruct")
        assert CONFIG["source"] == entry["source_url"]
        assert {k: CONFIG["published"][k] for k in entry["config"]} \
            == entry["config"]
    assert set(CONFIG["published"]) - {"num_local_experts"} \
        == set(PUBLISHED) - {"num_local_experts"}
    assert CONFIG["reduced"] == ["num_hidden_layers",
                                 "max_position_embeddings"]
    role = CONFIG["roles"]["serve"]
    assert role["model"] == {"num_hidden_layers": 8,
                             "max_position_embeddings": 32768}
    assert {k: CONFIG[k] for k in PUBLISHED} == PUBLISHED
    assert not set(role["model"]) & set(manifest.WIDTH_KEYS)
    assert "decode_quantum" not in json.dumps(role)
    assert role["scheduler"] == {"max_admissions_per_tick": 1}
    for item in ("block", "rope", "yarn", "qk_norm", "window", "router",
                 "intermediate_size", "num_local_experts", "mtp_head",
                 "tokens", "deployment_tables", "weights", "served_tokens"):
        assert CONFIG["assumed"][item]
    assert set(role["held"]) == {"why"} | {k for k, _, _ in reference.HELD}


# --- the lift: command-a's programs and a.x-k1's tables are the parent's ----- #
def _text(fn, *args) -> str:
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))


def command_a_texts():
    """The jaxpr of each program of ``models/cohere2_moe.py``, on shapes
    alone: the full forward and its gradient, the dense cache, and
    ``apply_paged`` over one table, over a manager's two segments (a chunk
    and a decode) and as a mixed call."""
    m = cohere2_moe
    cfg = m.Cohere2MoeConfig.tiny(drop_tokens=False)
    s, i32 = jax.ShapeDtypeStruct, jnp.int32
    params = jax.eval_shape(lambda k: m.init(cfg, k), jax.random.PRNGKey(0))
    tokens, one, lens = s((2, 8), i32), s((2, 1), i32), s((2,), i32)
    width = 128 // 4
    table, kinds = s((2, width), i32), s((2, width + 1 + 7), i32)
    dense = jax.eval_shape(lambda: m.init_cache(cfg, 2, 32))
    paged = jax.eval_shape(lambda: m.init_paged_cache(
        cfg, 16, 4, window_blocks={"window": 12}))
    paged_fn = lambda p, t, c, b, n: m.apply_paged(cfg, p, t, c, b, n)

    def mixed(p, t, c, tables, lens, active, ctab, ctx, nv, rows):
        call = MixedCall(tables, lens, active, ctab, ctx, nv)
        return m.apply_paged(cfg, p, t, c, call, None,
                             valid=call.valid(t.shape[1]), rows=rows)

    grad = jax.grad(lambda p, t: m.loss_fn(cfg, p, {"tokens": t})[0])
    return {
        "apply": _text(lambda p, t: m.apply(cfg, p, t), params, tokens),
        "loss_grad": _text(grad, params, tokens),
        "apply_cached": _text(
            lambda p, t, c, n: m.apply_cached(cfg, p, t, c, n),
            params, tokens, dense, lens),
        "apply_paged": _text(paged_fn, params, tokens, paged, table, lens),
        "apply_paged_kinds": _text(paged_fn, params, tokens, paged, kinds,
                                   lens),
        "apply_paged_decode": _text(paged_fn, params, one, paged, kinds,
                                    lens),
        "apply_paged_mixed": _text(
            mixed, params, s((1, 4 + 8), i32), paged,
            s((4, width + 8), i32), s((4,), i32), s((4,), bool),
            s((width + 8,), i32), s((), i32), s((), i32), s((1, 5), i32)),
    }


# sha256 of each, taken on ISSUE 61's parent (696faff) under a one-device
# process mesh: that PR moved the family's scan nest, its kinds and its
# per-kind attention step into ``models/_paged.py`` and meant to change no
# program. A PR that means to change one replaces its line.
COMMAND_A_PARENT = {
    "apply": "de495bb1920b9868",
    "loss_grad": "eaf8de5258ed8ae8",
    "apply_cached": "c6a4120f0a0cacfc",
    "apply_paged": "ffcc2c3e4e6b4d5b",
    "apply_paged_kinds": "30fef86750ad648a",
    "apply_paged_decode": "76b66711753ec598",
    "apply_paged_mixed": "f4429f479d1032cb",
}


@pytest.fixture(scope="module")
def command_a_hashes():
    before = mesh_lib._global_mesh
    mesh_lib.set_mesh(None)
    mesh_lib.init_mesh({"data": 1}, devices=jax.devices()[:1])
    try:
        return {name: hashlib.sha256(text.encode()).hexdigest()[:16]
                for name, text in command_a_texts().items()}
    finally:
        mesh_lib.set_mesh(before)


@pytest.mark.parametrize("program", sorted(COMMAND_A_PARENT))
def test_command_as_program_is_the_parents(command_a_hashes, program):
    assert command_a_hashes[program] == COMMAND_A_PARENT[program]


# sha256 of a.x-k1's cos and sin tables' bytes on the same parent:
# ``yarn_frequencies`` serves that family's ``table_scale`` (the quotient of
# two mscales) and this one's ``attention_factor`` by the one argument
AXK1_PARENT_TABLES = {"tiny": "f246746b140350e6",
                      "published_32k": "197edc7133a4d79c"}


@pytest.mark.parametrize("size", sorted(AXK1_PARENT_TABLES))
def test_axk1s_rope_tables_are_the_parents_bit_for_bit(size):
    cfg = axk1.AxK1Config.tiny() if size == "tiny" else dataclasses.replace(
        axk1.AxK1Config(), max_seq_len=32768)
    cos, sin = axk1._rope(cfg)
    digest = hashlib.sha256(np.asarray(cos).tobytes()
                            + np.asarray(sin).tobytes()).hexdigest()[:16]
    assert digest == AXK1_PARENT_TABLES[size]
