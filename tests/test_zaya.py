"""ZAYA1-8B through ``models/zaya.py`` (ISSUE 64) against the benchmark's
plain reference (``benchmark/reference/zaya.py``) at toy widths on the CPU,
float32 (and bfloat16 along the two main paths) and seeded: attention in a
convolved latent whose TAIL lies on the slot pool beside the paged keys and
values of every layer, a top-1 bank behind an MLP router whose state rides
the layer scan, a skip output, a scaled residual path - along every path
(the full forward; chunked prefill then decode through pages and tails, the
prompt cut at every offset around a block's edge; a mixed call; a slot's
row reused; ``ServingScheduler.tick``), each deliberately wrong variant,
what the family refuses, what its spans and counters say, the
configuration's file - and what was lifted to make room for it:
``_state.short_conv``'s plain form and ``MoELayer``'s seam for a family's
own logits change no program of the eight families that had them.
"""

import dataclasses
import functools
import hashlib
import importlib
import json
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import zaya as family
from benchmark.harness import costs, costs_cca, manifest
from benchmark.reference import zaya as reference
from benchmark.reference import zaya_variants as variants
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference.engine_v2 import (_REFUSALS,
                                               RecurrentStateError,
                                               build_engine_v2)
from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                             ServingScheduler)
from deepspeed_tpu.models import zaya
from deepspeed_tpu.models._paged import MixedCall
from deepspeed_tpu.moe.layer import MoELayer
from deepspeed_tpu.telemetry import schema
from deepspeed_tpu.utils.tree import cast_floating

CONFIG = manifest.load_json(os.path.join(
    manifest.BENCH_DIR, "configs", family.CONFIG_FILE))
TINY = {**CONFIG["published"], **CONFIG["rehearsal"]["published"],
        "num_hidden_layers": 3, "max_position_embeddings": 128}
PROMPT, CHUNK, STEPS, BLOCK = 29, 8, 6, 4
# float32 on both sides, the sums in another order: 1e-5 of unit logits is
# what the two forms differ by, ten times that is the limit
TOL = 1e-4
F32 = jnp.float32


def build():
    """The configuration, seeded random weights (the norms' and the choice
    bias too, which ``init`` leaves flat) and a row of tokens."""
    cfg = family.build_cfg(TINY)
    params = family.init(cfg, jax.random.PRNGKey(0))
    layers = params["layers"]
    for i, name in enumerate(("attn_norm", "mlp_norm")):
        layers[name] = layers[name] * (1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(10 + i), layers[name].shape))
    moe = layers["moe"]
    moe["router_bias"] = 0.02 * jax.random.normal(
        jax.random.PRNGKey(12), moe["router_bias"].shape)
    moe["router_norm"] = moe["router_norm"] * (1.0 + 0.2 * jax.random.normal(
        jax.random.PRNGKey(13), moe["router_norm"].shape))
    row = np.random.default_rng(0).integers(0, 256, PROMPT + STEPS)
    return cfg, params, row


def gap(a, b):
    return float(jnp.abs(jnp.asarray(a, F32) - jnp.asarray(b, F32)).max())


@functools.lru_cache(maxsize=None)
def paged_fn(cfg, dtype):
    return jax.jit(functools.partial(zaya.apply_paged, cfg,
                                     compute_dtype=jnp.dtype(dtype)))


def paged_logits(cfg, params, row, cuts, dtype="float32", slot=1, cache=None):
    """Logits ``[len(row), vocab]`` of ``apply_paged``: the row's pieces
    between ``cuts`` (a piece of one token is a decode call), through slot
    ``slot`` of a three-slot pool and blocks in no order."""
    paged = paged_fn(cfg, dtype)
    if cache is None:
        cache = zaya.init_paged_cache(cfg, 40, BLOCK, dtype=jnp.dtype(dtype),
                                      slots=3)
    table = jnp.asarray([[7, 3, 11, 2, 9, 5, 13, 4, 6, 8]], jnp.int32)
    out = []
    for a, b in zip(cuts, cuts[1:]):
        width = 1 if b - a == 1 else -(-(b - a) // CHUNK) * CHUNK
        padded = np.zeros((1, width), np.int32)
        padded[0, :b - a] = row[a:b]
        logits, cache = paged(
            params, jnp.asarray(padded), cache, table,
            jnp.asarray([a], jnp.int32),
            valid=jnp.arange(width)[None] < b - a,
            slots=jnp.asarray([slot], jnp.int32))
        out.append(logits[0, :b - a])
    return jnp.concatenate(out), cache


def cuts_of(row, chunk=CHUNK):
    return list(range(0, PROMPT, chunk)) + list(range(PROMPT, len(row) + 1))


@pytest.fixture(scope="module")
def f32():
    cfg, params, row = build()
    return cfg, params, row, reference.logits(TINY, family.Weights(params),
                                              row)


# --- program against reference ------------------------------------------------ #
@pytest.mark.parametrize("path", ["apply", "apply_paged"])
@pytest.mark.parametrize("dtype,tol", [("float32", TOL), ("bfloat16", 0.25)])
def test_program_agrees_with_the_plain_reference(f32, path, dtype, tol):
    """The full forward, and chunked prefill then decode through pages AND
    tails, against the reference's full forward pass, in logits. bfloat16:
    the same weights rounded, held to bf16's noise at these toy widths on
    the rows whose top-1 no rounding flips (the median row)."""
    cfg, params, row, want = f32
    if dtype == "bfloat16":
        params = cast_floating(params, jnp.bfloat16,
                               keep=zaya.FLOAT32_PARAMS)
        want = reference.logits(TINY, family.Weights(params), row)
    with jax.default_matmul_precision("highest"):
        if path == "apply":
            got = zaya.apply(cfg, params, jnp.asarray(row[None]),
                             compute_dtype=jnp.dtype(dtype))[0]
        else:
            got, _ = paged_logits(cfg, params, row, cuts_of(row), dtype)
    if dtype == "float32":
        assert gap(got, want) < tol
    else:
        rows = np.abs(np.asarray(got, np.float32) - want).mean(-1)
        assert float(np.median(rows)) < tol / 5 and np.isfinite(rows).all()


@pytest.mark.parametrize("variant", variants.NAMES)
def test_each_wrong_variant_fails_the_tolerance(f32, variant):
    """No convolution, a depthwise second one, no q-k mean, no value shift,
    a tail dropped or rounded between calls, no L2 norm, no ``tau``, rope
    over the whole head, a router state not carried, a bf16 router, a gate
    renormalised, a skipped row through expert 0 and a residual scale left
    out each lie a hundred times beyond what the program is held to."""
    _, params, row, want = f32
    starts = reference.call_starts(len(row), STEPS, CHUNK)
    wrong = variants.logits(variant, TINY, family.Weights(params), row,
                            starts=starts)
    assert gap(wrong, want) > 100 * TOL


def test_the_router_state_rides_the_scan_as_the_unrolled_layers_carry_it(f32):
    """The reference is the layers one after another in Python, ``z`` handed
    from each to the next; the program's scan carries it. They agree (the
    paths above), a router fed by its own layer alone does not, and layer
    0's carry scale meets zeros (any value of it gives the same logits)."""
    cfg, params, row, want = f32
    moe = params["layers"]["moe"]
    other = {**params, "layers": {**params["layers"], "moe": {
        **moe, "router_carry": moe["router_carry"].at[0].set(7.0)}}}
    got = zaya.apply(cfg, other, jnp.asarray(row[None]), compute_dtype=F32)
    assert gap(got[0], want) < TOL
    alone = variants.logits("router_not_carried", TINY,
                            family.Weights(params), row)
    assert gap(alone, want) > 100 * TOL


def test_the_cells_rule_balances_the_routers_by_the_reference(f32):
    """``families/zaya.py _balance`` (the choice bias of the cell's random
    weights, set by the plain reference's layers, not the program's): under
    it no output of any layer takes more than a few times its share of
    OTHER sequences' rows, the skip included (but the LAST layer's, which
    the rule never chooses); with the bias taken out again the same weights
    send a layer's rows to fewer outputs."""
    cfg = f32[0]
    drawn = family.init(cfg, jax.random.PRNGKey(3))
    bias = drawn["layers"]["moe"]["router_bias"]
    assert bias.shape == (cfg.num_layers, cfg.num_experts + 1)
    flat = {**drawn, "layers": {**drawn["layers"], "moe": {
        **drawn["layers"]["moe"], "router_bias": jnp.zeros_like(bias)}}}

    def load(p):
        total = np.zeros(bias.shape)
        for seed in range(4):
            counts = []
            reference.logits(TINY, family.Weights(p), np.random.default_rng(
                seed).integers(0, 256, 128), counts=counts)
            total += np.stack(counts)
        return total / total.sum(1, keepdims=True)

    balanced, unbalanced = load(drawn), load(flat)
    assert balanced[-1, -1] == 0 and float(bias[-1, -1]) == family.NO_SKIP
    balanced[-1, -1] = balanced[-1, :-1].mean()
    assert balanced.min() > 0.02 and balanced.max() < 0.5, balanced
    assert balanced.max(1).mean() < unbalanced.max(1).mean()


# --- the tail across calls ------------------------------------------------------ #
@pytest.mark.parametrize("edge", [4, 8, 16])
@pytest.mark.parametrize("offset", [-3, -2, -1, 0, 1, 2, 3])
def test_a_prompt_cut_anywhere_around_a_blocks_edge_is_the_one_shot_prefill(
        f32, edge, offset):
    """The prompt in two calls, cut 1..3 tokens before, at and after a block
    edge: the second call's first rows take ``p_(t-2)``, ``p_(t-1)`` and the
    value's half from the pool, and every row is the one-shot prefill's."""
    cfg, params, row, want = f32
    cut = edge + offset
    with jax.default_matmul_precision("highest"):
        got, _ = paged_logits(cfg, params, row[:PROMPT], [0, cut, PROMPT])
    assert gap(got, want[:PROMPT]) < TOL


def test_a_fresh_slot_reusing_a_finished_slots_row_starts_from_zeros(f32):
    """Another sequence through the SAME slot and blocks after the first has
    ended: its first call has context 0 and reads zeros, not the row the
    first sequence left there."""
    cfg, params, row, want = f32
    other = np.random.default_rng(3).integers(0, 256, len(row))
    with jax.default_matmul_precision("highest"):
        _, cache = paged_logits(cfg, params, other, cuts_of(other))
        assert float(jnp.abs(cache["tail"][:, 1]).max()) > 0
        got, cache = paged_logits(cfg, params, row, cuts_of(row), cache=cache)
    assert gap(got, want) < TOL
    # (slots 0 and 2 and the trash row never written but by padding)
    assert float(jnp.abs(cache["tail"][:, 0]).max()) == 0.0


def test_a_mixed_call_is_its_two_segments(f32, one_device):
    """One chunk's rows beside two decode rows in ONE call of
    ``apply_paged``: every row's logits are the reference's - each decode
    row's tail from its own slot's row, the chunk's (at a context offset)
    from its own - and the pools it leaves are those of the two calls run
    apart."""
    cfg, params, row, want = f32
    rng = np.random.default_rng(5)
    others = [rng.integers(0, 256, n) for n in (13, 22)]
    wants = [reference.logits(TINY, family.Weights(params), o)
             for o in others]
    paged = paged_fn(cfg, "float32")
    cache = zaya.init_paged_cache(cfg, 40, BLOCK, dtype=F32, slots=4)
    tables = np.zeros((4, 32), np.int32)
    tables[0, :8], tables[1, :8], tables[3, :8] = (
        np.arange(1, 9), np.arange(9, 17), np.arange(17, 25))

    def prefill(slot, tokens, cache):
        for start in range(0, len(tokens), CHUNK):
            piece = tokens[start:start + CHUNK]
            pad = np.zeros((1, CHUNK), np.int32)
            pad[0, :len(piece)] = piece
            _, cache = paged(
                params, jnp.asarray(pad), cache,
                jnp.asarray(tables[slot][None]),
                jnp.asarray([start], jnp.int32),
                valid=jnp.arange(CHUNK)[None] < len(piece),
                slots=jnp.asarray([slot], jnp.int32))
        return cache

    with jax.default_matmul_precision("highest"):
        for slot, o in zip((0, 1), others):
            cache = prefill(slot, o[:-1], cache)
        cache = prefill(3, row[:16], cache)
        apart = jax.tree.map(jnp.copy, cache)
        call = MixedCall(
            tables=jnp.asarray(tables), lens=jnp.asarray([12, 21, 0, 0]),
            active=jnp.asarray([True, True, False, False]),
            chunk_table=jnp.asarray(tables[3]), chunk_ctx=jnp.asarray(16),
            chunk_valid=jnp.asarray(5), chunk_slot=jnp.asarray(3))
        tokens = np.zeros((1, 4 + 8), np.int32)
        tokens[0, 0], tokens[0, 1] = others[0][-1], others[1][-1]
        tokens[0, 4:9] = row[16:21]
        got, cache = paged(params, jnp.asarray(tokens), cache, call, None,
                           valid=call.valid(12))
        # the same two segments as two calls
        pad = np.zeros((1, CHUNK), np.int32)
        pad[0, :5] = row[16:21]
        _, apart = paged(params, jnp.asarray(pad), apart,
                         jnp.asarray(tables[3][None]),
                         jnp.asarray([16], jnp.int32),
                         valid=jnp.arange(CHUNK)[None] < 5,
                         slots=jnp.asarray([3], jnp.int32))
        _, apart = paged(params, jnp.asarray(tokens[:, :4].T), apart,
                         jnp.asarray(tables), jnp.asarray([12, 21, 0, 0]),
                         valid=jnp.asarray([[True], [True], [False],
                                            [False]]))
    assert gap(got[0, 0], wants[0][-1]) < TOL
    assert gap(got[0, 1], wants[1][-1]) < TOL
    assert gap(got[0, 4:9], want[16:21]) < TOL
    # the slots' rows (not the trash row, which padding may write) and the
    # blocks the sequences hold
    assert gap(cache["tail"][:, :4], apart["tail"][:, :4]) < 1e-5
    for name in ("k", "v"):
        assert gap(cache[name][:, 1:25], apart[name][:, 1:25]) < 1e-5


# --- the skip -------------------------------------------------------------------- #
@pytest.mark.parametrize("form", ["grouped", "slabs"])
def test_a_skipped_row_adds_nothing_and_is_counted_once(form, request):
    """``MoELayer(E + 1, top_k=1, held=(0, E))`` under a family's own logits:
    the sum is each row's ONE expert under its softmax gate (not
    renormalised), a row whose top-1 is the last output gets exactly zero -
    no place and no tile in the grouped form, no column in the slabs - and
    the expert that would be index ``E`` is never read (the bank has none)."""
    if form == "grouped":
        request.getfixturevalue("one_device")
    E, h, inter, T = 4, 16, 8, 24
    layer = MoELayer(E + 1, 1, drop_tokens=False, norm_topk=False,
                     held=(0, E))
    assert layer.grouped() == (form == "grouped")
    keys = jax.random.split(jax.random.PRNGKey(2), 5)
    bank = {"w_gate": jax.random.normal(keys[0], (2, E, h, inter)) * 0.3,
            "w_up": jax.random.normal(keys[1], (2, E, h, inter)) * 0.3,
            "w_down": jax.random.normal(keys[2], (2, E, inter, h)) * 0.3}
    x = jax.random.normal(keys[3], (1, T, h))
    logits = 2.0 * jax.random.normal(keys[4], (T, E + 1))
    out, _ = layer(bank, x, layer=1, logits=logits)
    P = jax.nn.softmax(logits, axis=-1)
    top = np.asarray(jnp.argmax(P, axis=-1))
    skipped = top == E
    assert 0 < skipped.sum() < T
    want = np.zeros((T, h), np.float32)
    for t in np.flatnonzero(~skipped):
        e = top[t]
        want[t] = float(P[t, e]) * np.asarray(
            (jax.nn.silu(x[0, t] @ bank["w_gate"][1, e])
             * (x[0, t] @ bank["w_up"][1, e])) @ bank["w_down"][1, e])
    assert float(jnp.abs(out[0][skipped]).max()) == 0.0
    assert gap(out[0], want) < 1e-5
    # the reference's sum over the same rows, the skip counted once
    counts = []
    cfg, params, row = build()
    reference.logits(TINY, family.Weights(params), row, counts=counts)
    assert all(int(c.sum()) == len(row) and len(c) == cfg.num_experts + 1
               for c in counts) and sum(int(c[-1]) for c in counts) > 0


def test_the_seam_takes_a_familys_logits_and_the_routers_are_what_they_were():
    """``logits=None`` computes ``tokens @ router`` as before (float32 rows
    under a float32 router); the same logits handed in give the same sum."""
    E, h = 4, 16
    layer = MoELayer(E, 2, drop_tokens=False)
    keys = jax.random.split(jax.random.PRNGKey(4), 5)
    params = {"router": jax.random.normal(keys[0], (h, E)),
              "w_gate": jax.random.normal(keys[1], (E, h, 8)),
              "w_up": jax.random.normal(keys[2], (E, h, 8)),
              "w_down": jax.random.normal(keys[3], (E, 8, h))}
    x = jax.random.normal(keys[4], (2, 6, h))
    own, _ = layer(params, x)
    given, _ = layer({k: v for k, v in params.items() if k != "router"}, x,
                     logits=x.reshape(-1, h) @ params["router"])
    assert gap(own, given) == 0.0


# --- the served path --------------------------------------------------------------- #
ENGINE = {"dtype": "float32", "prefill_bucket": 8,
          "split_prefill_chunk": CHUNK, "trace": {"enabled": True},
          "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                     "memory_config_blocks": 140, "block_size": BLOCK}}


def float32_module():
    # (the engine's pools and its forward are bfloat16 whatever its
    # ``dtype``: the family's defaults. Float32 both here, so that a served
    # token is held to float32's noise and no router's near-tie is flipped
    # by a rounded row)
    return types.SimpleNamespace(**{
        **vars(family.module()),
        "init_paged_cache": functools.partial(zaya.init_paged_cache,
                                              dtype=F32),
        "apply_paged": functools.partial(zaya.apply_paged,
                                         compute_dtype=F32)})


@pytest.fixture(scope="module")
def served():
    """Four prompts (one shorter than a chunk, one of several chunks)
    through ``ServingScheduler.tick`` of a float32 engine on a one-chip
    mesh: ``(engine, prompts, each request's tokens)``."""
    before = mesh_lib._global_mesh
    mesh_lib.set_mesh(None)
    mesh_lib.init_mesh({"data": 1}, devices=jax.devices()[:1])
    try:
        cfg, params, _ = build()
        eng = build_engine_v2(float32_module(), cfg, params, config=ENGINE)
        sched = ServingScheduler(eng,
                                 SchedulerConfig(max_admissions_per_tick=1))
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 256, n).tolist() for n in (5, 37, 21, 9)]
        handles = [sched.submit(Request(prompt=list(p), max_new_tokens=7))
                   for p in prompts]
        with jax.default_matmul_precision("highest"):
            for _ in range(300):
                if not sched.pending:
                    break
                sched.tick()
                eng.state.debug_check()
        assert not sched.pending
        yield eng, params, prompts, [list(h.tokens) for h in handles]
    finally:
        mesh_lib.set_mesh(before)


def test_the_served_path_agrees_with_the_reference(served):
    """Chunked prefill then paged decode through ``build_engine_v2`` +
    ``ServingScheduler`` (mixed calls, launched ahead) against the
    reference's FULL forward: every served token is the top of the
    reference's logits (1e-3: float32's noise, where two logits tie)."""
    eng, params, prompts, tokens = served
    for prompt, toks in zip(prompts, tokens):
        seq = np.asarray(prompt + toks[:-1])
        want = reference.logits(TINY, family.Weights(params),
                                seq)[len(prompt) - 1:]
        gaps = want.max(-1) - want[np.arange(len(toks)), toks]
        assert len(toks) == 7 and gaps.max() < 1e-3, gaps
    assert eng.mixed_steps > 0 and eng.overlapped_steps > 0


def test_the_spans_and_counters_say_the_rows(served):
    """``decode_step`` and ``prefill_chunk`` carry ``cca_rows`` /
    ``cca_tail_rows`` and, with ``prefill_batch``, ``moe_rows_skipped``
    beside ``moe_rows_routed`` / ``moe_rows_computed``, under registered
    span names; the engine's counters are the registered ones."""
    eng = served[0]
    spans = [e for e in eng.tracer.events() if e["ph"] == "X"]
    steps = [e["args"] for e in spans if e["name"] == "decode_step"]
    # (a chunk that rode a decode step leaves a lifecycle span of the name
    # with no rows on it)
    chunks = [e["args"] for e in spans if e["name"] == "prefill_chunk"
              and "rows" in e["args"]]
    assert steps and all(e["name"] in schema.TRACER_SPANS for e in spans)
    for a in steps:
        chunk = a.get("chunk_tokens", 0)
        assert a["cca_rows"] == a["batch"] + chunk
        assert a["cca_tail_rows"] == a["batch"] + (chunk > 0)
        assert a["moe_rows_routed"] + a["moe_rows_skipped"] == a["rows"]
        assert a["moe_rows_skipped"] == a["rows"] - a["rows"] * 4 // 5
        assert a["ssm_rows"] == a["batch"]
    assert any(a.get("chunk_tokens", 0) for a in steps)     # a mixed step
    for a in chunks:
        assert a["cca_tail_rows"] == 1 and a["cca_rows"] > 0
    batches = [e["args"] for e in spans if e["name"] == "prefill_batch"]
    assert batches and all(
        a["moe_rows_routed"] + a["moe_rows_skipped"] == a["rows"]
        for a in batches)
    events = dict((name, value) for name, value, _ in eng.engine_events())
    assert not schema.validate_events(eng.engine_events())
    for name in events:
        assert name in schema.SERVING_SERIES, name


REFUSED_AT_CONFIGURATION = {
    "prefix_cache": {"prefix_cache": {"enabled": True}},
    "host_spill": {"prefix_cache": {"enabled": False, "host_spill": True}},
    "speculative": {"speculative": {"enabled": True}},
    "kv_quant": {"kv_quant": {"enabled": True}},
    "tensor_parallel": {"tensor_parallel": {"tp_size": 2}},
}


@pytest.mark.parametrize("feature", sorted(REFUSED_AT_CONFIGURATION))
def test_what_treats_blocks_as_the_state_is_refused_at_configuration(
        feature):
    """Each configuration row of ``_REFUSALS["recurrent_state"]`` (and the
    tensor mesh of ``"state_and_experts"``) is raised for this family too: a
    cached prefix's blocks do not hold the tail at their end."""
    cfg, params, _ = build()
    mesh_lib.set_mesh(None)
    with pytest.raises(RecurrentStateError, match="recurrent state|mixer"):
        build_engine_v2(float32_module(), cfg, params, config={
            **ENGINE, **REFUSED_AT_CONFIGURATION[feature]})


@pytest.mark.parametrize("call", ["fork", "export_kv_blocks",
                                  "import_kv_blocks"])
def test_what_treats_blocks_as_the_state_is_refused_at_its_call(served, call):
    eng = served[0]
    assert eng._refusals == [_REFUSALS["recurrent_state"],
                             _REFUSALS["state_and_experts"]]
    eng.put(21, served[2][3])
    args = {"fork": (21, 22), "export_kv_blocks": (21,),
            "import_kv_blocks": ([], [])}[call]
    with pytest.raises(RecurrentStateError, match=call):
        getattr(eng, call)(*args)
    eng.state.debug_check()                  # nothing half done
    eng.finish(21)


def test_training_and_the_dense_cache_are_refused_by_name():
    cfg, params, _ = build()
    with pytest.raises(NotImplementedError, match="serving family"):
        zaya.loss_fn(cfg, params, {"tokens": jnp.zeros((1, 8), jnp.int32)})
    with pytest.raises(NotImplementedError, match="build_engine_v2"):
        zaya.init_cache(cfg, 1, 8)
    with pytest.raises(NotImplementedError, match="build_engine_v2"):
        zaya.apply_cached(cfg, params, None, None, None)
    with pytest.raises(ValueError, match="cca_time0"):
        zaya.init(dataclasses.replace(cfg, cca_time0=4),
                  jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="TWO key-value heads"):
        zaya.init(dataclasses.replace(cfg, num_kv_heads=4),
                  jax.random.PRNGKey(0))


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
    slots, neighbours live, a tail row a slot), its reading is a line of the
    output, and a program that is not the reference's (its output projection
    negated) raises by name."""
    _, params, row, want = f32
    weights = family.Weights(params, role=ROLE)
    if name == "right":
        with jax.default_matmul_precision("highest"):
            got, margin = reference.logits_and_margin(TINY, weights, row)
        assert gap(got, want) < TOL
        # a top-1 margin a position (Mixtral's rule, not the flat one)
        assert margin.shape == (len(row),) and bool((margin > 0).all())
        line = json.loads(capsys.readouterr().out.splitlines()[-1])
        assert line["phase"] == "held" and line["why_not"] == []
        assert line["decode_rows"] == reference.decode_rows(len(row))
        return
    layers = {**params["layers"], "wo": -params["layers"]["wo"]}
    weights.program.params = {**params, "layers": layers}
    with jax.default_matmul_precision("highest"), \
            pytest.raises(reference.Disagreement, match="logits lie"):
        reference.logits_and_margin(TINY, weights, row)


# --- the configuration's file ------------------------------------------------ #
PUBLISHED = {**CONFIG["published"], **CONFIG["roles"]["serve"]["model"]}
CHANGED = [("model_type", "mellum"), ("attention_bias", True),
           ("lm_head_bias", True), ("tie_word_embeddings", False),
           ("hidden_act", "gelu"), ("sliding_window", 4096),
           ("num_experts_per_tok", 2)]


@pytest.mark.parametrize("key,value", CHANGED)
def test_build_cfg_refuses_a_changed_published_key_by_name(key, value):
    with pytest.raises(ValueError, match=key):
        family.build_cfg({**PUBLISHED, key: value})


def test_the_family_declares_what_the_engine_reads():
    cfg = family.build_cfg(PUBLISHED)
    assert (cfg.num_layers, cfg.latent, cfg.rotary_dim, cfg.tail_width) == (
        20, 1280, 64, 2688)
    assert cfg.tail_part == (0, 16, 256)
    assert zaya.STATE_LEAVES == ("tail",)
    assert zaya.state_slot_bytes(cfg) == 20 * 16 * 256 * 2
    assert set(zaya.FLOAT32_PARAMS) >= {"router_down", "router_out",
                                        "router_bias"}
    assert not hasattr(zaya, "model_spec") and not hasattr(zaya,
                                                           "window_kinds")
    assert zaya.state_rows(cfg, 64, 512) == {"cca_rows": 576,
                                             "cca_tail_rows": 65}
    assert zaya.state_rows(cfg, 64, 0) == {"cca_rows": 64,
                                           "cca_tail_rows": 64}
    rows = zaya.moe_rows(cfg, 576)
    assert rows["moe_rows_routed"] + rows["moe_rows_skipped"] == 576
    assert rows["moe_rows_skipped"] == 576 - 576 * 16 // 17
    shapes = jax.eval_shape(lambda k: zaya.init(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    moe = shapes["layers"]["moe"]
    assert all(moe[k].dtype == F32 for k in zaya.FLOAT32_PARAMS)
    assert moe["w_up"].shape == (20, 16, 2048, 2048)
    assert moe["router_out"].shape == (20, 256, 17) and "router" not in moe
    assert shapes["embed"].shape == (262272, 2048) \
        and "lm_head" not in shapes
    cache = jax.eval_shape(lambda: zaya.init_paged_cache(cfg, 2560, 64,
                                                         slots=64))
    assert cache["k"].shape == (20, 2560, 2, 64, 128)
    assert cache["tail"].shape == (20, 65, 16, 256)
    assert cache["tail"].dtype == jnp.bfloat16


def test_the_bytes_are_the_issues():
    """Parameters from the shapes the program builds (no array is made):
    207.58 M a layer, 9.38 GB of bf16 weights; 20 480 B of K and V a token;
    and the benchmark's counts of the same model."""
    cfg = family.build_cfg(PUBLISHED)
    shapes = jax.eval_shape(lambda k: zaya.init(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    layer = count(shapes["layers"]) / 20
    assert abs(layer - 207.58e6) < 0.02e6, layer
    total = count(shapes)
    assert abs(total * 2 / 1e9 - 9.38) < 0.01, total
    assert costs.kv_bytes_per_token(PUBLISHED) == 20480
    assert costs.head_dim(PUBLISHED) == 128
    assert costs.attention_params(PUBLISHED) == 5242880
    assert costs.ffn_params(PUBLISHED) == 3 * 2048 * 2048
    assert costs_cca.tail_numbers(PUBLISHED) == cfg.tail_width == 2688
    assert costs_cca.conv_params(PUBLISHED) == (
        int(np.prod(shapes["layers"]["conv0_w"].shape[1:]))
        + int(np.prod(shapes["layers"]["conv1_w"].shape[1:])) + 2 * 1280)


def test_the_configuration_file_is_the_catalogs_with_the_cut_laid_over():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    cut = {"num_hidden_layers": 20, "max_position_embeddings": 4096}
    added = {"intermediate_size": 2048, "num_local_experts": 16}
    if os.path.exists(catalog):
        row = next(json.loads(ln) for ln in open(catalog)
                   if '"ZAYA1-8B"' in ln)
        assert CONFIG["published"] == {**row["config"], **added}
        assert CONFIG["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert CONFIG[key] == cut.get(key, value), key
    assert CONFIG["reduced"] == list(cut)
    role = CONFIG["roles"]["serve"]
    assert role["model"] == cut
    assert not set(role["model"]) & set(manifest.WIDTH_KEYS)
    assert role["scheduler"] == {"max_admissions_per_tick": 1,
                                 "preempt": False}
    for item in ("conv0", "conv1", "qk_mean", "value_shift", "l2_norm",
                 "grouping", "rope", "skip", "router", "residual",
                 "intermediate_size", "num_local_experts", "tokens",
                 "deployment_tables", "weights", "served_tokens"):
        assert CONFIG["assumed"][item]
    assert set(role["held"]) == {"why"} | {k for k, _, _ in reference.HELD}
    for said in ("two pipeline stages of 20 layers", "FIRST stage",
                 "16 experts", "whole tied vocabulary"):
        assert said in CONFIG["deployment"], said
    for said in ("9.38 GB", "20 480 B", "5.37 GB"):
        assert said in role["why"], said


# --- the lift: eight families' programs are the parent's ----------------------- #
# sha256 of each program's jaxpr on shapes alone, taken on ISSUE 64's parent
# (86e8e2c) under a one-device process mesh (the grouped MoE form): that PR
# gave ``_state.short_conv`` a plain form and ``MoELayer.__call__`` a seam
# for a family's own logits, and meant to change no program of the families
# that had them. A PR that means to change one replaces its line.
FAMILIES = {
    "granite_hybrid": ("granite_hybrid", "GraniteHybridConfig", {}),
    "nemotron_h": ("nemotron_h", "NemotronHConfig", {}),
    "solar_open2": ("solar_open2", "SolarOpen2Config", {}),
    "mixtral": ("mixtral", "MixtralConfig", {"drop_tokens": False}),
    "olmoe": ("mixtral", "MixtralConfig", {
        "drop_tokens": False, "norm_topk_prob": False, "qk_norm": True}),
    "keye": ("mixtral", "MixtralConfig", {"drop_tokens": False,
                                          "experts_held": (1, 2)}),
    "cohere2_moe": ("cohere2_moe", "Cohere2MoeConfig",
                    {"drop_tokens": False}),
    "mellum": ("mellum", "MellumConfig", {}),
}
PARENT = {
    "granite_hybrid": {"apply": "34d0e704108d1c67",
                       "chunk": "6721b5115cdfe3e8",
                       "decode": "f62cfbbc54653adc",
                       "mixed": "53eaf5d923805e1f"},
    "nemotron_h": {"apply": "4e204bcba5015d6f", "chunk": "666fb2c1fa4b5fde",
                   "decode": "8bcd1a0ea7861b62", "mixed": "857b1987100d6c34"},
    "solar_open2": {"apply": "8614872bbb9ce67e", "chunk": "b2bfa346f8e10eaa",
                    "decode": "753cc07c03bb5861",
                    "mixed": "8e2b10348c93d3e3"},
    "mixtral": {"apply": "9dda8853ea9faa33", "chunk": "1a2223f5e0a690be",
                "decode": "03277e39c779a305", "mixed": "79422e37949ad3bf"},
    "olmoe": {"apply": "2ead13d4e7bd58d1", "chunk": "585545abbae8c1ec",
              "decode": "788e3ed2506c9c48", "mixed": "1c4c83ce148becf4"},
    "keye": {"apply": "156b1c3c5afd4c47", "chunk": "5c5a9b4194ed1bd7",
             "decode": "6cfd881804e891ea", "mixed": "00f7b2ef808a2bca"},
    "cohere2_moe": {"apply": "de495bb1920b9868", "chunk": "ffcc2c3e4e6b4d5b",
                    "decode": "2a426568bbd5ddc5",
                    "mixed": "273b3b34a5cbfc12"},
    "mellum": {"apply": "2eb8aa134bd4082f", "chunk": "57ec97635669263c",
               "decode": "42a2f5080bed7845", "mixed": "4b008d36028407c3"},
}


def _text(fn, *args) -> str:
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))


def program_texts(name):
    """The jaxpr of the full forward and of ``apply_paged`` as a chunk call,
    a decode call and a mixed call of one family at its tiny size."""
    module, cls, kw = FAMILIES[name]
    m = importlib.import_module("deepspeed_tpu.models." + module)
    C = getattr(m, cls)
    fields = {f.name for f in dataclasses.fields(C)}
    cfg = C.tiny(**{k: v for k, v in kw.items() if k in fields})
    s, i32 = jax.ShapeDtypeStruct, jnp.int32
    params = jax.eval_shape(lambda k: m.init(cfg, k), jax.random.PRNGKey(0))
    width = max(2, -(-cfg.max_seq_len // 4))
    kw_cache = {"slots": 4} if hasattr(m, "state_slot_bytes") else {}
    if hasattr(m, "window_kinds") and m.window_kinds(cfg):
        kw_cache["window_blocks"] = {"window": 12}
    cache = jax.eval_shape(lambda: m.init_paged_cache(cfg, 16, 4, **kw_cache))
    paged = lambda p, t, c, b, n: m.apply_paged(cfg, p, t, c, b, n)

    def mixed(p, t, c, tables, lens, active, ctab, ctx, nv, slot, rows):
        call = MixedCall(tables, lens, active, ctab, ctx, nv, slot)
        return m.apply_paged(cfg, p, t, c, call, None,
                             valid=call.valid(t.shape[1]), rows=rows)

    return {
        "apply": _text(lambda p, t: m.apply(cfg, p, t), params,
                       s((2, 8), i32)),
        "chunk": _text(paged, params, s((2, 8), i32), cache,
                       s((2, width), i32), s((2,), i32)),
        "decode": _text(paged, params, s((2, 1), i32), cache,
                        s((2, width), i32), s((2,), i32)),
        "mixed": _text(mixed, params, s((1, 4 + 8), i32), cache,
                       s((4, width), i32), s((4,), i32), s((4,), bool),
                       s((width,), i32), s((), i32), s((), i32), s((), i32),
                       s((1, 5), i32)),
    }


@pytest.fixture(scope="module")
def family_hashes():
    before = mesh_lib._global_mesh
    mesh_lib.set_mesh(None)
    mesh_lib.init_mesh({"data": 1}, devices=jax.devices()[:1])
    try:
        return {name: {k: hashlib.sha256(v.encode()).hexdigest()[:16]
                       for k, v in program_texts(name).items()}
                for name in FAMILIES}
    finally:
        mesh_lib.set_mesh(before)


@pytest.mark.parametrize("program", ["apply", "chunk", "decode", "mixed"])
@pytest.mark.parametrize("name", sorted(FAMILIES))
def test_an_older_familys_program_is_the_parents(family_hashes, name,
                                                 program):
    assert family_hashes[name][program] == PARENT[name][program]
