"""Granite-4.0-H (``models/granite_hybrid.py``, ISSUE 31) against the
benchmark's plain reference (``benchmark/reference/granite_hybrid.py`` - the
one reference, not a copy) at a small size on the CPU: a whole 10-layer
period (two, where the outer scan matters) at the published RATIOS of widths,
seeded random weights, float32 on both sides. Everything is compared in
LOGITS: the full forward, a one-shot padded prefill, chunked prefill whose
chunks end off the SSD block and off the convolution's tail, decode through
the state, and the engine's slots (inactive, prefilling, reused, preempted).

Tolerance. Program and reference both compute in float32 (the reference
under matmul precision "highest", the program's matmuls are the CPU's own
float32) in another order of operations: the largest difference measured
over every path below is 2.4e-5 of unit-variance logits (the residual
stream is ~20 times the embedded token - ``init``'s output gain - and the
last layers' sums cancel). ``TOL`` = 2e-4 is eight times that - and a
thirtieth of what the NEAREST wrong variant gives (the recurrent state
rounded to bfloat16 after every token: 6e-3 in the reference, 6e-2 in the
program; the other five 0.8 to 5), so a bf16 state, or any of the six
variants, fails it.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import granite_hybrid as family
from benchmark.reference import granite_hybrid as reference
from benchmark.reference import granite_hybrid_variants as variants
from deepspeed_tpu.inference.engine_v2 import (RecurrentStateError,
                                               build_engine_v2)
from deepspeed_tpu.models import granite_hybrid as gh
from deepspeed_tpu.ops import ssm

TOL = 2e-4
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def published(periods=1):
    """The published keys at the test size (the ratios of the release:
    inner = 2 x hidden = heads x head size, attention group 4, state = 2 x
    the Mamba head size, ``attention_multiplier`` = 1 / head size)."""
    return dict(
        attention_bias=False, attention_multiplier=0.25,
        embedding_multiplier=12, hidden_act="silu", hidden_size=32,
        intermediate_size=128, layer_types=PERIOD * periods,
        logits_scaling=8, mamba_chunk_size=16, mamba_conv_bias=True,
        mamba_d_conv=4, mamba_d_head=8, mamba_d_state=16, mamba_expand=2,
        mamba_n_groups=1, mamba_n_heads=8, mamba_proj_bias=False,
        max_position_embeddings=256, model_type="granitemoehybrid",
        normalization_function="rmsnorm", num_attention_heads=8,
        num_experts_per_tok=0, num_hidden_layers=10 * periods,
        num_key_value_heads=2, num_local_experts=0,
        position_embedding_type="nope", residual_multiplier=0.22,
        rms_norm_eps=1e-5, rope_scaling=None, rope_theta=10000,
        shared_intermediate_size=128, tie_word_embeddings=True,
        vocab_size=256)


def build(periods=1):
    """The configuration, its seeded weights in float32 - the norms' weights
    and ``D`` too, which ``init`` leaves at one: a weight that went unused
    would otherwise pass - and a row of tokens."""
    hf = published(periods)
    cfg = family.build_cfg(hf, compute_dtype="float32")
    params = family.init(cfg, jax.random.PRNGKey(0))
    for kind, names in (("mamba", ("norm", "mlp_norm", "gate_norm", "D")),
                        ("attn", ("norm", "mlp_norm"))):
        for i, name in enumerate(names):
            leaf = params[kind][name]
            params[kind][name] = 1.0 + 0.2 * jax.random.normal(
                jax.random.PRNGKey(10 + i), leaf.shape)
    params["final_norm"] = 1.0 + 0.2 * jax.random.normal(
        jax.random.PRNGKey(20), params["final_norm"].shape)
    row = np.random.default_rng(0).integers(0, 256, 72)
    return hf, cfg, params, row


@pytest.fixture(scope="module")
def model():
    hf, cfg, params, row = build(periods=2)
    want = np.asarray(reference.logits(hf, family.Weights(params), row))
    return hf, cfg, params, row, want


@functools.partial(jax.jit, static_argnums=(0,))
def _paged_call(cfg, params, cache, padded, table, start, n, slot):
    return gh.apply_paged(cfg, params, padded, cache, table, start[None],
                          valid=jnp.arange(padded.shape[1])[None] < n,
                          slots=slot[None])


def paged_logits(cfg, params, row, calls, slot=1, slots=3, block=8):
    """Logits of ``row`` fed through ``apply_paged`` call by call:
    ``calls`` = ``(tokens in the call, width the call is padded to)``."""
    with jax.default_matmul_precision("highest"):
        cache = gh.init_paged_cache(cfg, 24, block, dtype=jnp.float32,
                                    slots=slots)
        table = np.zeros((1, 32), np.int32)
        table[0, :12] = [3, 1, 7, 2, 9, 4, 5, 11, 6, 8, 10, 12]   # 0: trash
        out, start = [], 0
        for n, width in calls:
            padded = np.zeros((1, width), np.int32)
            padded[0, :n] = row[start:start + n]
            logits, cache = _paged_call(
                cfg, params, cache, jnp.asarray(padded), jnp.asarray(table),
                jnp.int32(start), jnp.int32(n), jnp.int32(slot))
            out.append(np.asarray(logits[0, :n]))
            start += n
    return np.concatenate(out), cache


# (tokens, padded width) of each call. The SSD block is 16 and the
# convolution's tail 3 rows: chunks of 13, 2 and 1 end off both, 16 on the
# block, and a chunk of 21 in 24 spans two blocks with padding in the second
PATHS = {
    "one_shot_padded_prefill": [(72, 80)],
    "chunks_off_the_block_and_the_tail": [(13, 16), (2, 16), (1, 16),
                                          (16, 16), (21, 24), (19, 24)],
    "prefill_then_32_decode_steps": [(40, 48)] + [(1, 1)] * 32,
    "decode_from_the_first_token": [(1, 1)] * 12,
}


def test_full_forward_agrees_with_the_plain_reference(model):
    hf, cfg, params, row, want = model
    with jax.default_matmul_precision("highest"):
        got = gh.apply(cfg, params, jnp.asarray(row[None]))[0]
    assert float(np.abs(np.asarray(got) - want).max()) < TOL


@pytest.mark.parametrize("path", sorted(PATHS))
def test_paged_path_agrees_with_the_plain_reference_in_logits(model, path):
    hf, cfg, params, row, want = model
    got, _ = paged_logits(cfg, params, row, PATHS[path])
    assert float(np.abs(got - want[:len(got)]).max()) < TOL


@pytest.mark.parametrize("variant", variants.NAMES)
def test_each_wrong_variant_stands_apart_by_more_than_the_tolerance(
        model, variant):
    hf, cfg, params, row, want = model
    wrong = np.asarray(variants.logits(variant, hf, family.Weights(params),
                                       row))
    assert float(np.abs(wrong - want).max()) > 3 * TOL


def test_a_bfloat16_state_in_the_program_fails_the_tolerance(model):
    """The program itself with its recurrent state kept in bfloat16 (the
    nearest precision below the one the configuration states) is NOT within
    the tolerance of the reference: the comparison sees the state's type."""
    hf, cfg, params, row, want = model
    low = dataclasses.replace(cfg, state_dtype="bfloat16")
    got, _ = paged_logits(low, params, row,
                          PATHS["prefill_then_32_decode_steps"])
    assert float(np.abs(got - want[:len(got)]).max()) > 3 * TOL


def test_chunked_scan_is_the_token_by_token_recurrence():
    k = jax.random.split(jax.random.PRNGKey(1), 7)
    b, t, H, P, N = 2, 37, 4, 8, 16
    x = jax.random.normal(k[0], (b, t, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, H)))
    dt = dt.at[1, 30:].set(0.0)           # a row's padding
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B, C = (jax.random.normal(k[i], (b, t, N)) for i in (3, 4))
    h0 = jax.random.normal(k[5], (b, H, P, N))
    y, h = ssm.ssm_recurrence(x, dt, A, B, C, h0)
    for chunk in (8, 16, 64):             # blocks off, on and over the length
        y2, h2 = ssm.ssd_chunked_scan(x, dt, A, B, C, h0, chunk)
        assert float(jnp.abs(y - y2).max()) < 1e-4
        assert float(jnp.abs(h - h2).max()) < 1e-5
    # padding neither decays nor feeds the state
    _, h30 = ssm.ssm_recurrence(x[1:, :30], dt[1:, :30], A, B[1:, :30],
                                C[1:, :30], h0[1:])
    assert float(jnp.abs(h[1] - h30[0]).max()) < 1e-6


def test_interpreted_kernels_are_their_xla_references_at_distinct_layers():
    """``ssm_decode_update`` and the two row ops, Pallas in interpret mode
    against XLA, on a 3-layer pool whose layers differ: each call touches
    its own layer's rows and nothing else, the trash row apart."""
    from deepspeed_tpu.ops.pallas import ssm as kernels

    k = jax.random.split(jax.random.PRNGKey(2), 8)
    L, S, N, T, HP, b = 3, 5, 16, 8, 64, 4
    pool = jax.random.normal(k[0], (L, S + 1, N + T, HP), jnp.float32)
    rows = jnp.asarray([2, S, 0, S])                # S: the trash row
    fresh = jnp.asarray([False, False, True, False])
    decay = jax.random.uniform(k[1], (b, HP))
    dtx = jax.random.normal(k[2], (b, HP))
    B, C = (jax.random.normal(k[i], (b, N)) for i in (3, 4))
    live = np.asarray([0, 2])
    for layer in range(L):
        want, y = ssm.ssm_decode_update_xla(pool, layer, rows, fresh, decay,
                                            dtx, B, C)
        got, y2 = kernels.ssm_decode_update(pool, jnp.int32(layer), rows,
                                            fresh, decay, dtx, B, C)
        np.testing.assert_allclose(np.asarray(y2)[live], np.asarray(y)[live],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got)[:, :S],
                                   np.asarray(want)[:, :S], rtol=1e-6,
                                   atol=1e-6)
        others = [i for i in range(L) if i != layer]
        np.testing.assert_array_equal(np.asarray(got)[others],
                                      np.asarray(pool)[others])
        np.testing.assert_array_equal(np.asarray(got)[layer, :S, N:],
                                      np.asarray(pool)[layer, :S, N:])
        for part in ((0, N, HP), (N, T, HP)):
            np.testing.assert_array_equal(
                np.asarray(kernels.state_rows_read(pool, layer, rows, part)),
                np.asarray(ssm.state_rows_read_xla(pool, layer, rows, part)))
            new = jax.random.normal(k[5], (b,) + part[1:])
            np.testing.assert_array_equal(
                np.asarray(kernels.state_rows_write(pool, layer, rows, new,
                                                    part))[:, :S],
                np.asarray(ssm.state_rows_write_xla(pool, layer, rows, new,
                                                    part))[:, :S])


def test_scan_nest_follows_layer_types_and_not_a_fixed_period():
    assert gh.layer_plan(tuple(PERIOD * 4)) == (
        4, [("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 4)],
        {"mamba": 9, "attention": 1})
    odd = ("attention", "mamba", "mamba", "attention", "mamba")
    assert gh.layer_plan(odd)[0] == 1
    hf = {**published(), "layer_types": list(odd), "num_hidden_layers": 5}
    cfg = family.build_cfg(hf, compute_dtype="float32")
    params = gh.init(cfg, jax.random.PRNGKey(3))
    row = np.random.default_rng(3).integers(0, 256, 20)
    want = np.asarray(reference.logits(hf, family.Weights(params), row))
    got, _ = paged_logits(cfg, params, row, [(9, 16), (11, 16)])
    assert float(np.abs(got - want).max()) < TOL


# --- the engine's slots ---------------------------------------------------- #
ENGINE = {"dtype": "float32", "prefill_bucket": 8, "split_prefill_chunk": 16,
          "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                     "memory_config_blocks": 64, "block_size": 8}}


@pytest.fixture(scope="module")
def served():
    hf, cfg, params, _ = build()
    return hf, cfg, params


def engine(served, **config):
    _, cfg, params = served
    return build_engine_v2(gh, cfg, params, config={**ENGINE, **config})


def gaps(served, eng, prompt, out):
    """How far below the reference's top each served token lies, and the
    reference's logits at the served positions."""
    hf = served[0]
    tokens = np.asarray(list(prompt) + out[:-1], np.int32)
    want = np.asarray(reference.logits(hf, family.Weights(eng.params),
                                       tokens))[len(prompt) - 1:]
    return want.max(-1) - want[np.arange(len(out)), out]


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lengths]


def state_of(eng, slot):
    return np.asarray(eng.cache["ssm"][:, slot])


def test_inactive_and_prefilling_slots_keep_their_state_bit_for_bit(served):
    """A decode step over slots of which one is active, one is prefilling
    (admitted by chunks, its first chunk done) and two are free: the free
    slots' rows and the prefilling slot's row come out bit-equal, the active
    slot's row moves, and every served token is the reference's top."""
    eng = engine(served)
    a, b = prompts(11, 40)
    out = [eng.put(1, a)]
    eng.put_split(2, b)
    out.append(eng.step()[1])                # runs b's first chunk too
    slots = {u: eng.state.seqs[u].slot for u in (1, 2)}
    free = [s for s in range(4) if s not in slots.values()]
    eng._pending_prefill.clear()             # hold b where it is
    before = {s: state_of(eng, s) for s in range(4)}
    out.append(eng.step()[1])
    for s in free + [slots[2]]:
        np.testing.assert_array_equal(state_of(eng, s), before[s])
    assert np.abs(state_of(eng, slots[1]) - before[slots[1]]).max() > 0
    assert eng.last_step["ssm_rows"] == 1 and eng.last_step["ssm_tokens"] == 1
    assert float(gaps(served, eng, a, out).max()) == 0.0


def test_a_prompt_that_completes_beside_a_decode_advances_its_state_once(
        served):
    """The step whose final chunk seats a sequence also runs a decode over
    the slots, which the new sequence is no part of (``engine_v2._slots``:
    active are the sequences the call decodes): its recurrent state must not
    move, or its first real decode advances it a second time. Both streams
    stay the reference's."""
    eng = engine(served)
    a, b = prompts(11, 37)
    first = [eng.put(1, a)]
    eng.put_split(2, b)
    second = []
    while len(second) < 6:
        out = eng.step()
        first += [out[1]] if 1 in out else []
        second += [out[2]] if 2 in out else []
    assert float(gaps(served, eng, a, first).max()) == 0.0
    assert float(gaps(served, eng, b, second).max()) == 0.0
    assert len(set(first + second)) > 6      # no one token repeated


TRACED = {"trace": {"enabled": True, "ring_size": 4096,
                    "dump_on_crash": False}}


def test_both_forms_of_the_chunk_scan_serve_a_two_chunk_prompt_alike():
    """``ssm_chunk_scan``'s two backends - the XLA read, scan and write, and
    the Mosaic kernel (interpreted; at sizes it tiles: a 128-lane inner
    width, N = 128) - under one engine run each: a 27-token prompt enters
    in a 16-token chunk and a ragged one of 11 beside a live stream; both
    serve the same tokens and leave the same state rows, and a step's span
    says ``ssm_chunk_rows`` - the chunk's tokens on a chunk's tick where the
    kernel took them, 0 on a decode-only tick and wherever the XLA form
    ran."""
    from deepspeed_tpu.ops import registry

    hf = dict(published(), hidden_size=64, mamba_n_heads=4, mamba_d_head=32,
              mamba_d_state=128)
    cfg = family.build_cfg(hf, compute_dtype="float32")
    assert gh._ssm_scan.takes(128, 4, 32, 1, cfg.state_dtype)
    params = family.init(cfg, jax.random.PRNGKey(0))
    a, b = prompts(9, 27)
    runs = {}
    for backend in ("xla", "pallas"):
        registry.set_backend("ssm_chunk_scan", backend)
        try:
            eng = build_engine_v2(gh, cfg, params,
                                  config={**ENGINE, **TRACED})
            out = [eng.put(1, a)]
            eng.put_split(2, b)
            for _ in range(5):
                out += sorted(eng.step().items())
        finally:
            registry.set_backend("ssm_chunk_scan", None)
        steps = [e["args"] for e in eng.tracer.events()
                 if e["ph"] == "X" and e["name"] == "decode_step"]
        runs[backend] = (out, np.asarray(eng.cache["ssm"])[:, :-1], [
            (s["chunk_tokens"], s["ssm_chunk_rows"]) for s in steps])
    (out, state, said), (out_k, state_k, said_k) = runs["xla"], runs["pallas"]
    assert out_k == out
    # (rows of magnitude ~4; the first five layers agree to 2e-6, the
    # attention layer after them widens that to 5e-4)
    np.testing.assert_allclose(state_k, state, rtol=1e-3, atol=2e-3)
    assert said == [(16, 0), (11, 0), (0, 0), (0, 0), (0, 0)]
    assert said_k == [(16, 16), (11, 11), (0, 0), (0, 0), (0, 0)]


def test_a_retired_slot_leaks_nothing_into_the_next_sequence(served):
    """A sequence served in a slot another has just left (its row still
    holds the former state) gives the logits of a fresh start: offset 0
    resets inside the program, with no host-side clear."""
    eng = engine(served)
    first, second = prompts(37, 9, seed=1)
    eng.put(1, first)
    for _ in range(5):
        eng.step()
    slot = eng.state.seqs[1].slot
    eng.finish(1)
    assert np.abs(state_of(eng, slot)).max() > 0     # nothing cleared it
    out = [eng.put(2, second)]
    assert eng.state.seqs[2].slot == slot
    out += [eng.step()[2] for _ in range(6)]
    assert float(gaps(served, eng, second, out).max()) == 0.0
    # the chunked path into a used slot too
    eng.finish(2)
    eng.put_split(3, first)
    out = []
    while len(out) < 4:
        tok = eng.step().get(3)
        out += [] if tok is None else [tok]
    assert eng.state.seqs[3].slot == slot
    assert float(gaps(served, eng, first, out).max()) == 0.0


def test_preemption_and_readmission_continue_the_stream(served):
    """``park`` / ``resume`` work by recomputation: the history is
    prefilled again from offset 0 (which resets the slot's state), one-shot
    or by chunks, and the stream continues as if never interrupted."""
    for split in (False, True):
        eng = engine(served)
        (prompt,) = prompts(21, seed=2)
        out = [eng.put(1, prompt)]
        out += [eng.step()[1] for _ in range(4)]
        parked = eng.park(1)
        eng.put(9, prompts(30, seed=3)[0])           # takes the slot over
        eng.step()
        out += eng.resume(parked, split=split)
        while len(out) < 10:
            tok = eng.step().get(1)
            out += [] if tok is None else [tok]
        assert float(gaps(served, eng, prompt, out).max()) == 0.0
        assert eng.finish(1) == out


def test_admission_reports_the_state_beside_the_blocks(served):
    eng = engine(served)
    per_slot = gh.state_slot_bytes(served[1])
    assert per_slot == 9 * (16 + 8) * 64 * 4
    room = eng.kv_headroom()
    assert room["state_bytes_per_slot"] == per_slot
    assert room["state_bytes_free"] == room["state_bytes_total"] == 4 * per_slot
    eng.put(1, prompts(9)[0])
    assert eng.kv_headroom()["state_bytes_free"] == 3 * per_slot
    events = dict((n, v) for n, v, _ in eng.state_events())
    assert events["Serving/state/bytes"] == eng.cache["ssm"].nbytes
    assert events["Serving/state/slots_held"] == 1
    from deepspeed_tpu.telemetry.schema import SERVING_SERIES
    assert set(events) <= SERVING_SERIES


REFUSED_AT_CONFIGURATION = {
    "prefix_cache": {"prefix_cache": {"enabled": True}},
    "host_spill": {"prefix_cache": {"enabled": False, "host_spill": True}},
    "speculative": {"speculative": {"enabled": True}},
    "kv_quant": {"kv_quant": {"enabled": True}},
}


@pytest.mark.parametrize("feature", sorted(REFUSED_AT_CONFIGURATION))
def test_what_needs_state_snapshots_is_refused_at_configuration(served,
                                                                feature):
    with pytest.raises(RecurrentStateError, match="recurrent state"):
        engine(served, **REFUSED_AT_CONFIGURATION[feature])


@pytest.mark.parametrize("call", ["fork", "export_kv_blocks",
                                  "import_kv_blocks"])
def test_what_needs_state_snapshots_is_refused_at_its_call(served, call):
    eng = engine(served)
    eng.put(1, prompts(9)[0])
    args = {"fork": (1, 2), "export_kv_blocks": (1,),
            "import_kv_blocks": ([], [])}[call]
    with pytest.raises(RecurrentStateError, match=call):
        getattr(eng, call)(*args)
    eng.state.debug_check()                  # nothing half done


def test_importing_the_package_loads_neither_the_family_nor_its_kernels():
    import subprocess
    import sys

    code = ("import sys, deepspeed_tpu, deepspeed_tpu.models, "
            "deepspeed_tpu.inference.engine_v2\n"
            "bad = [m for m in sys.modules if m.endswith(('granite_hybrid', "
            "'ops.ssm', 'pallas.ssm'))]\nassert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})


def test_hf_name_map_on_a_synthetic_state_dict():
    """``granitemoehybrid`` checkpoints by their published names (no network
    here: a synthetic state dict of the test size, in torch's layouts) come
    out as the tree ``init`` builds, and compute the same logits."""
    import types

    from deepspeed_tpu.models import hf_import

    hf, cfg, params, row = build()
    sd = {"model.embed_tokens.weight": params["embed"],
          "model.norm.weight": params["final_norm"],
          "lm_head.weight": params["embed"]}
    counts = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(hf["layer_types"]):
        p = params[gh.KINDS[kind]]
        j = counts[kind]
        counts[kind] += 1
        at = f"model.layers.{i}."
        sd[at + "input_layernorm.weight"] = p["norm"][j]
        sd[at + "post_attention_layernorm.weight"] = p["mlp_norm"][j]
        sd[at + "shared_mlp.input_linear.weight"] = p["w_in"][j].T
        sd[at + "shared_mlp.output_linear.weight"] = p["w_out"][j].T
        if kind == "attention":
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "o_proj")):
                sd[at + f"self_attn.{theirs}.weight"] = p[ours][j].T
            continue
        sd[at + "mamba.in_proj.weight"] = jnp.concatenate(
            [p["in_proj"][j], p["dt_proj"][j]], axis=1).T
        sd[at + "mamba.conv1d.weight"] = p["conv_w"][j].T[:, None, :]
        sd[at + "mamba.conv1d.bias"] = p["conv_b"][j]
        for ours, theirs in (("dt_bias", "dt_bias"), ("A_log", "A_log"),
                             ("D", "D"), ("gate_norm", "norm.weight")):
            sd[at + "mamba." + theirs] = p[ours][j]
        sd[at + "mamba.out_proj.weight"] = p["out_proj"][j].T
    sd = {k: np.asarray(v) for k, v in sd.items()}
    got_cfg = hf_import.granitemoehybrid_config_from_hf(
        types.SimpleNamespace(**hf))
    assert dataclasses.replace(got_cfg, compute_dtype="float32") == cfg
    got = hf_import.granitemoehybrid_params_from_hf(sd, got_cfg)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert hf_import.resolve_module("granitemoehybrid") is gh
    with pytest.raises(ValueError, match="sparse branch"):
        hf_import.granitemoehybrid_config_from_hf(
            types.SimpleNamespace(**{**hf, "num_local_experts": 8}))


# --- the benchmark's configuration, costs and readers ----------------------- #
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _configuration():
    from benchmark.harness import manifest

    return manifest.load_json(
        manifest.ROOT + "/benchmark/configs/granite-4.0-h-micro.json")


def test_published_is_the_catalog_row_and_nothing_is_cut_but_the_context():
    import json
    import os

    from benchmark.harness.manifest import Cell

    data = _configuration()
    assert data["reduced"] == ["max_position_embeddings"]
    assert set(data["assumed"]) >= {"state_dtype", "weights",
                                    "max_admissions_per_tick"}
    cell = Cell("granite-4.0-h-micro.serve-chat-64")
    cut = {k for k in cell.model if cell.model[k] != data["published"][k]}
    assert cut == {"max_position_embeddings"}
    assert len(cell.model["layer_types"]) == 40 == \
        cell.model["num_hidden_layers"]
    # the top level is the configuration as it is run: every key of the
    # catalog's entry under its own name, the role's cut laid over it
    assert {k: data[k] for k in data["published"]} == cell.model
    if not os.path.exists(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert data["published"] == row["config"]
    assert data["source"] == row["source_url"]


def test_the_cells_configuration_is_the_published_model_in_the_program():
    from benchmark.harness.manifest import Cell

    cell = Cell("granite-4.0-h-micro.serve-chat-64")
    cfg = family.build_cfg(cell.model, **cell.role["program_options"])
    assert (cfg.count("mamba"), cfg.count("attention")) == (36, 4)
    assert gh.layer_plan(cfg.layer_types)[:2] == (
        4, [("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 4)])
    assert (cfg.d_inner, cfg.conv_dim, cfg.head_size) == (4096, 4352, 64)
    assert cfg.tail_part == (128, 8, 1664) and cfg.state_dtype == "float32"
    shapes = jax.eval_shape(lambda: gh.init_paged_cache(cfg, 2816, 32,
                                                        slots=64))
    assert shapes["k"].shape == (4, 2816, 4, 32, 128)
    assert shapes["ssm"].shape == (36, 65, 136, 4096)
    assert gh.state_slot_bytes(cfg) == 36 * 136 * 4096 * 4
    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(
        jax.eval_shape(lambda k: gh.init(cfg, k), jax.random.PRNGKey(0))))
    assert 3.18e9 < n < 3.20e9           # 3.19 G parameters, 6.38 GB in bf16


def test_the_configurations_weights_are_the_programs_but_for_the_output_gain():
    """``benchmark/families/granite_hybrid.py init`` (what the harness draws
    a cell's weights with, through ``module()``) is the program's ``init``
    with each layer's two output projections ``out_gain`` times larger -
    21.6 at the published sizes - and nothing else moved; the program's own
    ``init`` knows nothing of the benchmark's served-token tolerance."""
    cfg = gh.GraniteHybridConfig.tiny()
    key = jax.random.PRNGKey(4)
    plain, scaled = gh.init(cfg, key), family.module().init(cfg, key)
    gain = family.out_gain(cfg)
    for kind in ("mamba", "attn"):
        for name, leaf in plain[kind].items():
            want = gain if (kind, name) in family.OUT_PROJECTIONS else 1.0
            np.testing.assert_allclose(scaled[kind][name], leaf * want,
                                       rtol=1e-6)
    assert np.array_equal(scaled["embed"], plain["embed"])
    assert family.out_gain(gh.GraniteHybridConfig()) == pytest.approx(
        21.6, abs=0.05)
    assert family.module().apply_paged is gh.apply_paged


def test_state_costs_count_layers_by_kind():
    from benchmark.harness import costs_ssm
    from benchmark.harness.manifest import Cell

    cell = Cell("granite-4.0-h-micro.serve-chat-64")
    assert costs_ssm.layer_counts(cell.model) == {"attention": 4, "mamba": 36}
    row = costs_ssm.state_bytes_per_row(cell.model, cell.role)
    assert row == 64 * 64 * 128 * 4                     # 2.10 MB
    assert costs_ssm.decode_update_floor_bytes(cell.model, cell.role, 64) \
        == 2 * 64 * row


def test_ssm_readers_report_nothing_where_the_program_names_nothing():
    """On a program without the mixer's scopes, spans or kernel (the parent
    commit, any other family) both new readers return None and do not
    raise."""
    from benchmark.readers import scope_share_ssm, ssm_decode_roofline

    assert scope_share_ssm.read({"trace": None}, ["ssm_state"]) is None
    assert ssm_decode_roofline.read({"trace": None, "peaks": None},
                                    "ssm_decode_update") is None
