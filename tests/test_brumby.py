"""Brumby (``models/brumby.py``, ISSUE 55) against the benchmark's plain
reference (``benchmark/reference/brumby.py`` - the one reference, not a copy)
at a small size on the CPU: three power-retention layers, a group of two
query heads a key-value head, head size 16, seeded random weights, float32
on both sides. Everything is compared in LOGITS: the full forward in both of
its forms, chunked prefill whose chunks end off the retention's tile, decode
through the state, the two kernels interpreted, and the engine's slots with
NO block pool behind them.

Tolerance. Program and reference both compute in float32 in another order
of operations (the program through its tiled ``phi`` and the state, the
reference through the quadratic form): the largest difference measured over
every path below is 4e-5 of unit-variance logits (the decode steps). ``TOL``
= 2e-4 (Granite's and Nemotron's) is five times that and a five-hundredth of
what the NEAREST wrong form gives (the state rounded to bfloat16: 0.10 at
the largest logit in the program, 2.3 in the reference with 0.013 at its
median row; every other variant 2.9 and up, 0.30 at the median row), so a
bf16 state, or any of the variants, fails it.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import brumby as family
from benchmark.reference import brumby as reference
from benchmark.reference import brumby_variants as variants
from deepspeed_tpu.inference.engine_v2 import (RecurrentStateError,
                                               build_engine_v2)
from deepspeed_tpu.models import brumby as bm
from deepspeed_tpu.ops import retention
from deepspeed_tpu.ops.pallas import retention as kernels

TOL = 2e-4
ENGINE = {"dtype": "float32", "prefill_bucket": 8, "split_prefill_chunk": 16,
          "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                     "block_size": 8}}


def published(**kw):
    """The published keys at the test size (the release's ratios: untied
    tables, per-head q/k norms, a KV group of two)."""
    hf = dict(
        attention_bias=False, head_dim=16, hidden_act="silu", hidden_size=64,
        intermediate_size=128, max_position_embeddings=256,
        max_window_layers=3, model_type="brumby", num_attention_heads=4,
        num_hidden_layers=3, num_key_value_heads=2, rms_norm_eps=1e-6,
        rope_scaling=None, rope_theta=10000, sliding_window=None,
        tie_word_embeddings=False, use_sliding_window=False, vocab_size=256)
    hf.update(kw)
    return hf


def build(**kw):
    """The configuration, its seeded weights in float32 - the norms' weights
    too, which ``init`` leaves at one: a weight that went unused would
    otherwise pass - and a row of tokens."""
    hf = published(**kw)
    cfg = family.build_cfg(hf, compute_dtype="float32", retention_tile=8)
    params = bm.init(cfg, jax.random.PRNGKey(0))
    for i, name in enumerate(("attn_norm", "mlp_norm", "q_norm", "k_norm")):
        leaf = params["layers"][name]
        params["layers"][name] = 1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(10 + i), leaf.shape)
    params["final_norm"] = 1.0 + 0.2 * jax.random.normal(
        jax.random.PRNGKey(20), params["final_norm"].shape)
    row = np.random.default_rng(0).integers(0, 256, 72)
    return hf, cfg, params, row


def plain(params):
    """The reference's weights with no program beside them."""
    weights = family.Weights(params)
    weights.program = None
    return weights


@pytest.fixture(scope="module")
def model():
    hf, cfg, params, row = build()
    want = reference.logits(hf, plain(params), row)
    return hf, cfg, params, row, want


@functools.partial(jax.jit, static_argnums=(0,))
def _paged_call(cfg, params, cache, padded, start, n, slot):
    return bm.apply_paged(cfg, params, padded, cache,
                          jnp.zeros((1, 1), jnp.int32), start[None],
                          valid=jnp.arange(padded.shape[1])[None] < n,
                          slots=slot[None])


def paged_logits(cfg, params, row, calls, slot=1, slots=3):
    """Logits of ``row`` fed through ``apply_paged`` call by call:
    ``calls`` = ``(tokens in the call, width the call is padded to)``."""
    with jax.default_matmul_precision("highest"):
        cache = bm.init_paged_cache(cfg, 0, 0, slots=slots)
        out, start = [], 0
        for n, width in calls:
            padded = np.zeros((1, width), np.int32)
            padded[0, :n] = row[start:start + n]
            logits, cache = _paged_call(
                cfg, params, cache, jnp.asarray(padded), jnp.int32(start),
                jnp.int32(n), jnp.int32(slot))
            out.append(np.asarray(logits[0, :n]))
            start += n
    return np.concatenate(out), cache


# (tokens, padded width) of each call. The retention's tile is 8: chunks of
# 13, 2 and 1 end off it, and a chunk of 21 in a width of 24 pads inside one
PATHS = {
    "chunks_off_the_tile": [(13, 16), (2, 16), (1, 16), (16, 16), (21, 24),
                            (19, 24)],
    "prefill_then_32_decode_steps": [(40, 48)] + [(1, 1)] * 32,
}


@pytest.mark.parametrize("form", ["chunked", "quadratic"])
def test_full_forward_agrees_with_the_plain_reference(model, form):
    hf, cfg, params, row, want = model
    with jax.default_matmul_precision("highest"):
        got = bm.apply(cfg, params, jnp.asarray(row[None]), form=form)[0]
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
    wrong = variants.logits(variant, hf, plain(params), row)
    assert float(np.abs(wrong - want).max()) > 3 * TOL
    assert float(np.median(np.abs(wrong - want).mean(-1))) > 3 * TOL


def test_a_bfloat16_state_in_the_program_fails_the_tolerance(model):
    hf, cfg, params, row, want = model
    low = dataclasses.replace(cfg, state_dtype="bfloat16")
    got, cache = paged_logits(low, params, row,
                              PATHS["prefill_then_32_decode_steps"])
    assert cache["ret"].dtype == jnp.bfloat16
    assert float(np.abs(got - want[:len(got)]).max()) > 3 * TOL


def test_the_references_recurrence_is_its_quadratic_form(model):
    """The reference's own cross-check: the layer as a recurrence over the
    SMALLEST symmetric state (``d (d + 1) / 2`` products) gives the
    quadratic form's outputs."""
    hf, _, params, row, _ = model
    with jax.default_matmul_precision("highest"):
        w = plain(params).layer(1)
        x = plain(params).embed[jnp.asarray(row)]
        q, k, v, log_g = reference._mixer_in(x, w, reference._freeze(hf),
                                             reference.RIGHT)
        a = reference.quadratic_retention(q, k, v, log_g)
        b = reference.recurrent_retention(q, k, v, log_g)
    assert reference.phi(k).shape[-1] == 16 * 17 // 2
    assert float(jnp.abs(a - b).max()) < 1e-4


# --------------------------------------------------------------------------- #
# the ops: the layout, the three forms, the kernels interpreted
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("d", [8, 16, 128])
def test_phi_of_the_tiled_layout_squares_the_dot_product(d):
    a, b = jax.random.normal(jax.random.PRNGKey(d), (2, 7, d))
    fa, fb = retention.phi(a), retention.phi(b)
    rows = sum(8 * (d - 8 * j) for j in range(d // 8))
    assert fa.shape == (7, rows) == (7, retention.phi_rows(d))
    assert rows == {8: 64, 16: 192, 128: 8704}[d]
    want = jnp.sum(a * b, -1) ** 2
    assert float(jnp.abs(jnp.sum(fa * fb, -1) - want).max()) \
        < 1e-5 * float(want.max())
    m, n, w = retention.phi_index(d)
    np.testing.assert_allclose(fa, a[:, m] * a[:, n] * w, rtol=1e-6)
    # row m's products start at its own block of 8: whole sublane tiles
    assert all(n[m == r][0] == 8 * (r // 8) for r in range(d))


def _operands(b=2, t=21, nkv=2, g=2, d=16, seed=0, gate=2.0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (b, t, nkv * g, d)),
            jax.random.normal(k[1], (b, t, nkv, d)),
            jax.random.normal(k[2], (b, t, nkv, d)),
            jax.nn.log_sigmoid(gate + jax.random.normal(k[3], (b, t, nkv))))


def test_the_quadratic_chunked_and_recurrent_forms_agree():
    q, k, v, log_g = _operands()
    zeros = (jnp.zeros((2, 2, 16, 192)), jnp.zeros((2, 2, 192)))
    with jax.default_matmul_precision("highest"):
        quad = retention.retention_quadratic(q, k, v, log_g)
        rec, S, z = retention.retention_recurrence(q, k, v, log_g, *zeros)
        for tile in (8, 5, 64):     # off the length, and one tile for all
            got, S_c, z_c = retention.retention_chunked(q, k, v, log_g,
                                                        *zeros, tile)
            assert float(jnp.abs(got - rec).max()) < 1e-4
            assert float(jnp.abs(S_c - S).max()) < 1e-4
            assert float(jnp.abs(z_c - z).max()) < 1e-4
    assert float(jnp.abs(quad - rec).max()) < 1e-4


def _pool(slots=3, layers=2, nkv=2, d=16, seed=5):
    """A pool of live state - each row what six random tokens leave, so that
    every normaliser is a sum of squares as a served one is -, the trash row
    POISONED."""
    rows = layers * slots
    _, k, v, log_g = _operands(b=rows, t=6, nkv=nkv, d=d, seed=seed)
    lanes = retention.phi_rows(d)
    _, S, z = retention.retention_recurrence(
        jnp.zeros((rows, 6, nkv, d)), k, v, log_g,
        jnp.zeros((rows, nkv, d, lanes)), jnp.zeros((rows, nkv, lanes)))
    shape = retention.state_shape(layers, slots, nkv, d)
    live = retention.state_from_heads(S, z, shape[2]).reshape(
        (layers, slots) + shape[2:])
    return jnp.concatenate(
        [live, jnp.full((layers, 1) + shape[2:], jnp.nan)], axis=1)


def _states(pool, layer, rows, nkv=2, d=16):
    S, z = retention.state_to_heads(pool[layer, jnp.asarray(rows)], nkv, d)
    return np.concatenate([np.asarray(S).reshape(len(rows), -1),
                           np.asarray(z).reshape(len(rows), -1)], 1)


def test_interpreted_decode_kernel_is_one_token_of_the_recurrence():
    """Three rows of a call on a pool of three slots: slot 1 live, a row
    aimed at the trash row (whose state is NaN: it must poison nothing), a
    fresh row on slot 0 whose old state must not be read. The live rows'
    outputs and state are the XLA twin's, every other row of the pool is
    bit-equal to what it was."""
    q, k, v, log_g = (a[0, :3] for a in _operands())
    pool = _pool()
    rows = jnp.asarray([1, 3, 0], jnp.int32)
    fresh = jnp.asarray([False, False, True])
    with jax.default_matmul_precision("highest"):
        want_pool, want = retention.retention_decode_update_xla(
            pool, 1, rows, fresh, q, k, v, log_g)
        got_pool, got = kernels.retention_decode_update(
            pool, 1, rows, fresh, q, k, v, log_g)
    live = np.asarray([0, 2])
    assert np.isfinite(np.asarray(got)[live]).all()
    assert float(np.abs(np.asarray(got) - np.asarray(want))[live].max()) < 1e-4
    np.testing.assert_allclose(_states(got_pool, 1, [1, 0]),
                               _states(want_pool, 1, [1, 0]), atol=1e-5)
    np.testing.assert_array_equal(got_pool[0, :3], pool[0, :3])
    np.testing.assert_array_equal(got_pool[1, 2], pool[1, 2])
    # the fresh row read zeros: its state is the token's own rank-one term
    fk = retention.phi(k[2])
    S, z = retention.state_to_heads(got_pool[1, 0][None], 2, 16)
    np.testing.assert_allclose(z[0], fk, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(S[0], v[2][:, :, None] * fk[:, None, :],
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("t,tile", [(21, 8), (8, 8), (5, 8), (40, 16)])
def test_interpreted_chunk_kernel_is_the_chunked_form(t, tile):
    """Chunk lengths off the tile (21 and 40 pad their last tile, 5 is
    shorter than one), two rows of a call: one continues slot 2's state,
    one starts fresh on slot 0. Padding inside the length arrives as the
    family makes it (``k = v = 0``, ``log g = 0``) and moves no state."""
    q, k, v, log_g = _operands(t=t, seed=t)
    real = (jnp.arange(t) < t - 2)[None, :, None]           # row 1: 2 padded
    pad = lambda a, fill=0.0: a.at[1].set(jnp.where(
        real[0] if a.ndim == 3 else real[0, :, :, None], a[1], fill))
    k, v, log_g = pad(k), pad(v), pad(log_g)
    pool = _pool()
    rows, fresh = jnp.asarray([2, 0], jnp.int32), jnp.asarray([False, True])
    with jax.default_matmul_precision("highest"):
        want_pool, want = retention.retention_chunk_xla(
            pool, 0, rows, fresh, q, k, v, log_g, tile=tile)
        got_pool, got = kernels.retention_chunk(
            pool, 0, rows, fresh, q, k, v, log_g, tile=tile)
        # the state after the padded row is the state after its real tokens
        cut = lambda a: a[1:, :t - 2]
        _, S, z = retention.retention_recurrence(
            cut(q), cut(k), cut(v), cut(log_g),
            jnp.zeros((1, 2, 16, 192)), jnp.zeros((1, 2, 192)))
    # (a padded row's own output is unspecified: its real rows are held)
    assert float(jnp.abs(got - want)[0].max()) < 1e-4
    assert float(jnp.abs(got - want)[1, :t - 2].max()) < 1e-4
    np.testing.assert_allclose(_states(got_pool, 0, [2, 0]),
                               _states(want_pool, 0, [2, 0]), atol=2e-5)
    np.testing.assert_allclose(
        _states(got_pool, 0, [0])[0],
        np.concatenate([np.asarray(S).ravel(), np.asarray(z).ravel()]),
        atol=2e-5)
    np.testing.assert_array_equal(got_pool[1, :3], pool[1, :3])
    np.testing.assert_array_equal(got_pool[0, 1], pool[0, 1])


def test_a_chunk_row_aimed_at_the_trash_row_reads_nothing_of_it():
    q, k, v, log_g = _operands(b=1, t=8)
    pool = _pool()
    got_pool, got = kernels.retention_chunk(
        pool, 0, jnp.asarray([3], jnp.int32), jnp.asarray([False]), q, k, v,
        log_g, tile=8)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got_pool[:, :3], pool[:, :3])


# --------------------------------------------------------------------------- #
# the decoded rows are held by themselves
# --------------------------------------------------------------------------- #
def test_a_fault_in_the_single_token_call_alone_is_told_by_the_decoded_rows(
        model):
    """``reference.held`` reads a probe's decoded rows BY THEMSELVES, under a
    limit of their own (``roles.serve.held``): a state update that takes
    head 0's gate for every head, planted in the single-token program alone
    over the right program's prefilled pool (``tools/brumby_check.py`` does
    the same on the chip), leaves the chunked part's rows what they were and
    comes out by the decoded rows' limit and by no other."""
    from benchmark.tools.brumby_check import one_gate_update

    hf, _, params, row, want = model
    program = family.Program(params, {
        "program_options": {"state_dtype": "float32",
                            "compute_dtype": "float32", "retention_tile": 8},
        "held": {}, "weights_dtype": "float32",
        "engine": {"split_prefill_chunk": 64}})
    decode = reference.decode_rows(len(row))
    assert decode == len(row) // 2 and reference.decode_rows(735) == 96
    n = len(row) - decode
    with jax.default_matmul_precision("highest"):
        pre, cache = program.prefill(hf, row, n)
        pool = jax.device_get(cache)
        right = np.concatenate([pre, program.decode(hf, row, n, cache)])
        np.testing.assert_array_equal(
            right, program.logits(hf, row, decode))
        with one_gate_update():
            planted = family.paged_call.__wrapped__(program.cfg,
                                                    program.dtype.name)
            wrong = np.concatenate([pre, program.decode(
                hf, row, n, jax.device_put(pool), call=planted)])
    assert len(right) == len(row)
    limits = {"logits_mean_abs_diff": TOL, "decode_logits_mean_abs_diff": TOL}
    seen = reference.held(right, want, decode)
    assert seen["decode_rows"] == decode and seen["rows"] == len(row)
    assert reference.disagreements(seen, limits) == []
    seen = reference.held(wrong, want, decode)
    why = reference.disagreements(seen, limits)
    assert len(why) == 1 and "decoded" in why[0], why
    assert seen["logits_mean_abs_diff"] <= TOL
    assert seen["decode_logits_mean_abs_diff"] > 20 * TOL


# --------------------------------------------------------------------------- #
# the engine: slots and no blocks
# --------------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def served():
    hf, cfg, params, _ = build()
    eng = build_engine_v2(bm, cfg, params, config=ENGINE)
    return hf, cfg, params, eng


def gaps(served, eng, prompt, out):
    """How far below the reference's top each served token lies."""
    tokens = np.asarray(list(prompt) + out[:-1], np.int32)
    want = reference.logits(served[0], plain(eng.params),
                            tokens)[len(prompt) - 1:]
    return want.max(-1) - want[np.arange(len(out)), out]


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lengths]


def state_of(eng, slot):
    return np.asarray(eng.cache["ret"][:, slot])


def test_slots_keep_release_and_restart_their_state(served):
    """One module-scoped engine through Granite's slot tests in a row: a
    decode beside a free and a prefilling slot leaves their rows bit-equal
    and moves its own; a prompt that completes beside a decode advances its
    state once; a retired slot leaks nothing into the next sequence (its
    state restarts from zeros); preemption and readmission continue the
    stream. Every served token is the reference's top, and the step's span
    arguments carry the retention's rows."""
    eng = served[3]
    a, b = prompts(11, 40)
    out = [eng.put(1, a)]
    eng.put_split(2, b)
    out.append(eng.step()[1])                # runs b's first chunk too
    slots = {u: eng.state.seqs[u].slot for u in (1, 2)}
    free = [s for s in range(4) if s not in slots.values()]
    held = dict(eng._pending_prefill)
    eng._pending_prefill.clear()             # hold b where it is
    before = {s: state_of(eng, s) for s in range(4)}
    out.append(eng.step()[1])
    for s in free + [slots[2]]:
        np.testing.assert_array_equal(state_of(eng, s), before[s])
    assert np.abs(state_of(eng, slots[1]) - before[slots[1]]).max() > 0
    assert eng.last_step["ssm_rows"] == 1 and eng.last_step["ssm_tokens"] == 1
    assert eng.family.state_rows(eng.family.cfg, 3, 16) == {
        "retention_rows": 3, "retention_chunk_rows": 16}
    eng._pending_prefill.update(held)
    second = []
    while len(second) < 5:
        step = eng.step()
        out += [step[1]] if 1 in step else []
        second += [step[2]] if 2 in step else []
    assert eng.mixed_steps > 0
    assert float(gaps(served, eng, a, out).max()) == 0.0
    assert float(gaps(served, eng, b, second).max()) == 0.0
    # a retired slot: nothing cleared it, and the next sequence starts fresh
    slot = eng.state.seqs[1].slot
    eng.finish(2)
    eng.finish(1)                            # the next admission's slot
    assert np.abs(state_of(eng, slot)).max() > 0
    (third,) = prompts(9, seed=1)
    out = [eng.put(3, third)]
    assert eng.state.seqs[3].slot == slot
    out += [eng.step()[3] for _ in range(4)]
    # preemption and readmission (recomputation from offset 0)
    parked = eng.park(3)
    eng.put(9, prompts(30, seed=3)[0])           # takes the slot over
    eng.step()
    out += eng.resume(parked, split=True)
    while len(out) < 9:
        tok = eng.step().get(3)
        out += [] if tok is None else [tok]
    assert float(gaps(served, eng, third, out).max()) == 0.0
    assert eng.finish(3) == out
    eng.finish(9)
    eng.state.debug_check()
    eng.debug_check_cache()


def test_admission_is_bounded_by_slots_and_no_kv_pool_is_allocated(served):
    """The family declares state leaves and NO leaf with a block axis: the
    engine allocates no KV byte, counts no block, and a free slot is all an
    admission needs - a fifth sequence waits for a slot however short it
    is, and a sequence grows past what any block table would hold."""
    hf, cfg, _, eng = served
    per_slot = bm.state_slot_bytes(cfg)
    assert per_slot == 3 * (2 * 16 + 8) * 192 * 4
    assert set(eng.cache) == {"ret"} and eng.cache["ret"].dtype == jnp.float32
    assert eng.cache["ret"].shape == (3, 5, 40, 192)
    room = eng.kv_headroom()
    assert room["kv_bytes"] == 0 and room["total_blocks"] == 0
    assert room["state_bytes_per_slot"] == per_slot
    assert room["state_bytes_free"] == room["state_bytes_total"] == 4 * per_slot
    st = eng.state
    assert st.blockless and st.blocks_needed(10 ** 6) == 0
    assert st.table_width == 1
    for uid, prompt in enumerate(prompts(9, 9, 9, 9), 31):
        assert st.can_admit(len(prompt))
        eng.put(uid, prompt)
    assert not st.can_admit(1) and st.free_slots == 0
    assert all(d.blocks == [] for d in st.seqs.values())
    assert st.growth_blocks_short(n=64) == 0
    for _ in range(3):
        eng.step()
    st.debug_check()
    eng.finish(31)
    assert st.can_admit(200)
    for uid in (32, 33, 34):
        eng.finish(uid)
    # a Granite engine beside it still counts its blocks
    from test_granite_hybrid import ENGINE as granite_engine
    from test_granite_hybrid import build as granite

    from deepspeed_tpu.models import granite_hybrid as gh

    _, gcfg, gparams, _ = granite()
    other = build_engine_v2(gh, gcfg, gparams, config=granite_engine)
    assert not other.state.blockless and other.kv_headroom()["kv_bytes"] > 0


REFUSED_AT_CONFIGURATION = {
    "prefix_cache": {"prefix_cache": {"enabled": True}},
    "host_spill": {"prefix_cache": {"enabled": False, "host_spill": True}},
    "speculative": {"speculative": {"enabled": True}},
    "kv_quant": {"kv_quant": {"enabled": True}},
}


@pytest.mark.parametrize("feature", sorted(REFUSED_AT_CONFIGURATION))
def test_what_needs_state_snapshots_is_refused_at_configuration(served,
                                                                feature):
    _, cfg, params, _ = served
    with pytest.raises(RecurrentStateError, match="recurrent state"):
        build_engine_v2(bm, cfg, params, config={
            **ENGINE, **REFUSED_AT_CONFIGURATION[feature]})


@pytest.mark.parametrize("call", ["fork", "export_kv_blocks",
                                  "import_kv_blocks"])
def test_what_needs_state_snapshots_is_refused_at_its_call(served, call):
    from deepspeed_tpu.inference.engine_v2 import _REFUSALS

    eng = served[3]
    assert eng._refusals == [_REFUSALS["recurrent_state"]]
    eng.put(21, prompts(9)[0])
    args = {"fork": (21, 22), "export_kv_blocks": (21,),
            "import_kv_blocks": ([], [])}[call]
    with pytest.raises(RecurrentStateError, match=call):
        getattr(eng, call)(*args)
    eng.state.debug_check()                  # nothing half done
    eng.finish(21)


def test_training_the_dense_cache_and_other_retentions_are_refused_by_name(
        served):
    _, cfg, params, _ = served
    with pytest.raises(NotImplementedError, match="serving family"):
        bm.loss_fn(cfg, params, {"tokens": jnp.zeros((1, 8), jnp.int32)})
    with pytest.raises(NotImplementedError, match="build_engine_v2"):
        bm.init_cache(cfg, 1, 8)
    with pytest.raises(NotImplementedError, match="build_engine_v2"):
        bm.apply_cached(cfg, params, None, None, None)
    with pytest.raises(ValueError, match="degree 2"):
        bm.init(dataclasses.replace(cfg, retention_degree=4),
                jax.random.PRNGKey(0))


def test_importing_the_package_loads_neither_the_family_nor_its_kernels():
    import subprocess
    import sys

    code = ("import sys, deepspeed_tpu, deepspeed_tpu.models, "
            "deepspeed_tpu.inference.engine_v2\n"
            "bad = [m for m in sys.modules if m.endswith(('brumby', "
            "'ops.retention', 'pallas.retention'))]\n"
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})


def test_the_kernels_refuse_a_state_that_is_not_float32_by_name():
    q, k, v, log_g = _operands(b=1, t=8)
    pool = _pool().astype(jnp.bfloat16)
    rows, fresh = jnp.asarray([0], jnp.int32), jnp.asarray([False])
    with pytest.raises(NotImplementedError, match="float32 state"):
        kernels.retention_chunk(pool, 0, rows, fresh, q, k, v, log_g)
    with pytest.raises(NotImplementedError, match="float32 state"):
        kernels.retention_decode_update(pool, 0, rows, fresh, q[:, 0],
                                        k[:, 0], v[:, 0], log_g[:, 0])
