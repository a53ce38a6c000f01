"""Nemotron-H (``models/nemotron_h.py``, ISSUE 50) against the benchmark's
plain reference (``benchmark/reference/nemotron_h.py`` - the one reference,
not a copy) at a small size on the CPU: all three kinds of layer in a
pattern with no period, two groups of B and C, eight experts, seeded random
weights (the configuration's rule: a drawn choice bias), float32 on both
sides. Everything is compared in LOGITS: the full forward, chunked prefill
whose chunks end off the SSD block and off the convolution's tail, decode
through the state, one chip's share of the experts, and the engine's slots.

Tolerance. Program and reference both compute in float32 in another order
of operations: the largest difference measured over every path below is
1e-5 of unit-variance logits. ``TOL`` = 2e-4 (Granite's) is twenty times
that and a tenth of what the NEAREST wrong variant gives (the recurrent
state rounded to bfloat16 after every token: 8e-3 in the reference; every
other variant 3 to 5), so a bf16 state, or any of the variants, fails it.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import nemotron_h as family
from benchmark.reference import nemotron_h as reference
from benchmark.reference import nemotron_h_variants as variants
from deepspeed_tpu.inference.engine_v2 import (RecurrentStateError,
                                               build_engine_v2)
from deepspeed_tpu.models import _paged
from deepspeed_tpu.models import nemotron_h as nh
from deepspeed_tpu.ops import ssm

TOL = 2e-4
PATTERN = "MEM*EMEME"


def published(**kw):
    """The published keys at the test size (the ratios of the release:
    inner = heads x head size and NOT expand x hidden, the shared expert
    twice a routed one, KV group 2; an expert over one lane tile wide and
    off it, 136, so that the bank is LAID OUT wider - 256, zeros past 136:
    ``NemotronHConfig.expert_lanes`` - in every test below)."""
    hf = dict(
        attention_bias=False, chunk_size=16, conv_kernel=4, expand=3,
        head_dim=16, hidden_size=32, hybrid_override_pattern=PATTERN,
        intermediate_size=136, layer_norm_epsilon=1e-5, mamba_head_dim=8,
        mamba_hidden_act="silu", mamba_num_heads=8, mamba_proj_bias=False,
        max_position_embeddings=256, mlp_bias=False, mlp_hidden_act="relu2",
        model_type="nemotron_h", moe_intermediate_size=136,
        moe_shared_expert_intermediate_size=272, n_group=1, n_groups=2,
        n_routed_experts=8, n_shared_experts=1, norm_topk_prob=True,
        num_attention_heads=4, num_experts_per_tok=3,
        num_hidden_layers=len(PATTERN), num_key_value_heads=2,
        routed_scaling_factor=2.5, rope_theta=10000, ssm_state_size=16,
        tie_word_embeddings=False, topk_group=1, use_bias=False,
        use_conv_bias=True, vocab_size=256, num_experts=8)
    hf.update(kw)
    return hf


def build(**kw):
    """The configuration, its seeded weights in float32 - the norms' weights
    and ``D`` too, which ``init`` leaves at one: a weight that went unused
    would otherwise pass - and a row of tokens."""
    hf = published(**kw)
    cfg = family.build_cfg(hf, compute_dtype="float32")
    params = family.init(cfg, jax.random.PRNGKey(0))
    for kind, names in (("mamba", ("norm", "gate_norm", "D")),
                        ("moe", ("norm",)), ("attn", ("norm",))):
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
    hf, cfg, params, row = build()
    want = reference.logits(hf, family.Weights(params), row)
    return hf, cfg, params, row, want


@functools.partial(jax.jit, static_argnums=(0,))
def _paged_call(cfg, params, cache, padded, table, start, n, slot):
    return nh.apply_paged(cfg, params, padded, cache, table, start[None],
                          valid=jnp.arange(padded.shape[1])[None] < n,
                          slots=slot[None])


def paged_logits(cfg, params, row, calls, slot=1, slots=3, block=8):
    """Logits of ``row`` fed through ``apply_paged`` call by call:
    ``calls`` = ``(tokens in the call, width the call is padded to)``."""
    with jax.default_matmul_precision("highest"):
        cache = nh.init_paged_cache(cfg, 24, block, dtype=jnp.float32,
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
# convolution's tail 3 rows: chunks of 13, 2 and 1 end off both
PATHS = {
    "chunks_off_the_block_and_the_tail": [(13, 16), (2, 16), (1, 16),
                                          (16, 16), (21, 24), (19, 24)],
    "prefill_then_32_decode_steps": [(40, 48)] + [(1, 1)] * 32,
}


def test_full_forward_agrees_with_the_plain_reference(model):
    hf, cfg, params, row, want = model
    with jax.default_matmul_precision("highest"):
        got = nh.apply(cfg, params, jnp.asarray(row[None]))[0]
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
    wrong = variants.logits(variant, hf, family.Weights(params), row)
    assert float(np.abs(wrong - want).max()) > 3 * TOL


def test_d_inner_from_expand_cannot_read_the_published_in_proj(model):
    """The thirteenth wrong reading is told apart by shapes: were the
    mixer's inner width ``expand x hidden``, ``in_proj`` would have other
    columns than the weights' (and the program's) ``[z | xBC | dt]``."""
    hf, cfg, params, _, _ = model
    have = params["mamba"]["in_proj"].shape[-1] \
        + params["mamba"]["dt_proj"].shape[-1]
    assert have == 2 * cfg.d_inner + 2 * 2 * 16 + 8
    assert cfg.d_inner == 8 * 8 != hf["expand"] * hf["hidden_size"]
    assert variants.expand_in_proj_width(hf) != have


def test_a_bfloat16_state_in_the_program_fails_the_tolerance(model):
    hf, cfg, params, row, want = model
    low = dataclasses.replace(cfg, state_dtype="bfloat16")
    got, _ = paged_logits(low, params, row,
                          PATHS["prefill_then_32_decode_steps"])
    assert float(np.abs(got - want[:len(got)]).max()) > 3 * TOL


def _recurrence_inputs(groups, b=2, t=37, H=8, P=8, N=16):
    k = jax.random.split(jax.random.PRNGKey(1), 7)
    x = jax.random.normal(k[0], (b, t, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, t, H)))
    dt = dt.at[1, 30:].set(0.0)           # a row's padding
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    B, C = (jax.random.normal(k[i], (b, t, groups, N)) for i in (3, 4))
    h0 = jax.random.normal(k[5], (b, H, P, N))
    return x, dt, A, B, C, h0


@jax.jit
def _by_head(x, dt, A, B, C, h0):
    """The recurrence a head at a time with ITS group's B and C, through the
    one-group form: what groups mean, with no grouped code in the way."""
    H, G = x.shape[2], B.shape[2]
    ys, hs = zip(*(ssm.ssm_recurrence(
        x[:, :, h:h + 1], dt[:, :, h:h + 1], A[h:h + 1],
        B[:, :, h // (H // G)], C[:, :, h // (H // G)], h0[:, h:h + 1])
        for h in range(H)))
    return jnp.concatenate(ys, axis=2), jnp.concatenate(hs, axis=1)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_chunked_scan_is_the_token_by_token_recurrence_by_groups(groups):
    x, dt, A, B, C, h0 = _recurrence_inputs(groups)
    y, h = _by_head(x, dt, A, B, C, h0)
    y1, h1 = jax.jit(ssm.ssm_recurrence)(x, dt, A, B, C, h0)
    assert float(jnp.abs(y - y1).max()) < 1e-5
    assert float(jnp.abs(h - h1).max()) < 1e-5
    scan = jax.jit(ssm.ssd_chunked_scan, static_argnums=6)
    for chunk in (8, 16, 64):             # blocks off, on and over the length
        y2, h2 = scan(x, dt, A, B, C, h0, chunk)
        assert float(jnp.abs(y - y2).max()) < 1e-4
        assert float(jnp.abs(h - h2).max()) < 5e-5
    if groups == 1:     # one group as [.., N]: the form before groups
        y3, h3 = scan(x, dt, A, B[:, :, 0], C[:, :, 0], h0, 16)
        assert float(jnp.abs(y - y3).max()) < 1e-4
        assert float(jnp.abs(h - h3).max()) < 5e-5


@pytest.mark.parametrize("lanes", [None, 128])
@pytest.mark.parametrize("groups", [1, 2, 8])
def test_interpreted_decode_kernel_is_one_token_of_the_recurrence(groups,
                                                                  lanes,
                                                                  monkeypatch):
    """``ssm_decode_update`` (Pallas in interpret mode) and its XLA twin
    against one token of the token-by-token recurrence, on a pool whose
    layers differ. ``lanes`` 128 of 256: a block narrower than a group at 1
    and 2 groups, one that spans four at 8."""
    from deepspeed_tpu.ops.pallas import ssm as kernels

    if lanes:
        monkeypatch.setattr(kernels, "_LANES", lanes)
    k = jax.random.split(jax.random.PRNGKey(2), 8)
    L, S, N, T, H, P, b = 2, 5, 16, 8, 32, 8, 4
    HP = H * P
    pool = jax.random.normal(k[0], (L, S + 1, N + T, HP), jnp.float32)
    rows = jnp.asarray([2, S, 0, S])                # S: the trash row
    fresh = jnp.asarray([False, False, True, False])
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, H)))
    A = -jnp.exp(jax.random.normal(k[5], (H,)))
    x = jax.random.normal(k[2], (b, H, P))
    B, C = (jax.random.normal(k[i], (b, groups, N)) for i in (3, 4))
    per_lane = lambda a: jnp.repeat(a, P, axis=-1)
    decay, dtx = per_lane(jnp.exp(dt * A)), per_lane(dt) * x.reshape(b, HP)
    live = np.asarray([0, 2])
    layer = 1
    h0 = jnp.where(fresh[:, None, None, None], 0.0,
                   ssm.state_to_heads(pool[layer, rows, :N], H))
    y, h = _by_head(x[:, None], dt[:, None], A, B[:, None], C[:, None], h0)
    want_y, want_h = y[:, 0].reshape(b, HP), ssm.state_from_heads(h)
    for op in (ssm.ssm_decode_update_xla, kernels.ssm_decode_update):
        for Bc in ((B, C), (B[:, 0], C[:, 0])) if groups == 1 else ((B, C),):
            got, y2 = op(pool, jnp.int32(layer), rows, fresh, decay, dtx,
                         *Bc)
            np.testing.assert_allclose(np.asarray(y2)[live],
                                       np.asarray(want_y)[live], rtol=1e-4,
                                       atol=1e-4)
            np.testing.assert_allclose(
                np.asarray(got)[layer, np.asarray(rows)[live], :N],
                np.asarray(want_h)[live], rtol=1e-5, atol=1e-5)
            np.testing.assert_array_equal(np.asarray(got)[0],
                                          np.asarray(pool)[0])
            np.testing.assert_array_equal(np.asarray(got)[layer, :S, N:],
                                          np.asarray(pool)[layer, :S, N:])


def test_the_scan_nest_follows_this_pattern_and_granites_from_one_function():
    from deepspeed_tpu.models import granite_hybrid as gh

    assert gh.layer_plan is _paged.layer_plan
    granite = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4
    assert _paged.layer_plan(granite * 4) == (
        4, [("mamba", 0, 5), ("attention", 0, 1), ("mamba", 5, 4)],
        {"mamba": 9, "attention": 1})
    periods, runs, each = _paged.layer_plan(nh.PUBLISHED_PATTERN)
    assert (periods, each) == (1, {"M": 23, "E": 23, "*": 6})
    assert runs == [
        (tuple("MEMEM*E"), (0, 0, 1, 1, 2, 0, 2), 5),
        (("M", "E"), (15, 15), 3), ("M", 18, 1), ("*", 5, 1),
        (("E", "M"), (18, 19), 4), ("E", 22, 1)]
    # every layer once, in the pattern's order, each with its kind's weights
    # at its own index among its kind - whatever the pattern
    for pattern in (nh.PUBLISHED_PATTERN, PATTERN, "M*E" * 3, "EEMM*"):
        blocks = {k: (lambda x, w, log, index, k=k: (x + 1, log.at[x].set(
            jnp.stack(["ME*".index(k), w, index])))) for k in "ME*"}
        n, log = jax.jit(lambda: _paged.scan_nest(
            tuple(pattern),
            {k: jnp.arange(pattern.count(k), dtype=jnp.int32) for k in "ME*"},
            jnp.zeros((), jnp.int32),
            jnp.full((len(pattern), 3), -1, jnp.int32), blocks))()
        assert int(n) == len(pattern)
        want = [("ME*".index(k), pattern[:i].count(k), pattern[:i].count(k))
                for i, k in enumerate(pattern)]
        assert [tuple(r) for r in np.asarray(log).tolist()] == want


def test_the_shares_routed_parts_and_one_shared_expert_are_the_uncut_layer(
        model):
    """At the test size two shares of four experts each: each share's
    reference output for ONE sparse layer, less the shared expert it holds
    whole, adds up with ONE shared expert to the uncut reference's layer;
    and the program with a held range agrees with its share's reference."""
    hf, cfg, params, row, _ = model
    weights = family.Weights(params)
    y = jax.random.normal(jax.random.PRNGKey(4), (24, 32))
    whole = weights.layer("E", 1)
    with jax.default_matmul_precision("highest"):
        uncut = reference.experts(y, whole, hf)
        shared = reference.experts(
            y, {**whole, "experts": []}, {**hf, "num_experts": 0})
        total = shared
        for first in (0, 4):
            share_hf = {**hf, "num_experts": 4, "experts_first": first}
            w = {**whole, "experts": whole["experts"][first:first + 4]}
            total = total + reference.experts(y, w, share_hf) - shared
    assert float(jnp.abs(total - uncut).max()) < 1e-5
    assert float(jnp.abs(uncut - shared).max()) > 0.1
    # the program's share against the share's reference, in logits
    share_hf = {**hf, "num_experts": 4, "experts_first": 4}
    share_cfg = family.build_cfg(share_hf, compute_dtype="float32")
    assert share_cfg.experts_held == (4, 4)
    share = jax.tree.map(lambda p: p, params)
    share["moe"] = {**params["moe"],
                    "w_up": params["moe"]["w_up"][:, 4:],
                    "w_down": params["moe"]["w_down"][:, 4:]}
    want = reference.logits(share_hf, family.Weights(share), row[:40])
    got, _ = paged_logits(share_cfg, share, row[:40], [(24, 24), (16, 16)])
    assert float(np.abs(got - want).max()) < TOL
    assert float(np.abs(want - model[4][:40]).max()) > 3 * TOL


def test_the_grouped_form_is_the_slabs_with_a_two_matrix_bank(model,
                                                             one_device):
    """On one device the serving forward takes the grouped matmul (its XLA
    twin and the interpreted kernel) over the stacked two-matrix bank, held
    range and choice bias included: the same logits."""
    from deepspeed_tpu.ops.pallas import grouped_matmul as gm
    from deepspeed_tpu.ops.registry import get_op

    hf, cfg, params, row, want = model
    assert nh._moe(cfg).grouped()
    got, _ = paged_logits(cfg, params, row[:40], [(24, 24), (16, 16)])
    assert float(np.abs(got - want[:40]).max()) < TOL
    k = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(k[0], (4 * 16, 32), jnp.float32)
    up = jax.random.normal(k[1], (2, 3, 32, 24)) * 0.2
    down = jax.random.normal(k[2], (2, 3, 24, 32)) * 0.2
    tile_expert = jnp.asarray([0, 2, 2, 1], jnp.int32)
    tile_rows = jnp.asarray([16, 16, 5, 0], jnp.int32)
    a = gm.moe_grouped_matmul(x, None, up, down, tile_expert, tile_rows, 3,
                              1, tile=16)
    b = gm.moe_grouped_matmul_xla(x, None, up, down, tile_expert, tile_rows,
                                  3, 1, tile=16)
    np.testing.assert_allclose(np.asarray(a)[:37], np.asarray(b)[:37],
                               rtol=2e-5, atol=2e-5)
    rows = x[16:32]
    np.testing.assert_allclose(
        np.asarray(b)[16:32],
        np.asarray(jnp.square(jax.nn.relu(rows @ up[1, 2])) @ down[1, 2]),
        rtol=2e-5, atol=2e-5)
    assert get_op("moe_grouped_matmul") is not None


def test_the_choice_bias_enters_the_choice_alone():
    from deepspeed_tpu.moe.sharded_moe import (top_k_gating,
                                               top_k_gating_compact)

    logits = jax.random.normal(jax.random.PRNGKey(7), (32, 16))
    bias = jax.random.normal(jax.random.PRNGKey(8), (16,)) * 0.3
    kw = dict(score="sigmoid", drop_tokens=False)
    plain = top_k_gating_compact(logits, 4, **kw)
    biased = top_k_gating_compact(logits, 4, bias=bias, **kw)
    s = jax.nn.sigmoid(logits)
    want_idx = jax.lax.top_k(s + bias, 4)[1]
    np.testing.assert_array_equal(np.asarray(biased.topk_idx),
                                  np.asarray(want_idx))
    assert (np.asarray(biased.topk_idx) != np.asarray(plain.topk_idx)).any()
    chosen = jnp.take_along_axis(s, want_idx, axis=1)
    np.testing.assert_allclose(
        np.asarray(biased.gates),
        np.asarray(chosen / chosen.sum(-1, keepdims=True)), rtol=1e-6)
    zero = top_k_gating_compact(logits, 4, bias=jnp.zeros((16,)), **kw)
    np.testing.assert_array_equal(np.asarray(zero.topk_idx),
                                  np.asarray(plain.topk_idx))
    dense = top_k_gating(logits, 4, bias=bias, **kw)
    np.testing.assert_allclose(
        np.asarray(dense.combine_weights.sum(-1)),
        np.asarray(jnp.sum(jax.nn.one_hot(want_idx, 16)
                           * biased.gates[..., None], axis=1)), rtol=1e-6)


# --- the engine's slots ---------------------------------------------------- #
ENGINE = {"dtype": "float32", "prefill_bucket": 8, "split_prefill_chunk": 16,
          "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                     "memory_config_blocks": 64, "block_size": 8}}


def test_a_fault_in_the_single_token_call_alone_is_told_by_the_decoded_rows(
        model):
    """``reference.held`` reads a probe's decoded rows BY THEMSELVES, under a
    limit of their own (``roles.serve.held``): a state update that takes
    group 0's B and C for every head, planted in the single-token program
    alone over the right program's prefilled pools (``tools/
    nemotron_h_check.py`` does the same on the chip), leaves the chunked
    part's rows what they were and comes out by the decoded rows' limit and
    by no other."""
    from benchmark.tools.nemotron_h_check import one_group_update

    hf, _, params, row, want = model
    program = family.Program(params, {
        "program_options": {"state_dtype": "float32", "drop_tokens": False,
                            "compute_dtype": "float32"},
        "held": {}, "weights_dtype": "float32",
        "engine": {"ragged": {"block_size": 8}, "split_prefill_chunk": 64}})
    decode = reference.decode_rows(len(row))
    assert decode == len(row) // 2 and reference.decode_rows(735) == 96
    n = len(row) - decode
    with jax.default_matmul_precision("highest"):
        pre, cache, table = program.prefill(hf, row, n)
        pools = jax.device_get(cache)
        right = np.concatenate(
            [pre, program.decode(hf, row, n, cache, table)])
        np.testing.assert_array_equal(
            right, program.logits(hf, row, decode))
        with one_group_update():
            planted = family.paged_call.__wrapped__(program.cfg,
                                                    program.dtype.name)
            wrong = np.concatenate([pre, program.decode(
                hf, row, n, jax.device_put(pools), table, call=planted)])
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


@pytest.fixture(scope="module")
def served():
    hf, cfg, params, _ = build()
    eng = build_engine_v2(nh, cfg, params, config=ENGINE)
    return hf, cfg, params, eng


def gaps(served, eng, prompt, out):
    """How far below the reference's top each served token lies."""
    hf = served[0]
    tokens = np.asarray(list(prompt) + out[:-1], np.int32)
    weights = family.Weights(eng.params)
    weights.program = None
    want = reference.logits(hf, weights, tokens)[len(prompt) - 1:]
    return want.max(-1) - want[np.arange(len(out)), out]


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lengths]


def state_of(eng, slot):
    return np.asarray(eng.cache["ssm"][:, slot])


def test_slots_keep_release_and_restart_their_state(served):
    """One module-scoped engine through Granite's slot tests in a row: a
    decode beside a free and a prefilling slot leaves their rows bit-equal
    and moves its own; a prompt that completes beside a decode advances its
    state once; a retired slot leaks nothing into the next sequence;
    preemption and readmission continue the stream. Every served token is
    the reference's top, and the spans' arguments carry the state's rows
    AND the experts' at once."""
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
    assert eng.family.moe_rows(eng.family.cfg, 4)["moe_rows_routed"] == 12
    eng._pending_prefill.update(held)
    second = []
    while len(second) < 5:
        step = eng.step()
        out += [step[1]] if 1 in step else []
        second += [step[2]] if 2 in step else []
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


def test_both_forms_of_the_chunk_scan_serve_a_two_chunk_prompt_alike():
    """``ssm_chunk_scan``'s two backends - the XLA read, scan and write, and
    the Mosaic kernel (interpreted; at sizes it tiles: two groups of one
    128-lane tile each, N = 128) - under one engine run each: a 27-token
    prompt enters in a 16-token chunk and a ragged one of 11 with nothing
    decoding beside it, then decodes; both serve the same tokens and leave
    the same state rows, and the spans say ``ssm_chunk_rows`` - the chunk's
    tokens on ``prefill_chunk`` where the kernel took them, 0 on a
    decode-only ``decode_step`` and wherever the XLA form ran."""
    from deepspeed_tpu.ops import registry

    hf, cfg, params, _ = build(mamba_num_heads=8, mamba_head_dim=32,
                               ssm_state_size=128)
    assert nh.state_rows is not None and cfg.mamba_groups == 2
    (b,) = prompts(27)
    runs = {}
    for backend in ("xla", "pallas"):
        registry.set_backend("ssm_chunk_scan", backend)
        try:
            eng = build_engine_v2(nh, cfg, params, config={
                **ENGINE, "trace": {"enabled": True, "ring_size": 4096,
                                    "dump_on_crash": False}})
            eng.put_split(2, b)
            out = [sorted(eng.step().items()) for _ in range(5)]
        finally:
            registry.set_backend("ssm_chunk_scan", None)
        said = [(e["name"], e["args"]["ssm_chunk_rows"])
                for e in eng.tracer.events() if e["ph"] == "X"
                and e["name"] in ("prefill_chunk", "decode_step")]
        runs[backend] = out, np.asarray(eng.cache["ssm"])[:, :-1], said
    (out, state, said), (out_k, state_k, said_k) = runs["xla"], runs["pallas"]
    assert out_k == out and sum(map(len, out)) >= 3
    np.testing.assert_allclose(state_k, state, rtol=1e-3, atol=2e-3)
    assert [n for n, _ in said] == [n for n, _ in said_k]
    assert {n for n, _ in said} == {"prefill_chunk", "decode_step"}
    assert not any(rows for _, rows in said)
    assert [rows for name, rows in said_k if name == "prefill_chunk"] \
        == [16, 11]
    assert not any(rows for name, rows in said_k if name == "decode_step")


def test_admission_reports_the_state_beside_the_blocks(served):
    hf, cfg, _, eng = served
    per_slot = nh.state_slot_bytes(cfg)
    assert per_slot == 4 * (16 + 8) * 64 * 4
    room = eng.kv_headroom()
    assert room["state_bytes_per_slot"] == per_slot
    assert room["state_bytes_free"] == room["state_bytes_total"] == 4 * per_slot
    assert eng.cache["ssm"].dtype == jnp.float32
    # the router stays float32 in a bf16 engine, its bank does not
    low = build_engine_v2(nh, cfg, served[2],
                          config={**ENGINE, "dtype": "bfloat16"})
    moe = low.params["moe"]
    assert moe["router"].dtype == moe["router_bias"].dtype == jnp.float32
    assert moe["w_up"].dtype == low.params["embed"].dtype == jnp.bfloat16


REFUSED_AT_CONFIGURATION = {
    "prefix_cache": {"prefix_cache": {"enabled": True}},
    "host_spill": {"prefix_cache": {"enabled": False, "host_spill": True}},
    "speculative": {"speculative": {"enabled": True}},
    "kv_quant": {"kv_quant": {"enabled": True}},
    "tensor_parallel": {"tensor_parallel": {"tp_size": 2}},
}


@pytest.mark.parametrize("feature", sorted(REFUSED_AT_CONFIGURATION))
def test_what_needs_state_snapshots_is_refused_at_configuration(served,
                                                                feature):
    _, cfg, params, _ = served
    with pytest.raises(RecurrentStateError, match="recurrent state"):
        build_engine_v2(nh, cfg, params, config={
            **ENGINE, **REFUSED_AT_CONFIGURATION[feature]})


def test_a_state_family_without_experts_still_builds_over_a_tensor_mesh(
        served):
    """The mesh is refused where a family declares experts BESIDE its state
    (``_REFUSALS`` ``state_and_experts``), not for recurrent state alone:
    Granite over ``tp_size`` 2 builds and serves as it did (its mixers whole
    on every device)."""
    from test_granite_hybrid import ENGINE as granite_engine
    from test_granite_hybrid import build

    from deepspeed_tpu.inference.engine_v2 import _REFUSALS
    from deepspeed_tpu.models import granite_hybrid as gh

    _, cfg, params, row = build()
    eng = build_engine_v2(gh, cfg, params, config={
        **granite_engine, "tensor_parallel": {"tp_size": 2}})
    assert eng._refusals == [_REFUSALS["recurrent_state"]]
    assert eng.put(1, row[:9].tolist()) is not None
    assert served[3]._refusals == [_REFUSALS["recurrent_state"],
                                   _REFUSALS["state_and_experts"]]


@pytest.mark.parametrize("call", ["fork", "export_kv_blocks",
                                  "import_kv_blocks"])
def test_what_needs_state_snapshots_is_refused_at_its_call(served, call):
    eng = served[3]
    eng.put(21, prompts(9)[0])
    args = {"fork": (21, 22), "export_kv_blocks": (21,),
            "import_kv_blocks": ([], [])}[call]
    with pytest.raises(RecurrentStateError, match=call):
        getattr(eng, call)(*args)
    eng.state.debug_check()                  # nothing half done
    eng.finish(21)


def test_training_and_the_dense_cache_are_refused_by_name(served):
    _, cfg, params, _ = served
    with pytest.raises(NotImplementedError, match="serving family"):
        nh.loss_fn(cfg, params, {"tokens": jnp.zeros((1, 8), jnp.int32)})
    with pytest.raises(NotImplementedError, match="build_engine_v2"):
        nh.init_cache(cfg, 1, 8)


def test_importing_the_package_loads_neither_the_family_nor_its_kernels():
    import subprocess
    import sys

    code = ("import sys, deepspeed_tpu, deepspeed_tpu.models, "
            "deepspeed_tpu.inference.engine_v2\n"
            "bad = [m for m in sys.modules if m.endswith(('nemotron_h', "
            "'granite_hybrid', 'ops.ssm', 'pallas.ssm'))]\n"
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})


def test_hf_name_map_on_a_synthetic_state_dict():
    """``nemotron_h`` checkpoints by their published names (no network here:
    a synthetic state dict of the test size, in torch's layouts) come out as
    the tree ``init`` builds, the bank in the program's layout (256 lanes
    for the 136 published, zeros past them)."""
    import types

    from deepspeed_tpu.models import hf_import

    hf, cfg, params, row = build()
    assert cfg.expert_lanes == 256
    assert params["moe"]["w_up"].shape == (4, 8, 32, 256)
    assert params["moe"]["w_down"].shape == (4, 8, 256, 32)
    assert not np.asarray(params["moe"]["w_up"][..., 136:]).any()
    assert nh.moe_rows(cfg, 4) == nh.mixtral.moe_rows(
        dataclasses.replace(cfg, intermediate_size=256), 4)
    sd = {"backbone.embeddings.weight": params["embed"],
          "backbone.norm_f.weight": params["final_norm"],
          "lm_head.weight": params["lm_head"].T}
    counts = dict.fromkeys("ME*", 0)
    for i, kind in enumerate(PATTERN):
        p = params[nh.KINDS[kind]]
        j = counts[kind]
        counts[kind] += 1
        at = f"backbone.layers.{i}."
        sd[at + "norm.weight"] = p["norm"][j]
        at += "mixer."
        if kind == "*":
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "o_proj")):
                sd[at + f"{theirs}.weight"] = p[ours][j].T
        elif kind == "E":
            sd[at + "gate.weight"] = p["router"][j].T
            sd[at + "gate.e_score_correction_bias"] = p["router_bias"][j]
            for e in range(8):
                sd[at + f"experts.{e}.up_proj.weight"] = \
                    p["w_up"][j, e, :, :136].T
                sd[at + f"experts.{e}.down_proj.weight"] = \
                    p["w_down"][j, e, :136].T
            sd[at + "shared_experts.up_proj.weight"] = p["shared_w_up"][j].T
            sd[at + "shared_experts.down_proj.weight"] = \
                p["shared_w_down"][j].T
        else:
            sd[at + "in_proj.weight"] = jnp.concatenate(
                [p["in_proj"][j], p["dt_proj"][j]], axis=1).T
            sd[at + "conv1d.weight"] = p["conv_w"][j].T[:, None, :]
            sd[at + "conv1d.bias"] = p["conv_b"][j]
            for ours, theirs in (("dt_bias", "dt_bias"), ("A_log", "A_log"),
                                 ("D", "D"), ("gate_norm", "norm.weight")):
                sd[at + theirs] = p[ours][j]
            sd[at + "out_proj.weight"] = p["out_proj"][j].T
    sd = {k: np.asarray(v) for k, v in sd.items()}
    got_cfg = hf_import.nemotron_h_config_from_hf(
        types.SimpleNamespace(**hf))
    assert dataclasses.replace(got_cfg, compute_dtype="float32") == cfg
    got = hf_import.nemotron_h_params_from_hf(sd, got_cfg)
    assert jax.tree.structure(got) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert hf_import.resolve_module("nemotron_h") is nh
    with pytest.raises(ValueError, match="relu2"):
        hf_import.nemotron_h_config_from_hf(
            types.SimpleNamespace(**{**hf, "mlp_hidden_act": "silu"}))
