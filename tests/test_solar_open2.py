"""Solar-Open2 (``models/solar_open2.py``, ISSUE 57) against the benchmark's
plain reference (``benchmark/reference/solar_open2.py`` - the one reference,
not a copy) at a small size on the CPU: two periods of [1 GQA, 3 KDA] layers,
a KV group of two, heads of 16, 4 of 8 experts' worth of router, seeded random
weights, float32 on both sides. Everything is compared in LOGITS: the full
forward in both of its forms, chunked prefill whose chunks end off the delta
rule's tile, decode through the pools, the ops interpreted, mixed calls, and
the engine's slots beside its blocks.

Tolerance. Program and reference both compute in float32 in another order of
operations (the program through tiles and triangular systems, the reference a
token at a time): the largest difference measured over the full forward in
both forms and both paged paths is 2.7e-5 of unit-variance logits. ``TOL`` =
2e-4 (Granite's, Nemotron's and Brumby's) is seven times that and a
twenty-thousandth of what the NEAREST wrong form gives at its largest logit
(``beta`` without its factor 2: 4.3; every variant reads 0.26 and up at the
lower decile of its rows), so any of the variants fails it.
"""

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import mixed_program
from benchmark.families import solar_open2 as family
from benchmark.reference import solar_open2 as reference
from benchmark.reference import solar_open2_variants as variants
from deepspeed_tpu.inference.engine_v2 import (RecurrentStateError,
                                               build_engine_v2)
from deepspeed_tpu.models import _state
from deepspeed_tpu.models import solar_open2 as so
from deepspeed_tpu.models._paged import MixedCall
from deepspeed_tpu.ops import delta
from deepspeed_tpu.ops.pallas import delta as kernels
from deepspeed_tpu.ops.pallas import delta_chunk as tiles

TOL = 2e-4
ENGINE = {"dtype": "float32", "prefill_bucket": 8, "split_prefill_chunk": 16,
          "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                     "memory_config_blocks": 64, "block_size": 8}}


@pytest.fixture(autouse=True, scope="module")
def tile_of_16():
    """The delta rule's tile (``ops/delta.py TILE``, 64 as served) at 16 for
    every program this file traces: chunks of 16-24 tokens then run in
    several tiles, and end off one."""
    served, delta.TILE = delta.TILE, 16
    yield
    delta.TILE = served


def published(**kw):
    """The published keys at the test size (the release's ratios)."""
    hf = dict(
        model_type="solar_open2", partial_rotary_factor=1,
        linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                                num_heads=4, num_kv_heads=None),
        hidden_size=32, num_hidden_layers=8, num_attention_heads=4,
        head_dim=16, num_key_value_heads=2, vocab_size=256,
        intermediate_size=48, moe_intermediate_size=24, rms_norm_eps=1e-5,
        rope_theta=10000, tie_word_embeddings=False,
        max_position_embeddings=256, first_k_dense_replace=0, use_rope=False,
        gqa_interval=3, gqa_layers=[0, 4], use_gqa_gate=True,
        kda_use_full_proj=False, kda_allow_neg_eigval=True,
        n_routed_experts=8, n_shared_experts=1, norm_topk_prob=True,
        routed_scaling_factor=1, num_experts_per_tok=3, num_local_experts=8,
        num_experts=8)
    hf.update(kw)
    return hf


def build(**kw):
    """The configuration, its seeded weights in float32 - the norms' weights
    too, which ``init`` leaves at one: a weight that went unused would
    otherwise pass -, a drawn choice bias, and a row of tokens."""
    hf = published(**kw)
    cfg = family.build_cfg(hf, compute_dtype="float32")
    params = so.init(cfg, jax.random.PRNGKey(0))
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 16))
    for kind, names in (("delta", ("norm", "ffn_norm", "o_norm")),
                        ("attn", ("norm", "ffn_norm"))):
        for name in names:
            leaf = params[kind][name]
            params[kind][name] = 1.0 + 0.2 * jax.random.normal(
                next(keys), leaf.shape)
        bias = params[kind]["moe"]["router_bias"]
        params[kind]["moe"]["router_bias"] = jax.random.uniform(
            next(keys), bias.shape, jnp.float32, -0.1, 0.1)
    params["final_norm"] = 1.0 + 0.2 * jax.random.normal(
        next(keys), params["final_norm"].shape)
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


TABLE = jnp.asarray([[1 + i for i in range(12)]], jnp.int32)


@functools.partial(jax.jit, static_argnums=(0,))
def _paged_call(cfg, params, cache, padded, start, n, slot):
    return so.apply_paged(cfg, params, padded, cache, TABLE, start[None],
                          valid=jnp.arange(padded.shape[1])[None] < n,
                          slots=slot[None])


def fresh_cache(cfg, slots=3):
    return so.init_paged_cache(cfg, 16, 8, dtype=jnp.float32, slots=slots)


def paged_logits(cfg, params, row, calls, slot=1):
    """Logits of ``row`` fed through ``apply_paged`` call by call:
    ``calls`` = ``(tokens in the call, width the call is padded to)``."""
    with jax.default_matmul_precision("highest"):
        cache = fresh_cache(cfg)
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


# (tokens, padded width) of each call. The delta rule's tile is 16: chunks
# of 13, 2 and 1 end off it, and a chunk of 21 in a width of 24 pads inside
# a tile of 32
PATHS = {
    "chunks_off_the_tile": [(13, 16), (2, 16), (1, 16), (16, 16), (21, 24),
                            (19, 24)],
    "prefill_then_32_decode_steps": [(40, 48)] + [(1, 1)] * 32,
}


@pytest.mark.parametrize("form", ["chunked", "recurrence"])
def test_full_forward_agrees_with_the_plain_reference(model, form):
    hf, cfg, params, row, want = model
    with jax.default_matmul_precision("highest"):
        got = so.apply(cfg, params, jnp.asarray(row[None]), form=form)[0]
    assert float(np.abs(np.asarray(got) - want).max()) < TOL


@pytest.mark.parametrize("path", sorted(PATHS))
def test_paged_path_agrees_with_the_plain_reference_in_logits(model, path):
    hf, cfg, params, row, want = model
    got, cache = paged_logits(cfg, params, row, PATHS[path])
    assert float(np.abs(got - want[:len(got)]).max()) < TOL
    assert cache["delta"].dtype == jnp.float32
    # the other slots' rows and the spare lanes of the tail were not touched
    assert float(jnp.abs(cache["delta"][:, 0]).max()) == 0.0
    assert float(jnp.abs(cache["delta"][:, 2]).max()) == 0.0


@pytest.mark.parametrize("variant", variants.NAMES)
def test_each_wrong_variant_stands_apart_by_more_than_the_tolerance(
        model, variant):
    hf, _, params, row, want = model
    wrong = variants.logits(variant, hf, plain(params), row)
    assert float(np.abs(wrong - want).max()) > 100 * TOL
    seen = reference.held(wrong, want, reference.decode_rows(len(row)))
    assert seen["logits_mean_abs_diff"] > 5 * TOL
    assert seen["decode_logits_mean_abs_diff"] > 5 * TOL


def test_the_references_recurrence_is_its_triangular_system(model):
    """The reference's truth (a token at a time) against its cross-check
    (one triangular system over the sequence), on one layer's operands as
    the model makes them."""
    hf, _, params, row, _ = model
    w = plain(params).layer("delta", 1)
    names = ("q", "k", "v", "conv_q", "conv_k", "conv_v", "f1", "f2",
             "dt_bias", "A_log", "b", "g1", "g2")
    u = jax.random.normal(jax.random.PRNGKey(3), (48, 32))
    with jax.default_matmul_precision("highest"):
        q, k, v, log_a, beta, _ = reference._delta_in(
            u, {n: w[n] for n in names}, reference._freeze(hf),
            reference.RIGHT)
        a = reference.recurrent_delta(q, k, v, log_a, beta)
        b = reference.solved_delta(q, k, v, log_a, beta)
    assert float(jnp.abs(a).max()) > 1e-2
    assert float(jnp.abs(a - b).max()) < 1e-5
    assert float(beta.max()) > 1.0 and float(log_a.min()) < -0.5


# --------------------------------------------------------------------------- #
# the op pair
# --------------------------------------------------------------------------- #
def _operands(b, t, H=3, dk=16, dv=8, strength=1.0, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    q, k = unit(f(b, t, H, dk)) * dk ** -0.5, unit(f(b, t, H, dk))
    log_a = -strength * rng.uniform(0.5, 1.0, (b, t, H, dk)) \
        .astype(np.float32)
    beta = 2 * rng.uniform(size=(b, t, H)).astype(np.float32)
    return tuple(map(jnp.asarray, (q, k, f(b, t, H, dv), log_a, beta)))


@pytest.mark.parametrize("tile", [8, 16, 32, 64])
def test_the_chunked_form_is_the_recurrence_under_strong_decay(tile):
    """``log a`` = -3 to -6 a token a channel over 256 tokens: the running
    sum reaches -1500, ``exp`` of its negation is past float32 a hundred
    times over, and the chunked form stays finite and equal - no exponent is
    ever positive (``ops/delta.decayed_products``)."""
    ops = _operands(2, 256, strength=6.0)
    S0 = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 16, 8))
    with jax.default_matmul_precision("highest"):
        want, S_want = delta.delta_recurrence(*ops, S0)
        got, S_got = jax.jit(delta.delta_chunked, static_argnums=6)(
            *ops, S0, tile)
    assert bool(jnp.isfinite(got).all()) and bool(jnp.isfinite(S_got).all())
    assert float(jnp.abs(want).max()) > 0.05
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(S_got - S_want).max()) < 1e-5
    # and the plain product of the two exponentials is NOT finite there
    g = jnp.cumsum(ops[3], axis=1)
    assert not bool(jnp.isfinite(jnp.exp(-g)).all())


@pytest.mark.parametrize("t,tile", [(100, 64), (21, 16), (5, 64), (1, 64)])
def test_the_chunked_form_pads_and_tiles_any_length(t, tile):
    ops = _operands(2, t, strength=0.3, seed=t)
    S0 = jax.random.normal(jax.random.PRNGKey(2), (2, 3, 16, 8))
    with jax.default_matmul_precision("highest"):
        want, S_want = delta.delta_recurrence(*ops, S0)
        got, S_got = jax.jit(delta.delta_chunked, static_argnums=6)(
            *ops, S0, tile)
    assert got.shape == want.shape
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(S_got - S_want).max()) < 1e-5


def test_decayed_products_and_the_unit_lower_inverse_by_their_definitions():
    rng = np.random.default_rng(5)
    x, k = (jnp.asarray(rng.normal(size=(2, 64, 8)).astype(np.float32))
            for _ in range(2))
    g = jnp.cumsum(-jnp.asarray(rng.uniform(0, 8, (2, 64, 8))
                                .astype(np.float32)), axis=1)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(delta.decayed_products)(x, k, g)
        diff = g[:, :, None] - g[:, None]
        want = jnp.einsum("btc,btsc,bsc->bts", x, jnp.exp(jnp.where(
            jnp.tril(jnp.ones((64, 64), bool))[None, :, :, None], diff,
            -jnp.inf)), k)
        assert float(jnp.abs(got - want).max()) < 1e-5
        assert float(jnp.abs(jnp.triu(got, 1)).max()) == 0.0
        # identical keys at beta = 2 (every entry 2: the powers of L grow
        # to 1e30 by L^32 and the substitution never forms one), and keys
        # as the model makes them
        keys = jnp.asarray(rng.normal(size=(64, 8)).astype(np.float32))
        keys = keys / jnp.linalg.norm(keys, axis=-1, keepdims=True)
        L = jnp.stack([jnp.tril(jnp.full((64, 64), 2.0), -1),
                       jnp.tril(2.0 * keys @ keys.T, -1)])
        T = jax.jit(delta.unit_lower_inverse)(L)
        eye = jnp.eye(64)
        assert float(jnp.abs(T @ (eye + L) - eye).max()) < 1e-4
        assert float(jnp.abs(T[0]).max()) == 2.0
        assert float(jnp.abs(jnp.triu(T, 1)).max()) == 0.0


def _pool(L=2, slots=3, H=4, dk=16, dv=16, tail=16, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed),
                             (L, slots + 1, dk + tail, H * dv), jnp.float32)


def test_interpreted_decode_kernel_is_one_token_of_the_recurrence():
    """The Mosaic kernel (interpreted) against ``delta_step`` on the pool's
    rows: a live row, a fresh row (its old state must not be read), a row on
    the trash row; the tail's sublanes and every other row stay bit-equal."""
    pool = _pool()
    q, k, v, log_a, beta = (a[:, 0] for a in _operands(
        3, 1, H=4, dk=16, dv=16, strength=3.0))
    rows = jnp.asarray([1, 0, 3], jnp.int32)
    fresh = jnp.asarray([False, True, False])
    with jax.default_matmul_precision("highest"):
        want_pool, want = delta.delta_decode_update_xla(
            pool, 1, rows, fresh, q, k, v, log_a, beta)
        got_pool, got = jax.jit(kernels.delta_decode_update)(
            pool, 1, rows, fresh, q, k, v, log_a, beta)
        S0 = jnp.where(fresh[:2, None, None, None], 0.0,
                       delta.state_to_heads(pool[1, rows[:2], :16], 4))
        S1, o = delta.delta_step(S0, q[:2], k[:2], v[:2], log_a[:2],
                                 beta[:2])
    assert float(jnp.abs(got[:2] - o).max()) < 1e-6
    assert float(jnp.abs(got[:2] - want[:2]).max()) < 1e-6
    assert float(jnp.abs(delta.state_to_heads(got_pool[1, rows[:2], :16], 4)
                         - S1).max()) < 1e-6
    np.testing.assert_array_equal(got_pool[0], pool[0])
    np.testing.assert_array_equal(got_pool[1, 2], pool[1, 2])
    np.testing.assert_array_equal(got_pool[1, :3, 16:], pool[1, :3, 16:])
    assert float(jnp.abs(want_pool[1, :3] - got_pool[1, :3]).max()) < 1e-6


@pytest.mark.parametrize("t,tile", [(21, 16), (8, 8), (40, 32)])
def test_the_chunk_op_over_the_row_table_is_the_chunked_form(t, tile):
    pool = _pool()
    ops = _operands(2, t, H=4, dk=16, dv=16, strength=2.0, seed=t)
    rows = jnp.asarray([2, 0], jnp.int32)
    fresh = jnp.asarray([False, True])
    with jax.default_matmul_precision("highest"):
        want_pool, want = jax.jit(delta.delta_chunk_xla,
                                  static_argnames="tile")(
            pool, 0, rows, fresh, *ops, tile=tile)
        got_pool, got = jax.jit(kernels.delta_chunk, static_argnames="tile")(
            pool, 0, rows, fresh, *ops, tile=tile)
        # heads of 16 lanes are no shape the Mosaic kernel tiles: the op is
        # the XLA form between the row-table kernels, number for number
        assert not tiles.takes(16, 16, 4, t, pool.dtype)
        old_pool, old = jax.jit(kernels.delta_chunk_between_rows,
                                static_argnames="tile")(
            pool, 0, rows, fresh, *ops, tile=tile)
        S0 = jnp.where(fresh[:, None, None, None], 0.0,
                       delta.state_to_heads(pool[0, rows, :16], 4))
        o, S1 = delta.delta_recurrence(*ops, S0)
    np.testing.assert_array_equal(got, old)
    np.testing.assert_array_equal(got_pool, old_pool)
    assert float(jnp.abs(got - o).max()) < 1e-5
    assert float(jnp.abs(got - want).max()) < 1e-6
    assert float(jnp.abs(delta.state_to_heads(got_pool[0, rows, :16], 4)
                         - S1).max()) < 1e-5
    np.testing.assert_array_equal(got_pool[1], pool[1])
    np.testing.assert_array_equal(got_pool[0, 1], pool[0, 1])
    np.testing.assert_array_equal(got_pool[0, :, 16:], pool[0, :, 16:])
    assert float(jnp.abs(want_pool - got_pool).max()) < 1e-6


WIDE = dict(H=2, dk=128, dv=128)    # heads the chunk's Mosaic kernel tiles


@pytest.mark.parametrize("t", [256, 300, 40, 1])
def test_the_interpreted_chunk_kernel_is_the_recurrence_under_strong_decay(t):
    """At heads of whole 128-lane tiles ``delta_chunk`` is ONE Mosaic kernel
    over the row table (interpreted here) and one write of the rows: whole
    tiles of ``delta_chunk.TOKENS``, a ragged last tile, less than a tile
    and one token, under ``log a`` of -3 to -6 a token a channel (the plain
    ``exp(-g)`` is past float32 by the 30th token), a live row beside a
    fresh one whose old state must not be read - against the XLA form and
    the recurrence a token at a time; the other layer, the other rows and
    the tail's sublanes stay bit-equal."""
    pool = _pool(**WIDE)
    ops = _operands(2, t, strength=6.0, seed=t, **WIDE)
    rows = jnp.asarray([2, 0], jnp.int32)
    fresh = jnp.asarray([False, True])
    assert tiles.takes(128, 128, 2, t, pool.dtype)
    with jax.default_matmul_precision("highest"):
        want_pool, want = jax.jit(delta.delta_chunk_xla)(
            pool, 0, rows, fresh, *ops)
        op = jax.jit(kernels.delta_chunk)
        got_pool, got = op(pool, 0, rows, fresh, *ops)
        S0 = jnp.where(fresh[:, None, None, None], 0.0,
                       delta.state_to_heads(pool[0, rows, :128], 2))
        o, S1 = delta.delta_recurrence(*ops, S0)
        program = str(jax.make_jaxpr(kernels.delta_chunk)(
            pool, 0, rows, fresh, *ops))
    # the kernel by its literal name, the rows' write after it, and no read
    # of the rows before it
    assert program.count("name=delta_chunk") == 1
    assert program.count("name=state_rows_write") == 1
    assert "name=state_rows_read" not in program
    assert got.shape == o.shape == (2, t, 2, 128)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(o).max()) > 0.01
    assert float(jnp.abs(got - o).max()) < 1e-5
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(delta.state_to_heads(got_pool[0, rows, :128], 2)
                         - S1).max()) < 1e-5
    np.testing.assert_array_equal(got_pool[1], pool[1])
    np.testing.assert_array_equal(got_pool[0, 1], pool[0, 1])
    np.testing.assert_array_equal(got_pool[0, 3], pool[0, 3])
    np.testing.assert_array_equal(got_pool[0, :, 128:], pool[0, :, 128:])
    assert float(jnp.abs(want_pool - got_pool).max()) < 1e-5
    if t >= 256:
        g = jnp.cumsum(ops[3], axis=1)
        assert not bool(jnp.isfinite(jnp.exp(-g)).all())


@pytest.mark.parametrize("op", ["decode", "chunk"])
def test_rows_aimed_at_the_trash_row_read_nothing_of_it(op):
    if op == "decode":
        pool, ops = _pool(), _operands(2, 8, H=4, dk=16, dv=16)
        call = lambda *a: kernels.delta_decode_update(
            *a, *(x[:, 0] for x in ops))
    else:       # the Mosaic kernel of a multi-token segment
        pool, ops = _pool(**WIDE), _operands(2, 40, strength=2.0, **WIDE)
        call = lambda *a: kernels.delta_chunk(*a, *ops)
    pool = pool.at[:, 3].set(jnp.nan)          # the trash row poisoned
    rows, fresh = jnp.asarray([3, 3], jnp.int32), jnp.asarray([False, False])
    got_pool, got = call(pool, 0, rows, fresh)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(got_pool[:, :3], pool[:, :3])


@pytest.mark.parametrize("d", [16, 128])
def test_the_kernels_refuse_a_state_that_is_not_float32_by_name(d):
    pool = _pool(H=4, dk=d, dv=d).astype(jnp.bfloat16)
    ops = _operands(1, 8, H=4, dk=d, dv=d)
    rows, fresh = jnp.asarray([0], jnp.int32), jnp.asarray([False])
    with pytest.raises(NotImplementedError, match="float32 state"):
        kernels.delta_chunk(pool, 0, rows, fresh, *ops)
    with pytest.raises(NotImplementedError, match="float32 state"):
        kernels.delta_decode_update(pool, 0, rows, fresh,
                                    *(a[:, 0] for a in ops))


# --------------------------------------------------------------------------- #
# what was lifted for a second family
# --------------------------------------------------------------------------- #
def test_the_lifted_convolution_is_granites_with_and_without_its_bias():
    """``_state.short_conv`` by its definition (a causal depthwise
    convolution after the tail, then silu), with Mamba's bias and with
    none; and the tail's part of a row for the three families' sizes."""
    rng = np.random.default_rng(1)
    x, tail = (jnp.asarray(rng.normal(size=s).astype(np.float32))
               for s in ((2, 9, 6), (2, 3, 6)))
    taps, bias = (jnp.asarray(rng.normal(size=s).astype(np.float32))
                  for s in ((4, 6), (6,)))
    ext = np.concatenate([tail, x], axis=1)
    for b in (None, bias):
        want = sum(ext[:, k:k + 9] * np.asarray(taps)[k] for k in range(4)) \
            + (0 if b is None else np.asarray(b))
        got, rows = _state.short_conv(x, tail, taps, b)
        np.testing.assert_allclose(got, want / (1 + np.exp(-want)),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(rows, ext)
    assert _state.tail_part(128, 3 * 4352, 4096) == (128, 8, 1664)
    assert _state.tail_part(128, 3 * 6144, 4096) == (128, 8, 2304)
    assert _state.tail_part(128, 3 * 24576, 8192) == (128, 16, 4608)
    assert so.SolarOpen2Config().tail_part == (128, 16, 4608)
    assert so.SolarOpen2Config().state_row_bytes == 144 * 8192 * 4
    part = (16, 16, 64)
    packed = _state.pack_tail(jnp.arange(2 * 3 * 192.).reshape(2, 3, 192),
                              part)
    assert packed.shape == (2, 16, 64)
    np.testing.assert_array_equal(
        _state.unpack_tail(packed, 3, 192, jnp.float32),
        jnp.arange(2 * 3 * 192.).reshape(2, 3, 192))
    n_valid = jnp.asarray([9, 2], jnp.int32)
    np.testing.assert_array_equal(
        _state.next_tail(jnp.asarray(ext), n_valid, 3)[1], ext[1, 2:5])


# --------------------------------------------------------------------------- #
# mixed calls, the decoded rows by themselves, the shares
# --------------------------------------------------------------------------- #
def test_a_mixed_call_is_its_two_segments(model):
    """A decode row of each of two live slots and a third sequence's chunk
    in ONE call against the same rows as a chunk call then a decode call."""
    _, cfg, params, row, _ = model
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0], [7, 8, 9, 0],
                          [0, 0, 0, 0]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        # slots 0 and 1 stand at 10 and 13 tokens and slot 2 at 5 of its
        # prompt: whatever pools both paths start from (random ones here)
        cache = jax.tree.map(
            lambda a: 0.3 * jax.random.normal(jax.random.PRNGKey(a.ndim),
                                              a.shape, a.dtype),
            so.init_paged_cache(cfg, 16, 8, dtype=jnp.float32, slots=4))
        lens = jnp.asarray([10, 13, 0, 0], jnp.int32)
        active = jnp.asarray([True, True, False, False])
        step = jnp.asarray([[row[10]], [row[33]], [0], [0]], jnp.int32)
        chunk = np.zeros((1, 8), np.int32)
        chunk[0, :6] = row[45:51]
        forward = jax.jit(
            lambda tokens, cache, tables, ctx, valid, slots: so.apply_paged(
                cfg, params, tokens, cache, tables, ctx, valid=valid,
                slots=slots))
        want_c, two = forward(
            jnp.asarray(chunk), cache, tables[2][None],
            jnp.asarray([5], jnp.int32), jnp.arange(8)[None] < 6,
            jnp.asarray([2]))
        want_d, two = forward(step, two, tables, lens, active[:, None], None)
        call = MixedCall(tables, lens, active, tables[2], jnp.int32(5),
                         jnp.int32(6), jnp.int32(2))
        tokens = jnp.concatenate([step[:, 0], jnp.asarray(chunk[0])])[None]
        got, one = forward(tokens, cache, call, None, call.valid(12), None)
    assert float(jnp.abs(got[0, :2] - want_d[:2, 0]).max()) < TOL
    assert float(jnp.abs(got[0, 4:10] - want_c[0, :6]).max()) < TOL
    for name in ("delta", "k", "v"):
        live = (slice(None), slice(0, 3)) if name == "delta" \
            else (slice(None), slice(1, 10))
        assert float(jnp.abs(one[name][live] - two[name][live]).max()) < 1e-5


def test_a_fault_in_the_single_token_call_alone_is_told_by_the_decoded_rows(
        model):
    """``reference.held`` reads a probe's decoded rows BY THEMSELVES, under a
    limit of their own (``roles.serve.held``): a state update that decays a
    head by its channels' mean, planted in the single-token program alone
    over the right program's prefilled pools (``tools/solar_open2_check.py``
    does the same on the chip), leaves the chunked part's rows what they
    were and comes out by the decoded rows' limit and by no other."""
    from benchmark.tools.solar_open2_check import scalar_decay_update

    hf, _, params, row, want = model
    program = family.Program(params, {
        "program_options": {"state_dtype": "float32",
                            "compute_dtype": "float32"},
        "held": {}, "weights_dtype": "float32",
        "engine": {"split_prefill_chunk": 16,
                   "ragged": {"block_size": 8,
                              "max_tracked_sequences": 4}}})
    decode = reference.decode_rows(len(row))
    assert decode == len(row) // 2 and reference.decode_rows(2144) == 96
    n = len(row) - decode
    with jax.default_matmul_precision("highest"):
        pre, cache, book = program.prefill(hf, row, n)
        # the judged sequence beside two live neighbours, in a slot and in
        # blocks of the probe's own draw
        assert book.active.sum() == 2 and not book.active[book.judged]
        assert book.lens[book.judged] == n and book.judged != book.filler
        pools = jax.device_get(cache)
        right = np.concatenate(
            [pre, program.decode(hf, row, n, cache, book)])
        with scalar_decay_update():
            planted = mixed_program.mixed_call.__wrapped__(
                family, program.cfg, program.dtype.name)
            wrong = np.concatenate([pre, program.decode(
                hf, row, n, jax.device_put(pools), book, call=planted)])
    want = want[-len(right):]
    limits = {key: TOL for key, _, _ in reference.HELD}
    seen = reference.held(right, want, decode)
    assert seen["decode_rows"] == decode and seen["rows"] == len(right)
    assert reference.disagreements(seen, limits) == []
    seen = reference.held(wrong, want, decode)
    why = reference.disagreements(seen, limits)
    assert len(why) == 2 and all("decoded" in w for w in why), why
    assert seen["logits_mean_abs_diff"] <= TOL
    assert seen["decode_logits_mean_abs_diff"] > 20 * TOL


def test_the_eight_shares_routed_parts_and_one_shared_expert_are_the_uncut_layer(
        model):
    """At the test size eight shares of one expert each (the deployment's
    eight chips a layer): each share's reference output for ONE layer's
    feed-forward, less the input and the shared expert it holds whole, adds
    up with ONE shared expert to the uncut reference's layer (the program
    with a held range against its share's reference, through the whole
    path: ``benchmark/tests/test_solar_open2_cell.py``)."""
    hf, cfg, params, row, _ = model
    whole = plain(params).layer("delta", 2)
    x = jax.random.normal(jax.random.PRNGKey(4), (24, 32))
    with jax.default_matmul_precision("highest"):
        uncut = reference.experts(x, whole, hf)
        shared = reference.experts(x, {**whole, "experts": []},
                                   {**hf, "num_experts": 0})
        total = shared
        for first in range(8):
            share_hf = {**hf, "num_experts": 1, "experts_first": first}
            w = {**whole, "experts": whole["experts"][first:first + 1]}
            total = total + reference.experts(x, w, share_hf) - shared
    assert float(jnp.abs(total - uncut).max()) < 1e-5
    assert float(jnp.abs(uncut - shared).max()) > 0.05
    # and the program takes a held range as the reference's share does
    share = family.build_cfg({**hf, "num_experts": 3, "experts_first": 4})
    assert share.experts_held == (4, 3) and share.num_experts == 8
    assert so._moe(share).held == (4, 3)


# --------------------------------------------------------------------------- #
# the engine: slots beside blocks
# --------------------------------------------------------------------------- #
def _float32_pools(cfg, num_blocks, block_size, **kw):
    return so.init_paged_cache(cfg, num_blocks, block_size,
                               **{**kw, "dtype": jnp.float32})


# the engine builds a family's block pools in bfloat16 whatever its own type
# (``init_paged_cache``'s default), and at this toy width a bfloat16 key
# moves a logit by 0.15: the float32 engine of these tests is given float32
# pools, so that "the reference's top" is exact
FLOAT32 = types.SimpleNamespace(**{**vars(so),
                                   "init_paged_cache": _float32_pools})


@pytest.fixture(scope="module")
def served(model):
    hf, cfg, params, _, _ = model
    eng = build_engine_v2(FLOAT32, cfg, params, config=ENGINE)
    return hf, cfg, params, eng


def gaps(served, eng, prompt, out):
    """How far below the reference's top each served token lies (the
    sequence padded to the fixture's 72 tokens, which a causal model's
    earlier rows do not see: ONE compile of the reference a file)."""
    tokens = list(prompt) + out[:-1]
    padded = np.zeros(72, np.int32)
    padded[:len(tokens)] = tokens
    want = reference.logits(served[0], plain(eng.params),
                            padded)[len(prompt) - 1:len(tokens)]
    return want.max(-1) - want[np.arange(len(out)), out]


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lengths]


def state_of(eng, slot):
    return np.asarray(eng.cache["delta"][:, slot])


def test_slots_keep_release_and_restart_their_state(served):
    """One module-scoped engine through Granite's slot tests in a row: a
    decode beside a free and a prefilling slot leaves their rows bit-equal
    and moves its own; a prompt that completes beside a decode advances its
    state once; a retired slot leaks nothing into the next sequence (its
    state restarts from zeros after ``finish``); preemption and readmission
    continue the stream. Every served token is the reference's top, and the
    step's span arguments carry the delta rule's rows."""
    eng = served[3]
    assert set(eng.cache) == {"k", "v", "delta"}
    assert eng.family.name == "solar_open2"
    assert eng.cache["delta"].shape == (6, 5, 32, 64)
    assert not eng.state.blockless and eng._recurrent
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
        "delta_rows": 3, "delta_chunk_rows": 16}
    assert set(eng.family.moe_rows(eng.family.cfg, 20)) == {
        "moe_rows_routed", "moe_rows_computed", "moe_row_tile"}
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


def test_the_scheduler_serves_the_references_tokens(served):
    """``ServingScheduler`` over the same engine: three requests of unlike
    lengths, overlapped ticks; each served token the reference's top."""
    from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                                 ServingScheduler)

    eng = served[3]
    sched = ServingScheduler(eng, SchedulerConfig(
        decode_quantum=1, max_admissions_per_tick=1))
    handles = {i: sched.submit(Request(prompt=p, max_new_tokens=6))
               for i, p in enumerate(prompts(37, 9, 21, seed=5))}
    for _ in range(200):
        sched.tick()
        if all(h.done for h in handles.values()):
            break
    for i, p in enumerate(prompts(37, 9, 21, seed=5)):
        out = [int(t) for t in handles[i].tokens]
        assert len(out) == 6
        assert float(gaps(served, eng, p, out).max()) == 0.0
    eng.state.debug_check()


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
    with pytest.raises(RecurrentStateError, match="recurrent state|mixer"):
        build_engine_v2(FLOAT32, cfg, params, config={
            **ENGINE, **REFUSED_AT_CONFIGURATION[feature]})


@pytest.mark.parametrize("call", ["fork", "export_kv_blocks",
                                  "import_kv_blocks"])
def test_what_needs_state_snapshots_is_refused_at_its_call(served, call):
    from deepspeed_tpu.inference.engine_v2 import _REFUSALS

    eng = served[3]
    assert eng._refusals == [_REFUSALS["recurrent_state"],
                             _REFUSALS["state_and_experts"]]
    eng.put(21, prompts(9)[0])
    args = {"fork": (21, 22), "export_kv_blocks": (21,),
            "import_kv_blocks": ([], [])}[call]
    with pytest.raises(RecurrentStateError, match=call):
        getattr(eng, call)(*args)
    eng.state.debug_check()                  # nothing half done
    eng.finish(21)


def test_training_the_dense_cache_and_other_stacks_are_refused_by_name(
        served):
    _, cfg, params, _ = served
    with pytest.raises(NotImplementedError, match="serving family"):
        so.loss_fn(cfg, params, {"tokens": jnp.zeros((1, 8), jnp.int32)})
    with pytest.raises(NotImplementedError, match="build_engine_v2"):
        so.init_cache(cfg, 1, 8)
    with pytest.raises(NotImplementedError, match="build_engine_v2"):
        so.apply_cached(cfg, params, None, None, None)
    with pytest.raises(ValueError, match="delta-rule layers"):
        so.init(dataclasses.replace(cfg, gqa_layers=tuple(range(8))),
                jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="gqa_layers"):
        so.init(dataclasses.replace(cfg, gqa_layers=(0, 9)),
                jax.random.PRNGKey(0))
    for key in ("use_rope", "kda_use_full_proj", "first_k_dense_replace",
                "tie_word_embeddings"):
        with pytest.raises(ValueError, match=key):
            family.build_cfg(published(**{key: True}))


def test_importing_the_package_loads_neither_the_family_nor_its_kernels():
    import subprocess
    import sys

    code = ("import sys, deepspeed_tpu, deepspeed_tpu.models, "
            "deepspeed_tpu.inference.engine_v2\n"
            "bad = [m for m in sys.modules if m.endswith(('solar_open2', "
            "'ops.delta', 'pallas.delta'))]\n"
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})
