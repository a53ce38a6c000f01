"""The head scores the rows a program reads (ISSUE 44).

``apply_paged(..., rows=)`` gathers the hidden state to ``rows [b, r]``
before the final norm and the head (``_paged.gather_rows``), so its logits
are ``[b, r, V]`` and must be the rows ``r`` of what the same call gives for
every row - for each of the seven paged families on a ``[b, t]`` call and
on a ``MixedCall``. The engine hands over what its programs read (a prefill
its last real rows, a mixed step its slots' rows and the chunk's last real
one): held here against an engine of the same weights around a caller's own
``apply_paged`` that scores every row and picks ``rows`` from the result -
ISSUE 44's parent's programs - through ``ServingScheduler.tick``, to the
token. ``rows=`` is part of the ``apply_paged`` contract (ISSUE 46): a
caller's callable declares it.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference import SamplingParams, build_engine_v2
from deepspeed_tpu.inference.config import InferenceConfig
from deepspeed_tpu.inference.engine import ModelFamily
from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2
from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                             ServingScheduler)
from deepspeed_tpu.models import (cohere2_moe, exaone4, falcon, gpt,
                                  granite_hybrid, llama, mixtral)
from deepspeed_tpu.models._paged import MixedCall, gather_rows

SLOTS, BLOCK, WIDTH, BLOCKS, CHUNK = 4, 4, 8, 40, 8
F32 = jnp.float32

# family -> (module, config, what its paged cache takes beside the geometry)
FAMILIES = {
    "llama": (llama, lambda: llama.LlamaConfig.tiny(max_seq_len=32), {}),
    "mixtral": (mixtral, lambda: mixtral.MixtralConfig.tiny(max_seq_len=32),
                {}),
    # the call and the tick, not the ten-layer period
    # (tests/test_granite_hybrid.py keeps that): a run of each kind, repeated
    "granite_hybrid": (granite_hybrid,
                       lambda: granite_hybrid.GraniteHybridConfig.tiny(
                           max_seq_len=32,
                           layer_types=("mamba", "attention") * 2),
                       {"slots": SLOTS}),
    # a table of the full kind's width serves both kinds: nothing given back
    "cohere2_moe": (cohere2_moe,
                    lambda: cohere2_moe.Cohere2MoeConfig.tiny(max_seq_len=32),
                    {}),
    "gpt": (gpt, lambda: gpt.GPTConfig.tiny(max_seq_len=32), {}),
    "falcon": (falcon, lambda: falcon.FalconConfig.tiny(max_seq_len=32), {}),
    "exaone4": (exaone4, lambda: exaone4.Exaone4Config.tiny(max_seq_len=32),
                {}),
}
SERVED = ("cohere2_moe", "granite_hybrid", "llama", "mixtral")


def test_gather_rows_picks_each_sequences_own_rows():
    x = jnp.arange(2 * 5 * 3, dtype=F32).reshape(2, 5, 3)
    assert gather_rows(x, None) is x
    got = gather_rows(x, jnp.asarray([[4, 0, 4], [1, 2, 3]], jnp.int32))
    np.testing.assert_array_equal(got[0], x[0, [4, 0, 4]])
    np.testing.assert_array_equal(got[1], x[1, [1, 2, 3]])


@functools.cache
def _family(family):
    """``(module, config, weights, an empty cache, forward)`` of a family,
    once a module: ``forward`` is its ``apply_paged`` in float32 under ONE
    ``jax.jit``, so a family's cases share its compiled shapes (a mixed
    call's real rows are a value of the program)."""
    module, make, cache_kw = FAMILIES[family]
    cfg = make()
    params = module.init(cfg, jax.random.PRNGKey(0))
    cache = module.init_paged_cache(cfg, BLOCKS, BLOCK, dtype=F32, **cache_kw)
    return module, cfg, params, cache, jax.jit(functools.partial(
        module.apply_paged, cfg, params, compute_dtype=F32))


def _forward(family):
    module, cfg, _, cache, fwd = _family(family)
    rng = np.random.default_rng(3)
    tok = lambda *shape: jnp.asarray(rng.integers(1, cfg.vocab_size, shape),
                                     jnp.int32)
    # slot i owns blocks ``1 + i * WIDTH ..``; block 0 is the trash
    tables = jnp.asarray(np.arange(1, 1 + SLOTS * WIDTH, dtype=np.int32)
                         .reshape(SLOTS, WIDTH))
    return module, cfg, cache, fwd, tok, tables


def _same(got, want, rows):
    """``got [b, r, V]`` is ``want [b, t, V]`` at ``rows [b, r]``: at
    float32 noise (a matmul over fewer rows may tile otherwise), and the
    same token from each."""
    want = np.take_along_axis(np.asarray(want), np.asarray(rows)[:, :, None],
                              axis=1)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-5)
    np.testing.assert_array_equal(np.argmax(got, -1), np.argmax(want, -1))


def _same_cache(one, two):
    for name in one:
        np.testing.assert_array_equal(one[name], two[name], err_msg=name)


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_rows_of_a_batched_call_are_the_rows_of_every_rows_logits(family):
    """A prefill of four sequences of 8, 5, 2 and 0 real tokens: each
    sequence's last real row (what ``prefill`` reads; row 0 of the dummy),
    and a second, arbitrary column - ``r`` is any static count."""
    *_, cache, fwd, tok, tables = _forward(family)
    lengths = jnp.asarray([8, 5, 2, 0], jnp.int32)
    tokens, ctx = tok(SLOTS, CHUNK), jnp.zeros(SLOTS, jnp.int32)
    valid = jnp.arange(CHUNK)[None] < lengths[:, None]
    rows = jnp.stack([jnp.maximum(lengths - 1, 0),
                      jnp.asarray([0, 3, 7, 5], jnp.int32)], axis=1)
    want, two = fwd(tokens, cache, tables, ctx, valid=valid)
    got, one = fwd(tokens, cache, tables, ctx, valid=valid, rows=rows)
    _same(got, want, rows)
    _same_cache(one, two)


@pytest.mark.parametrize("n_valid", [CHUNK, 5], ids=["mid", "final_padded"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_rows_of_a_mixed_call_are_the_rows_of_every_rows_logits(family,
                                                                n_valid):
    """Slots 0 and 1 decode (contexts 5 and 9), slot 2's chunk rides along
    at context 8: the ``slots + 1`` rows ``decode_chunk`` reads - every
    slot's row and the chunk's last real one - of ``slots + chunk``."""
    module, _, cache, fwd, tok, tables = _forward(family)
    recurrent = "slots" in FAMILIES[family][2]
    lens = jnp.asarray([5, 9, 8, 0], jnp.int32)
    _, cache = fwd(tok(SLOTS, 12), cache, tables, jnp.zeros(SLOTS, jnp.int32),
                   valid=jnp.arange(12)[None] < lens[:, None])
    call = MixedCall(tables, lens, jnp.asarray([True, True, False, False]),
                     tables[2], lens[2], jnp.int32(n_valid),
                     *([jnp.int32(2)] if recurrent else []))
    tokens = tok(1, SLOTS + CHUNK)
    valid = call.valid(SLOTS + CHUNK)
    rows = jnp.asarray([[0, 1, 2, 3, SLOTS + n_valid - 1]], jnp.int32)
    want, two = fwd(tokens, cache, call, None, valid=valid)
    got, one = fwd(tokens, cache, call, None, valid=valid, rows=rows)
    assert got.shape == (1, SLOTS + 1, want.shape[-1])
    _same(got, want, rows)
    _same_cache(one, two)


# --- the engine's programs -------------------------------------------------- #
CONFIG = {"dtype": "float32", "prefill_bucket": CHUNK,
          "split_prefill_chunk": CHUNK, "trace": {"enabled": True},
          "ragged": {"max_tracked_sequences": SLOTS,
                     "max_ragged_batch_size": SLOTS,
                     "memory_config_blocks": 70, "block_size": BLOCK}}


def _scores_every_row(module):
    """``module.apply_paged`` as a caller's own callable whose head scores
    every row, ``rows`` picked from the result: ISSUE 44's parent's
    forward."""
    def apply_paged(cfg, params, tokens, cache, tables, ctx, *, valid=None,
                    rows=None, **kw):
        logits, cache = module.apply_paged(cfg, params, tokens, cache,
                                           tables, ctx, valid=valid, **kw)
        return gather_rows(logits, rows), cache

    return apply_paged


def _engines(family):
    """(the engine as ``build_engine_v2`` makes it, one of the same weights
    around a caller-supplied ``apply_paged`` that scores every row)."""
    module, cfg, params, *_ = _family(family)
    mesh_lib.set_mesh(None)
    one = build_engine_v2(module, cfg, params, config=CONFIG)
    two = InferenceEngineV2(
        ModelFamily.from_module(module, cfg), params,
        InferenceConfig.from_dict(CONFIG),
        init_paged_cache=module.init_paged_cache,
        apply_paged=_scores_every_row(module))
    return one, two


def _serve(eng):
    """A seeded run through ``ServingScheduler.tick``: a prompt of three
    chunks, two that fit one chunk (a one-shot prefill with nothing in
    flight, the chunk lane beside a program) and a sampled one; returns
    every request's tokens."""
    rng = np.random.default_rng(44)
    sched = ServingScheduler(eng, SchedulerConfig(
        decode_quantum=1, max_admissions_per_tick=1))
    sampled = SamplingParams(temperature=0.8, top_k=20, top_p=0.95)
    handles = [sched.submit(Request(
        prompt=rng.integers(1, 200, n).tolist(), max_new_tokens=6, sp=sp))
        for n, sp in ((5, SamplingParams(greedy=True)),
                      (21, SamplingParams(greedy=True)), (7, sampled),
                      (13, SamplingParams(greedy=True)))]
    for _ in range(100):
        if not sched.pending:
            break
        sched.tick()
    assert not sched.pending
    return [list(h.tokens) for h in handles]


def _launches(eng):
    """(span, rows, head_rows) of every launch (a mixed step's chunk also
    has a ``prefill_chunk`` record in its request's lifecycle: no rows)."""
    return [(e["name"], e["args"]["rows"], e["args"]["head_rows"])
            for e in eng.tracer.events() if e["ph"] == "X"
            and e["name"] in ("prefill_batch", "prefill_chunk",
                              "decode_step") and "head_rows" in e["args"]]


@pytest.mark.parametrize("family", SERVED)
def test_the_engine_serves_the_tokens_of_the_programs_that_score_every_row(
        family):
    """``prefill``, the mixed ``decode_chunk`` (mid and final chunks) and
    ``decode`` with the rows handed over, against the same programs around
    an ``apply_paged`` whose head scores every row: the same tokens for
    every request, the same programs launched over the same rows, and
    ``head_rows`` says what each program read - ``slots + 1`` of a mixed
    step's ``slots + chunk``, one a sequence of a prefill's ``n x pad_t``
    (what a caller's own callable does with ``rows`` is its business: the
    engine counts what it asked for)."""
    one, two = _engines(family)
    assert _serve(one) == _serve(two)
    mine, theirs = _launches(one), _launches(two)
    assert mine == theirs
    mixed = [(r, h) for n, r, h in mine if n == "decode_step" and r > SLOTS]
    assert mixed and set(mixed) == {(SLOTS + CHUNK, SLOTS + 1)}
    assert {(r, h) for n, r, h in mine if n == "decode_step"
            and r == SLOTS} <= {(SLOTS, SLOTS)}
    prefills = [(r, h) for n, r, h in mine if n == "prefill_batch"]
    assert prefills and all(r == h * CHUNK for r, h in prefills)
    events = dict((k, v) for k, v, _ in one.engine_events())
    assert events["Serving/engine/rows"] == sum(r for _, r, _ in mine)
    assert events["Serving/engine/head_rows"] == sum(h for *_, h in mine) \
        < events["Serving/engine/rows"]
