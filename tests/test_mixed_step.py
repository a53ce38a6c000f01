"""A prompt chunk rides in the decode program (ISSUE 32; since ISSUE 46 in
every family and from every caller: it is the one chunk program).

Every family's ``apply_paged`` takes a mixed call (``_paged.MixedCall``:
every slot's decode token and one sequence's chunk as ONE row dimension) and
must give what the chunk-then-decode pair of ``[b, t]`` calls gives - the
forms ``prefill`` and ``decode`` keep making -: the same K/V blocks (and
state rows), the same logits for the decode rows and the chunk's last real
row. Held here at tiny sizes on the CPU: the forward alone in float32, then
the engine's ``step()`` against the UNSPLIT engine of the same weights
(``split_prefill_chunk=0``: one-shot ``prefill`` then ``decode``), to the
token, and what its one span says.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference import SamplingParams, build_engine_v2
from deepspeed_tpu.models import (exaone4, falcon, gpt, granite_hybrid, llama,
                                  mixtral)
from deepspeed_tpu.models._paged import MixedCall

SLOTS, BLOCK, WIDTH, BLOCKS, CHUNK = 4, 4, 8, 40, 8
F32 = jnp.float32

# family -> (module, config, what its paged cache takes beside the geometry)
FAMILIES = {
    "llama": (llama, lambda: llama.LlamaConfig.tiny(max_seq_len=32), {}),
    "llama.int8": (llama, lambda: llama.LlamaConfig.tiny(max_seq_len=32),
                   {"kv_quant_group": 8}),
    "mixtral": (mixtral, lambda: mixtral.MixtralConfig.tiny(max_seq_len=32),
                {}),
    # the call and the tick, not the ten-layer period
    # (tests/test_granite_hybrid.py keeps that): a run of each kind, repeated
    "granite_hybrid": (granite_hybrid,
                       lambda: granite_hybrid.GraniteHybridConfig.tiny(
                           max_seq_len=32,
                           layer_types=("mamba", "attention") * 2),
                       {"slots": SLOTS}),
    "gpt": (gpt, lambda: gpt.GPTConfig.tiny(max_seq_len=32), {}),
    "falcon": (falcon, lambda: falcon.FalconConfig.tiny(max_seq_len=32), {}),
    "exaone4": (exaone4, lambda: exaone4.Exaone4Config.tiny(max_seq_len=32),
                {}),
}


@functools.cache
def _forward(family):
    """``(module, config, weights, forward)`` of a family, once a module:
    ``forward`` is its ``apply_paged`` in float32 under ONE ``jax.jit``, so
    the cases of a family (their contexts and real rows are values of the
    program) share its four compiled shapes."""
    module, make, _ = FAMILIES[family]
    cfg = make()
    params = module.init(cfg, jax.random.PRNGKey(0))
    return module, cfg, params, jax.jit(functools.partial(
        module.apply_paged, cfg, params, compute_dtype=F32))


def _tables():
    """Slot i owns blocks ``1 + i * WIDTH ..``; block 0 is the trash."""
    return np.arange(1, 1 + SLOTS * WIDTH, dtype=np.int32).reshape(SLOTS,
                                                                    WIDTH)


def _written(cache, state=()):
    """The cache without what padded rows may scribble on: the trash block,
    and the trash row of a recurrent family's ``state`` leaves."""
    return {n: np.asarray(c[:, :-1] if n in state else c[:, 1:], np.float32)
            for n, c in cache.items()}


@pytest.mark.parametrize("n_valid", [CHUNK, 5, 2],
                         ids=["mid", "final_padded", "mostly_padding"])
@pytest.mark.parametrize("ctx", [0, 8], ids=["first_chunk", "later_chunk"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_mixed_call_is_the_chunk_then_the_decode(family, ctx, n_valid):
    """Slots 0 and 1 decode (contexts 5 and 9), slot 2 is the prefilling
    sequence whose chunk rides along - not active -, slot 3 is free. In
    float32 the one call's logits (decode rows, the chunk's last real row)
    and every block and state row it wrote equal the two calls'. A first
    chunk that is mostly padding is what a short prompt admitted beside a
    program in flight rides in (ISSUE 37): its padded rows write no block
    but the trash."""
    module, cfg, _, fwd = _forward(family)
    cache_kw = FAMILIES[family][2]
    recurrent = "slots" in cache_kw
    cache = module.init_paged_cache(cfg, BLOCKS, BLOCK, dtype=F32, **cache_kw)
    rng = np.random.default_rng(ctx * 16 + n_valid)
    tok = lambda *shape: jnp.asarray(rng.integers(1, cfg.vocab_size, shape),
                                     jnp.int32)
    tables = jnp.asarray(_tables())
    lens = jnp.asarray([5, 9, ctx, 0], jnp.int32)
    # the contexts, by one prefill over the slots in order
    _, cache = fwd(tok(SLOTS, 12), cache, tables, jnp.zeros(SLOTS, jnp.int32),
                   valid=jnp.arange(12)[None] < lens[:, None])
    active = jnp.asarray([True, True, False, False])
    last, chunk = tok(SLOTS), tok(1, CHUNK)
    chunk_valid = (jnp.arange(CHUNK) < n_valid)[None]

    slot = {"slots": jnp.asarray([2], jnp.int32)} if recurrent else {}
    want_c, two = fwd(chunk, cache, tables[2][None], lens[2][None],
                      valid=chunk_valid, **slot)
    want_d, two = fwd(last[:, None], two, tables, lens,
                      valid=active[:, None])

    call = MixedCall(tables, lens, active, tables[2], lens[2],
                     jnp.int32(n_valid),
                     *([jnp.int32(2)] if recurrent else []))
    rows = jnp.concatenate([last, chunk[0]])[None]
    got, one = fwd(rows, cache, call, None, valid=call.valid(SLOTS + CHUNK))

    assert got.shape == (1, SLOTS + CHUNK, cfg.vocab_size)
    np.testing.assert_allclose(got[0, :2], want_d[:2, 0], atol=2e-5)
    np.testing.assert_allclose(got[0, SLOTS + n_valid - 1],
                               want_c[0, n_valid - 1], atol=2e-5)
    state = getattr(module, "STATE_LEAVES", ())
    one, two = _written(one, state), _written(two, state)
    assert sorted(one) == sorted(two)
    for name in one:
        np.testing.assert_allclose(one[name], two[name], atol=2e-5,
                                   err_msg=name)


def test_row_positions_of_both_kinds_of_call():
    from deepspeed_tpu.models._paged import row_positions

    lens = jnp.asarray([5, 9, 0], jnp.int32)
    np.testing.assert_array_equal(
        row_positions(None, lens, 2), [[5, 6], [9, 10], [0, 1]])
    call = MixedCall(jnp.zeros((3, 2), jnp.int32), lens,
                     jnp.ones(3, bool), jnp.zeros(2, jnp.int32),
                     jnp.int32(8), jnp.int32(3))
    np.testing.assert_array_equal(row_positions(call, None, 7),
                                  [[5, 9, 0, 8, 9, 10, 11]])
    np.testing.assert_array_equal(
        call.valid(7), [[True] * 3 + [True] * 3 + [False]])


# --- the engine's step() --------------------------------------------------- #
def _engine(family, split=CHUNK, trace=False, **extra):
    """``split=0``: the UNSPLIT engine, whose prompts are one-shot
    ``prefill`` calls and whose steps are ``decode`` alone."""
    module, cfg, params, _ = _forward(family)
    cache_kw = FAMILIES[family][2]
    mesh_lib.set_mesh(None)
    config = {"prefill_bucket": CHUNK, "split_prefill_chunk": split,
              "ragged": {"max_tracked_sequences": SLOTS,
                         "max_ragged_batch_size": SLOTS,
                         "memory_config_blocks": BLOCKS,
                         "block_size": BLOCK}}
    if "kv_quant_group" in cache_kw:
        config["kv_quant"] = {"enabled": True, "group_size": 8}
    if trace:
        config["trace"] = {"enabled": True}
    config.update(extra)
    return build_engine_v2(module, cfg, params, config=config)


def _prompts():
    rng = np.random.RandomState(11)
    return [rng.randint(1, 200, n).tolist() for n in (5, 9, 21)]


def _admit(eng, sp=SamplingParams(greedy=True), split=True):
    """Two live sequences and a slot left free; ``split``: and a split
    prompt of three chunks (8, 8, 5)."""
    a, b, c = _prompts()
    eng.put(1, a)
    eng.put(2, b, sp)
    if split:
        eng.put_split(3, c, sp)


def _streams(outs):
    """``{uid: tokens}`` of a run of ``step_many`` / speculative ``step``
    results, ``{uid: [tokens]}`` each."""
    streams = {}
    for out in outs:
        for uid, toks in out.items():
            streams.setdefault(uid, []).extend(toks)
    return streams


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_mixed_step_serves_what_the_two_programs_serve(family):
    """bf16, to the token: an engine whose steps run the chunk inside the
    decode program streams the tokens of the UNSPLIT one - which runs the
    prompt as a one-shot ``prefill`` where the split one's final chunk ran,
    and ``decode`` alone - and from there on leaves the same blocks (and
    state rows) behind; mid chunks, the final one, and the slot it seats -
    which has its first token only, and decodes from the next step on."""
    one, two = _engine(family), _engine(family, split=0)
    _admit(one)
    _admit(two, split=False)
    for step in range(5):
        out, want = one.step(seed=step), two.step(seed=step)
        if step == 2:
            want[3] = two.put(3, _prompts()[2])
        assert out == want
        assert (3 in out) == (step >= 2)
        assert one.last_step["decode_seqs"] == two.last_step["decode_seqs"]
        assert one.last_step["kv_tokens"] == two.last_step["kv_tokens"]
        d = one.state.seqs[3]
        if step == 2:       # seated by this step's final chunk: not decoded
            assert (d.seen_tokens, len(d.generated), d.prefilling) \
                == (21, 1, False)
            assert one.last_step["decode_seqs"] == 2
        if step < 2:
            continue        # the unsplit engine has not met the prompt yet
        state = one.family.state_leaves
        for name, got in _written(one.cache, state).items():
            np.testing.assert_allclose(
                got, _written(two.cache, state)[name], atol=0.05,
                err_msg=f"{name} after step {step}")
    assert (one.steps, one.mixed_steps, two.mixed_steps) == (5, 3, 0)
    assert one.state.seqs[3].seen_tokens == 21 + 2
    assert [k for k in one._paged_fns if k[0].startswith("decode_chunk")] \
        == [("decode_chunk", CHUNK)]        # ONE program, mid and final
    assert not any(k[0].startswith("decode_chunk") for k in two._paged_fns)
    # the rows the programs ran and the rows their heads scored (ISSUE 44):
    # two one-shot prefills (8 and 16 rows, one read each), three mixed
    # calls (4 slots + 8 chunk rows, 4 + 1 read) and two decodes (4 of 4);
    # the unsplit engine's third prefill ran 24 rows and read one
    assert dict((n, v) for n, v, _ in one.engine_events()) == {
        "Serving/engine/steps": 5.0, "Serving/engine/mixed_steps": 3.0,
        "Serving/engine/overlapped_steps": 0.0,
        "Serving/engine/rows": 8.0 + 16 + 3 * 12 + 2 * 4,
        "Serving/engine/head_rows": 1.0 + 1 + 3 * 5 + 2 * 4}
    assert (two.rows, two.head_rows) == (8 + 16 + 24 + 5 * 4,
                                         1 + 1 + 1 + 5 * 4)
    for uid in (1, 2, 3):
        assert one.finish(uid) == two.finish(uid)


def test_a_stochastic_request_takes_the_rows_variant_once():
    """Per-row sampling arrays (the slots' and then the chunk's) in one more
    program, whatever the mix; a mid chunk samples greedily for nothing."""
    sp = SamplingParams(temperature=0.7, top_k=5, top_p=0.9)
    eng = _engine("llama")
    _admit(eng, sp)
    for step in range(4):
        out = eng.step(seed=step)
        assert all(0 <= t < 256 for t in out.values())
    assert eng.mixed_steps == 3
    assert sorted(k[0] for k in eng._paged_fns
                  if k[0].startswith("decode")) \
        == ["decode_chunk_dyn", "decode_dyn"]


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_chunk_with_nothing_live_runs_the_same_one_program(family):
    """A split prompt alone in the engine (a server's start-up, a lone
    request): its chunks run ``decode_chunk`` with no active slot - the
    program the loaded server runs, so nothing else is lowered for them -,
    under ``prefill_chunk`` spans whose MoE rows are the call's, chunk after
    chunk in one step, and serve what the unsplit engine's one-shot
    ``prefill`` serves."""
    one, two = _engine(family, trace=True), _engine(family, split=0)
    prompt = np.random.RandomState(5).randint(1, 200, 21).tolist()
    one.put_split(3, prompt)
    assert one.step(seed=0) == {3: two.put(3, prompt)}  # the first token
    assert one.step(seed=1) == two.step(seed=1)         # a decode alone
    assert one.mixed_steps == 0         # counts chunks BESIDE decodes
    assert {k[0] for k in one._paged_fns} == {"decode_chunk", "decode"}
    assert {k[0] for k in two._paged_fns} == {"prefill", "decode"}
    chunks = [e for e in one.tracer.events() if e["name"] == "prefill_chunk"]
    assert [(c["args"]["tokens"], c["args"]["final"], c["args"]["rows"],
             c["args"]["head_rows"]) for c in chunks] \
        == [(8, False, SLOTS + CHUNK, SLOTS + 1)] * 2 \
        + [(5, True, SLOTS + CHUNK, SLOTS + 1)]
    if one.family.moe_rows:
        want = one.family.moe_rows(one.family.cfg, SLOTS + CHUNK)
        assert all({k: c["args"][k] for k in want} == want for c in chunks)
    state = one.family.state_leaves
    for name, got in _written(one.cache, state).items():
        np.testing.assert_allclose(got, _written(two.cache, state)[name],
                                   atol=0.05, err_msg=name)


@pytest.mark.parametrize("mode", ["quantum", "spec"])
def test_speculation_and_the_fused_quantum_take_their_chunk_through_decode_chunk(
        mode):
    """``step_many`` and a speculative ``step()`` advance a split prompt
    through ``decode_chunk`` with no slot active (never beside their
    decodes: ``mixed_steps`` 0), hand back the prompt's first token in the
    call whose chunk completes it - a 1-list -, and stream, greedily, what
    the unsplit engine streams."""
    extra = {} if mode == "quantum" else {
        "speculative": {"enabled": True, "max_draft_tokens": 3}}
    call = (lambda e: e.step_many(2)) if mode == "quantum" \
        else (lambda e: e.step())
    one, two = _engine("llama", **extra), _engine("llama", split=0, **extra)
    _admit(one)
    _admit(two, split=False)
    first = two.put(3, _prompts()[2])
    outs = [call(one) for _ in range(4)]
    assert [3 in out for out in outs] == [False, False, True, True]
    assert outs[2][3] == [first]
    got = _streams(outs)
    want = _streams(call(two) for _ in range(4))
    want[3].insert(0, first)
    for uid in (1, 2, 3):
        assert got[uid] == want[uid][:len(got[uid])] and len(got[uid]) >= 2
    assert one.mixed_steps == 0
    assert [k[0] for k in one._paged_fns if "chunk" in k[0]] \
        == ["decode_chunk"]
    assert one.in_flight == 0 and sum(one.drains.values()) == 0


# --- the span contract (what the benchmark's readers rest on) --------------- #
def _step_spans(eng, fn):
    seen = len(eng.tracer.events())
    fn()
    return [e for e in eng.tracer.events()[seen:] if e["ph"] == "X"]


def test_moe_rows_lie_on_one_span_a_device_pass():
    """Over a short served run of a MoE family: every dispatch is one pass
    through the expert bank, and exactly one span carries its MoE rows - a
    mixed step's ``decode_step``, whose rows are the whole call's (slots +
    chunk), while ``batch`` counts the decode rows alone and the chunk's
    facts ride as ``chunk_*``. The request's ring-only ``prefill_chunk``
    carries none."""
    eng = _engine("mixtral", trace=True)
    moe = eng.family.moe_rows
    spans = _step_spans(eng, lambda: _admit(eng))
    for step in range(5):
        spans += _step_spans(eng, lambda: eng.step(seed=step))
    passes = [e for e in spans if e["name"] == "engine_dispatch"]
    carrying = [e for e in spans if "moe_rows_routed" in e["args"]]
    assert len(carrying) == len(passes) == 2 + 5
    decodes = [e for e in carrying if e["name"] == "decode_step"]
    assert [d["args"]["chunk_tokens"] for d in decodes] == [8, 8, 5, 0, 0]
    assert [d["args"]["batch"] for d in decodes] == [2, 2, 2, 3, 3]
    for d in decodes:
        rows = SLOTS + (CHUNK if d["args"]["chunk_tokens"] else 0)
        assert {k: d["args"][k] for k in moe(eng.family.cfg, rows)} \
            == moe(eng.family.cfg, rows)
    mixed = decodes[:3]
    assert [(d["args"]["chunk_uid"], d["args"]["chunk_ctx"],
             d["args"]["chunk_final"], d["args"]["chunk_kv_blocks"])
            for d in mixed] == [(3, 0, False, 2), (3, 8, False, 4),
                                (3, 16, True, 6)]
    chunks = [e for e in spans if e["name"] == "prefill_chunk"]
    assert [(c["args"]["tokens"], c["args"]["ctx"]) for c in chunks] \
        == [(8, 0), (8, 8), (5, 16)]
    assert not any(k.startswith(("moe_", "ssm_")) for c in chunks
                   for k in c["args"])
    assert len(eng._lat["ttft_ms"]) == 3        # the split request's too


def test_ssm_rows_and_batch_count_the_decode_rows_alone():
    eng = _engine("granite_hybrid", trace=True)
    _admit(eng)
    for step in range(4):
        spans = _step_spans(eng, lambda: eng.step(seed=step))
        (decode,) = [e for e in spans if e["name"] == "decode_step"]
        live = 2 if step < 3 else 3
        assert (decode["args"]["batch"], decode["args"]["ssm_rows"],
                decode["args"]["ssm_tokens"]) == (live, live, live)
        chunk = [8, 8, 5, 0][step]
        assert decode["args"]["chunk_tokens"] == chunk
        # ``last_step`` counts what the two calls counted (and, with the
        # first step, the two one-shot prefills admitted before it)
        rows, tokens = (2, 5 + 9) if step == 0 else (0, 0)
        assert eng.last_step["ssm_rows"] == rows + live + bool(chunk)
        assert eng.last_step["ssm_tokens"] == tokens + live + chunk
        assert eng.last_step["prefill_tokens"] == chunk
