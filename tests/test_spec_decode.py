"""Speculative decoding tests (docs/serving.md): prompt-lookup drafting,
batched verification over the paged cache, exact rejection sampling for
non-greedy requests, KV rollback (``StateManager.truncate``) incl. rollback
into shared/forked prefix blocks, the default-OFF parity pin, and the
``Serving/spec/*`` telemetry surface."""

import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference import (InferenceConfig, SamplingParams,
                                     build_engine_v2, prompt_lookup_draft)
from deepspeed_tpu.inference.ragged import StateManager
from deepspeed_tpu.inference.sampling import filter_logits
from deepspeed_tpu.models import llama
from deepspeed_tpu.models._paged import gather_rows

SP = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(max_seq_len=256)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def build(tiny, spec_on=True, blocks=64, block_size=16, slots=4, k=4, **kw):
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    return build_engine_v2(
        llama, cfg, params,
        config=dict({"dtype": "float32", "prefill_bucket": 16,
                     "speculative": {"enabled": spec_on,
                                     "max_draft_tokens": k},
                     "ragged": {"max_tracked_sequences": slots,
                                "max_ragged_batch_size": slots,
                                "memory_config_blocks": blocks,
                                "block_size": block_size}}, **kw))


# module-scoped engines: program compiles dominate these tests' wall time,
# and generate() drains every sequence, so parity tests can share instances
@pytest.fixture(scope="module")
def eng_off(tiny):
    return build(tiny, spec_on=False)


@pytest.fixture(scope="module")
def eng_spec(tiny):
    return build(tiny, spec_on=True)


def _pattern_module(vocab, break_every=0, fixed_logits=None, max_seq_len=128):
    """Deterministic fake family for precise spec-decode control.

    Default rule: the next token after token ``t`` at absolute position ``p``
    is ``(t + 1) % vocab`` — greedy decode walks a cycle the prompt-lookup
    drafter nails, so acceptance is total and countable. ``break_every=n``
    deviates to ``(t + 2) % vocab`` whenever ``n`` divides ``p + 1``: the
    drafter (which replays history) mispredicts exactly at the breaks, so
    rejection + KV rollback run on a known schedule. ``fixed_logits`` (a
    [vocab] vector) instead makes every position's distribution that vector —
    the known target for the rejection-sampling distribution test."""
    fixed = None if fixed_logits is None \
        else jnp.asarray(fixed_logits, jnp.float32)

    def _next_logits(tokens, positions):
        if fixed is not None:
            return jnp.broadcast_to(fixed, tokens.shape + fixed.shape)
        nxt = (tokens + 1) % vocab
        if break_every:
            nxt = jnp.where((positions + 1) % break_every == 0,
                            (tokens + 2) % vocab, nxt)
        return 8.0 * jax.nn.one_hot(nxt, vocab, dtype=jnp.float32)

    def apply(cfg, params, tokens):
        b, t = tokens.shape
        pos = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
        return _next_logits(tokens, pos)

    def apply_cached(cfg, params, tokens, cache, cache_len):
        if getattr(cache_len, "ndim", 0) == 0:
            cache_len = jnp.broadcast_to(cache_len, (tokens.shape[0],))
        pos = cache_len[:, None] + jnp.arange(tokens.shape[1])[None, :]
        return _next_logits(tokens, pos), cache

    def apply_paged(cfg, params, tokens, cache, tables, ctx, valid=None,
                    rows=None, **kw):
        pos = ctx[:, None] + jnp.arange(tokens.shape[1])[None, :]
        # ``rows=`` is part of the contract: the rows the program reads
        return gather_rows(_next_logits(tokens, pos), rows), cache

    mod = types.SimpleNamespace(
        apply=apply, apply_cached=apply_cached,
        init_cache=lambda cfg, b, n: {"kv": jnp.zeros((1, 2), jnp.float32)},
        init_paged_cache=lambda cfg, nb, bs: {
            "kv": jnp.zeros((1, nb), jnp.float32)},
        apply_paged=apply_paged,
        param_logical_axes=lambda cfg: {"w": (None,)})
    cfg = types.SimpleNamespace(max_seq_len=max_seq_len, vocab_size=vocab)
    params = {"w": np.zeros((4,), np.float32)}
    return mod, cfg, params


def build_stub(vocab=8, break_every=0, fixed_logits=None, k=4, slots=2,
               blocks=32, block_size=8, spec_on=True, **kw):
    mod, cfg, params = _pattern_module(vocab, break_every, fixed_logits)
    mesh_lib.set_mesh(None)
    return build_engine_v2(
        mod, cfg, params,
        config=dict({"dtype": "float32", "prefill_bucket": 8,
                     "speculative": {"enabled": spec_on,
                                     "max_draft_tokens": k},
                     "ragged": {"max_tracked_sequences": slots,
                                "max_ragged_batch_size": slots,
                                "memory_config_blocks": blocks,
                                "block_size": block_size}}, **kw))


def _stub_reference(prompt, n_new, vocab, break_every=0):
    """Sequential greedy oracle for `_pattern_module`: t[p+1] = f(t[p], p)."""
    seq = list(prompt)
    out = []
    for _ in range(n_new):
        p = len(seq) - 1
        t = seq[-1]
        nxt = (t + 2) % vocab if break_every and (p + 1) % break_every == 0 \
            else (t + 1) % vocab
        out.append(nxt)
        seq.append(nxt)
    return out


# --------------------------------------------------------------------------- #
# config + drafter
# --------------------------------------------------------------------------- #
def test_spec_config_defaults_off():
    assert InferenceConfig().speculative.enabled is False
    assert InferenceConfig.from_dict({}).speculative.enabled is False
    c = InferenceConfig.from_dict(
        {"speculative": {"enabled": True, "max_draft_tokens": 6,
                         "ngram_max": 2, "min_match": 2}})
    assert c.speculative.enabled and c.speculative.max_draft_tokens == 6
    assert c.speculative.ngram_max == 2 and c.speculative.min_match == 2


def test_prompt_lookup_draft_basics():
    # trailing [1,2,3] matched at the start; the continuation follows it
    assert prompt_lookup_draft([1, 2, 3, 4, 1, 2, 3], 3) == [4, 1, 2]
    # clamp to max_tokens
    assert prompt_lookup_draft([1, 2, 3, 4, 1, 2, 3], 1) == [4]
    # nothing repeats → no draft
    assert prompt_lookup_draft([1, 2, 3, 4, 5], 4) == []
    assert prompt_lookup_draft([7], 4) == []
    assert prompt_lookup_draft([1, 2], 0) == []


def test_prompt_lookup_draft_recency_and_min_match():
    # [1,2] occurs twice; the MOST RECENT occurrence wins → continuation 8
    h = [5, 9, 1, 2, 7, 1, 2, 8, 1, 2]
    assert prompt_lookup_draft(h, 2, ngram_max=2)[0] == 8
    # min_match=2 rejects the 1-gram fallback that min_match=1 finds
    h2 = [3, 1, 4, 1]
    assert prompt_lookup_draft(h2, 2, ngram_max=2, min_match=1) == [4, 1]
    assert prompt_lookup_draft(h2, 2, ngram_max=2, min_match=2) == []
    # the trailing n-gram can never match itself (would draft nothing new)
    assert prompt_lookup_draft([6, 6], 2, ngram_max=1) == [6]


# --------------------------------------------------------------------------- #
# default-OFF parity pin + greedy bit-identity
# --------------------------------------------------------------------------- #
def test_spec_off_is_default_and_runs_pre_spec_programs(tiny, eng_off):
    rng = np.random.default_rng(0)
    cfg, _ = tiny
    p = rng.integers(0, cfg.vocab_size, (20,), dtype=np.int32).tolist()
    first = eng_off.put(1, p, SP)
    out = eng_off.step(SP)
    assert isinstance(out[1], int)         # spec off: unwrapped tokens
    assert not any(k[0] == "spec_verify" for k in eng_off._paged_fns)
    assert eng_off.spec_stats["verify_steps"] == 0
    assert isinstance(first, int)
    eng_off.finish(1)


def test_greedy_spec_bit_identical_to_plain_decode(tiny, eng_off, eng_spec):
    """Acceptance: with spec on and greedy sampling, generated tokens are
    bit-identical to non-spec decode while drafts are actually verified."""
    cfg, _ = tiny
    rng = np.random.default_rng(1)
    pat = rng.integers(0, cfg.vocab_size, (6,), dtype=np.int32).tolist()
    prompts = [(pat * 6)[:32],
               rng.integers(0, cfg.vocab_size, (23,), dtype=np.int32).tolist()]
    want = eng_off.generate(prompts, max_new_tokens=12)
    base = dict(eng_spec.spec_stats)
    got = eng_spec.generate(prompts, max_new_tokens=12)
    assert got == want
    assert eng_spec.spec_stats["drafted_tokens"] > base["drafted_tokens"]
    assert eng_spec.spec_stats["verify_steps"] > base["verify_steps"]
    eng_spec.state.debug_check()
    # steps_per_sync is subsumed by spec (step() already batches tokens);
    # same engine: programs are cached, so this replays deterministically
    got2 = eng_spec.generate(prompts, max_new_tokens=12, steps_per_sync=4)
    assert got2 == want


def test_greedy_spec_parity_composes_with_prefix_cache(tiny, eng_off):
    """Spec + prefix cache together still match the plain engine: drafts can
    roll back into COW'd / shared-prefix territory without corrupting
    either sequence."""
    cfg, _ = tiny
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, (16,), dtype=np.int32)
    pat = rng.integers(0, cfg.vocab_size, (4,), dtype=np.int32)
    pa = np.concatenate([shared, np.tile(pat, 2)])
    pb = np.concatenate([shared, pat])
    want = [eng_off.generate([p], max_new_tokens=6)[0] for p in (pa, pb)]
    eng = build(tiny, spec_on=True, prefix_cache={"enabled": True})
    # sequential arrivals so pb resolves pa's retained shared-prefix blocks
    got = [eng.generate([p], max_new_tokens=6)[0] for p in (pa, pb)]
    assert got == want
    assert eng.state.prefix_stats["hit_tokens"] > 0
    eng.state.debug_check()


def test_greedy_spec_after_a_fork_matches_plain_decode(tiny):
    """A fork shares every block with its parent, the partial tail too:
    each side's verify window must copy-on-write before it writes drafts,
    and a rollback into the shared tail must not reach the other side.
    Both streams stay the plain engine's, token for token."""
    cfg, _ = tiny
    rng = np.random.default_rng(1)
    pat = rng.integers(0, cfg.vocab_size, (6,), dtype=np.int32).tolist()
    prompt = (pat * 6)[:32]

    def run(spec_on, enough):
        eng = build(tiny, spec_on=spec_on)
        eng.put(1, prompt, SP)
        eng.step(SP)
        eng.fork(1, 2)
        i = 0
        while not enough(eng):
            eng.step(SP, seed=i)
            i += 1
        eng.state.debug_check()
        return eng, {u: list(eng.state.seqs[u].generated) for u in (1, 2)}

    spec, got = run(True, lambda e: e.spec_stats["verify_steps"] >= 4)
    assert spec.spec_stats["drafted_tokens"] > 0
    _, want = run(False, lambda e: all(
        len(e.state.seqs[u].generated) >= len(got[u]) for u in (1, 2)))
    for u in (1, 2):
        assert got[u] == want[u][:len(got[u])] and len(got[u]) > 4


def test_greedy_spec_on_a_windowed_family_matches_plain_decode():
    """exaone4's scanned per-layer sliding windows reach the verify pass
    as the same traced window scalar the decode kernel takes: verification
    over a hybrid-attention family emits the plain engine's stream."""
    from deepspeed_tpu.models import exaone4

    cfg = exaone4.Exaone4Config.tiny(max_seq_len=128)
    params = exaone4.init(cfg, jax.random.PRNGKey(0))
    mesh_lib.set_mesh(None)

    def mk(spec_on):
        return build_engine_v2(
            exaone4, cfg, params,
            config={"dtype": "float32", "prefill_bucket": 16,
                    "speculative": {"enabled": spec_on,
                                    "max_draft_tokens": 3},
                    "ragged": {"max_tracked_sequences": 2,
                               "max_ragged_batch_size": 2,
                               "memory_config_blocks": 32,
                               "block_size": 16}})

    rng = np.random.default_rng(5)
    pat = rng.integers(0, cfg.vocab_size, (5,), dtype=np.int32).tolist()
    prompts = [(pat * 6)[:24]]
    want = mk(False).generate(prompts, max_new_tokens=10)
    eng = mk(True)
    assert eng.generate(prompts, max_new_tokens=10) == want
    assert eng.spec_stats["verify_steps"] > 0
    assert eng.spec_stats["drafted_tokens"] > 0


# --------------------------------------------------------------------------- #
# deterministic acceptance / rejection via the stub family
# --------------------------------------------------------------------------- #
def test_full_acceptance_emits_k_plus_one_per_step():
    V, k = 4, 4
    eng = build_stub(vocab=V, k=k)
    prompt = [0, 1, 2, 3, 0, 1, 2, 3]
    first = eng.put(1, prompt, SP)
    assert first == 0                       # (3 + 1) % 4
    toks = [first]
    steps = 0
    while len(toks) < 17:
        out = eng.step(SP, seed=steps)
        toks += out[1]
        steps += 1
        eng.state.debug_check()
    want = _stub_reference(prompt, len(toks), V)
    assert toks == want
    s = eng.spec_stats
    # the cycle is drafted perfectly: every verify step accepts all k drafts
    # and emits the bonus token on top
    assert s["decode_steps"] == 0 and s["verify_steps"] == steps
    assert s["accepted_tokens"] == s["drafted_tokens"] > 0
    assert s["rolled_back_tokens"] == 0
    assert s["emitted_tokens"] / s["step_seqs"] == k + 1
    ev = dict((n.rsplit("/", 1)[1], v) for n, v, _ in eng.spec_events())
    assert ev["accept_rate"] == 1.0 and ev["tokens_per_step"] == k + 1
    eng.finish(1)


def test_partial_rejection_rolls_back_and_stays_exact():
    """The stub breaks its cycle at every 5th position: drafts replayed from
    history are wrong there, verification rejects mid-window, truncate
    un-fills the rejected KV — and the emitted stream still equals the
    sequential oracle exactly."""
    V, k, brk = 5, 4, 5
    eng = build_stub(vocab=V, break_every=brk, k=k, blocks=24, block_size=4)
    prompt = [0, 1, 2, 3, 0, 1, 2, 3]
    toks = [eng.put(1, prompt, SP)]
    for i in range(12):
        out = eng.step(SP, seed=i)
        toks += out.get(1, [])
        eng.state.debug_check()
    want = _stub_reference(prompt, len(toks), V, break_every=brk)
    assert toks == want
    s = eng.spec_stats
    assert s["rolled_back_tokens"] > 0      # rejections actually rolled back
    assert s["accepted_tokens"] > 0         # and some drafts survived
    assert eng.finish(1) == toks


def test_spec_respects_max_seq_len_boundary():
    """Near max_seq_len the drafter clamps so verification never writes past
    the last KV slot; the sequence still reaches exactly max_seq_len."""
    V = 4
    mod, cfg, params = _pattern_module(V, max_seq_len=24)
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        mod, cfg, params,
        config={"dtype": "float32", "prefill_bucket": 8,
                "speculative": {"enabled": True, "max_draft_tokens": 4},
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 16, "block_size": 8}})
    prompt = [0, 1, 2, 3, 0, 1, 2, 3]
    toks = [eng.put(1, prompt, SP)]
    for i in range(40):
        out = eng.step(SP, seed=i)
        toks += out.get(1, [])
        eng.state.debug_check()
        if eng.state.seqs[1].seen_tokens >= 24:
            break
    d = eng.state.seqs[1]
    assert d.seen_tokens == 24              # filled to the boundary, not past
    assert toks == _stub_reference(prompt, len(toks), V)


# --------------------------------------------------------------------------- #
# exact rejection sampling: distribution test
# --------------------------------------------------------------------------- #
def test_rejection_sampling_matches_plain_sampling_distribution():
    """Statistical equality at a fixed seed budget: with a known fixed
    target distribution, the first token a VERIFY step emits (accepted draft
    or residual correction) must be distributed like plain `sample` — the
    deterministic-drafter rejection-sampling identity."""
    V = 8
    L = np.asarray([2.0, 1.4, 0.9, 0.4, 0.0, -0.5, -1.2, -2.0], np.float32)
    sp = SamplingParams(temperature=0.9, top_k=5)
    p = np.asarray(jax.nn.softmax(filter_logits(jnp.asarray(L), sp)))

    def draw(spec_on, n=400):
        eng = build_stub(vocab=V, fixed_logits=L, k=3, slots=1, blocks=16,
                         block_size=8, spec_on=spec_on)
        counts = np.zeros(V)
        # prompt contains every token id, so whatever first token the
        # prefill samples, the 1-gram fallback finds a match → every
        # measured step is a verify step when spec is on
        prompt = list(range(V)) + [0, 1]
        for i in range(n):
            eng.put(7, prompt, sp, seed=1000 + i)
            out = eng.step(seed=i)
            tok = out[7][0] if spec_on else out[7]
            counts[tok] += 1
            eng.finish(7)
        if spec_on:
            assert eng.spec_stats["verify_steps"] == n
            assert eng.spec_stats["drafted_tokens"] >= n
        return counts / n

    f_spec = draw(True)
    f_plain = draw(False)
    # both within sampling noise of the true distribution, and of each other
    assert np.abs(f_spec - p).max() < 0.08, (f_spec, p)
    assert np.abs(f_plain - p).max() < 0.08, (f_plain, p)
    assert 0.5 * np.abs(f_spec - f_plain).sum() < 0.10


def test_rejected_tokens_outside_topk_always_rejected():
    """A draft outside the request's top-k filter has zero target probability
    and must never be emitted as an accepted draft."""
    V = 6
    L = np.asarray([3.0, 2.5, 2.0, 1.5, -8.0, -9.0], np.float32)
    sp = SamplingParams(temperature=1.0, top_k=2)
    eng = build_stub(vocab=V, fixed_logits=L, k=2, slots=1, blocks=16,
                     block_size=8)
    # whatever first token f ∈ {0, 1} the prefill samples, its earlier
    # occurrence in the prompt continued with 4: the drafter proposes 4 —
    # outside top_k=2, so p(4) = 0 → always rejected, and the residual
    # distribution is the untouched top-2 filter
    prompt = [0, 4, 1, 4, 3]
    for i in range(60):
        eng.put(1, prompt, sp, seed=i)
        out = eng.step(seed=i)
        for t in out[1]:
            assert t in (0, 1), out        # only top-2 tokens ever emitted
        eng.finish(1)
    assert eng.spec_stats["verify_steps"] == 60


# --------------------------------------------------------------------------- #
# KV rollback: StateManager.truncate invariants
# --------------------------------------------------------------------------- #
def test_truncate_releases_blocks_and_trims_state():
    sm = StateManager(4, 32, 4, 16, prefix_cache=True)
    d, _ = sm.admit_prompt(1, list(range(20)))      # 5 full blocks + reserve
    d.seen_tokens = 20
    sm.mark_filled(d)
    assert len(d.block_hashes) == 5
    pairs = sm.truncate(d, 13)
    assert pairs == []                              # private blocks: no COW
    assert d.seen_tokens == 13 and len(d.tokens) == 13
    assert len(d.blocks) == 4                       # ceil(13 / 4)
    assert len(d.block_hashes) == 3                 # 13 // 4 full blocks
    sm.debug_check()
    with pytest.raises(ValueError):
        sm.truncate(d, 0)
    with pytest.raises(ValueError):
        sm.truncate(d, 14)                          # beyond seen_tokens
    sm.retire(1)
    sm.debug_check()


def test_truncate_drops_stale_index_entry_for_private_tail():
    sm = StateManager(4, 32, 4, 16, prefix_cache=True)
    d, _ = sm.admit_prompt(1, list(range(16)))
    d.seen_tokens = 16
    sm.mark_filled(d)                               # 4 full blocks indexed
    tail = d.blocks[3]
    assert sm.index.is_indexed(tail)
    sm.truncate(d, 14)                              # tail now partial
    assert not sm.index.is_indexed(tail)            # stale entry dropped
    sm.debug_check()
    # a later identical admission may only resolve the 3 intact blocks
    d2, hit = sm.admit_prompt(2, list(range(16)))
    assert hit == 12
    sm.debug_check()


def test_truncate_into_shared_prefix_block_cows():
    """Rollback landing INSIDE a block another sequence still references
    must copy-on-write: the other holder keeps the original content."""
    sm = StateManager(4, 32, 4, 16, prefix_cache=True)
    d1, _ = sm.admit_prompt(1, list(range(16)))
    d1.seen_tokens = 16
    sm.mark_filled(d1)
    d2, hit = sm.admit_prompt(2, list(range(16)))   # shares 3 full blocks
    assert hit == 12
    d2.seen_tokens = 16
    shared = d2.blocks[2]                           # positions 8..11, ref 2
    assert sm.allocator.refcount(shared) == 2
    pairs = sm.truncate(d2, 10)                     # rollback INTO block 2
    assert pairs == [(shared, d2.blocks[2])]
    assert d2.blocks[2] != shared
    assert sm.allocator.refcount(shared) == 1       # d1 keeps the original
    assert sm.allocator.refcount(d2.blocks[2]) == 1
    assert d1.blocks[2] == shared
    assert sm.index.is_indexed(shared)              # canonical copy intact
    sm.debug_check()


def test_truncate_into_forked_tail_cows():
    """A freshly forked child shares every block with its parent, including
    the partial tail; rolling the child back INTO that tail must hand it a
    private copy (a write into the shared original would corrupt the
    parent). A child that already COW'd via ensure_writable before decoding
    needs no further copy on rollback."""
    sm = StateManager(4, 32, 4, 16, prefix_cache=True)
    d, _ = sm.admit_prompt(1, list(range(10)))
    d.seen_tokens = 10
    sm.mark_filled(d)
    c = sm.fork(1, 2)
    # rollback straight into the shared partial tail (block 2: pos 8..11)
    shared_tail = d.blocks[2]
    assert sm.allocator.refcount(shared_tail) == 2
    pairs = sm.truncate(c, 9)
    assert len(pairs) == 1
    src, dst = pairs[0]
    assert src == shared_tail and dst == c.blocks[2] != src
    assert sm.allocator.refcount(src) == 1          # parent keeps original
    assert sm.allocator.refcount(dst) == 1
    assert d.blocks[2] == shared_tail
    sm.debug_check()
    # second shape: a child that decoded (ensure_writable already COW'd the
    # write range) rolls back into its own PRIVATE copy → no pairs
    c2 = sm.fork(1, 3)
    sm.ensure_writable(c2, 14)
    sm.extend(c2, n=4)
    c2.tokens.extend([77, 78, 79, 80])
    c2.seen_tokens = 14
    assert sm.truncate(c2, 9) == []
    sm.debug_check()
    sm.retire(3)
    sm.retire(2)
    sm.retire(1)
    sm.debug_check()


def test_truncate_randomized_soak_with_all_ops():
    """Satellite: randomized admit/decode/fork/truncate/finish soak — the
    free/live/retained accounting must hold after every operation."""
    rng = np.random.default_rng(3)
    sm = StateManager(6, 24, 4, 10, prefix_cache=True)
    live = []
    next_uid = 0
    for it in range(400):
        op = rng.integers(0, 5)
        if op == 0 and len(live) < 6:
            n = int(rng.integers(1, 20))
            if sm.can_admit(n):
                d, _ = sm.admit_prompt(
                    next_uid, [int(t) for t in rng.integers(0, 3, n)])
                d.seen_tokens = n
                sm.mark_filled(d)
                live.append(next_uid)
                next_uid += 1
        elif op == 1 and live:                       # decode one token
            d = sm.seqs[rng.choice(live)]
            if (d.seen_tokens + sm.block_size) // sm.block_size + 1 \
                    <= sm.max_blocks_per_seq and sm.can_admit(1):
                sm.ensure_writable(d, d.seen_tokens + 1)
                sm.extend(d)
                d.tokens.append(int(rng.integers(0, 3)))
                d.seen_tokens += 1
                sm.mark_filled(d)
        elif op == 2 and live and len(live) < 6:     # fork
            if sm.allocator.free_blocks + sm.retained_blocks > 10:
                sm.fork(int(rng.choice(live)), next_uid)
                live.append(next_uid)
                next_uid += 1
        elif op == 3 and live:                       # speculative rollback
            d = sm.seqs[rng.choice(live)]
            if d.seen_tokens > 1:
                new_len = int(rng.integers(1, d.seen_tokens))
                sm.truncate(d, new_len)
        elif op == 4 and live:                       # finish
            sm.retire(live.pop(rng.integers(0, len(live))))
        sm.debug_check()
    for uid in live:
        sm.retire(uid)
    sm.debug_check()
    assert sm.allocator.free_blocks + sm.retained_blocks == 23


# --------------------------------------------------------------------------- #
# engine-level randomized soak: spec and non-spec traffic mixed
# --------------------------------------------------------------------------- #
def test_spec_soak_mixed_requests():
    """Random admits/finishes on a spec-enabled engine with a mix of
    draftable (repetitive) and non-draftable (random) prompts and greedy +
    stochastic sampling params; allocator invariants hold after every step
    and every sequence's emitted stream is internally consistent."""
    V = 16
    rng = np.random.default_rng(4)
    eng = build_stub(vocab=V, break_every=7, k=3, slots=4, blocks=48,
                     block_size=4)
    sps = [SamplingParams(greedy=True),
           SamplingParams(temperature=0.8, top_k=6),
           SamplingParams(temperature=1.2, top_p=0.9)]
    next_uid = 0
    for it in range(60):
        if len(eng.state.seqs) < 4 and rng.random() < 0.5:
            n = int(rng.integers(4, 14))
            if rng.random() < 0.5:                   # draftable prompt
                pat = rng.integers(0, V, (3,)).tolist()
                prompt = (pat * 6)[:n]
            else:                                    # nothing to look up
                prompt = rng.integers(0, V, (n,)).tolist()
            if eng.state.can_admit(len(prompt)):
                eng.put(next_uid, prompt, sps[next_uid % 3], seed=it)
                next_uid += 1
        eng.step(seed=it)
        eng.state.debug_check()
        for uid in list(eng.state.seqs):
            if len(eng.state.seqs[uid].generated) >= 10 or rng.random() < .1:
                eng.finish(uid)
        eng.state.debug_check()
    s = eng.spec_stats
    assert s["verify_steps"] > 0 and s["drafted_tokens"] > 0
    assert s["emitted_tokens"] >= s["accepted_tokens"]


# --------------------------------------------------------------------------- #
# telemetry surface
# --------------------------------------------------------------------------- #
def test_spec_events_schema_registered():
    from deepspeed_tpu.telemetry import SERVING_SERIES, validate_events

    eng = build_stub(vocab=4, k=2, slots=1, blocks=16, block_size=8)
    eng.put(1, [0, 1, 2, 3, 0, 1], SP)
    eng.step(SP)
    events = eng.spec_events(step=2)
    assert events and validate_events(events) == []
    assert all(n in SERVING_SERIES for n, _, _ in events)
    # unregistered serving series are a schema violation, not silent loss
    assert validate_events([("Serving/spec/bogus_counter", 1.0, 1)])
    assert validate_events([("Serving/prefix_cache/nope", 1.0, 1)])
    eng.finish(1)


def test_spec_hub_publish_and_report(tmp_path):
    from deepspeed_tpu.monitor.monitor import JSONLMonitor
    from deepspeed_tpu.telemetry import TelemetryHub

    class MonCfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "spec"

    class HubCfg:
        pass

    mon = JSONLMonitor(MonCfg())
    hub = TelemetryHub(HubCfg(), monitor=mon)
    mod, cfg, params = _pattern_module(4)   # cycle matches the prompt tiling
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        mod, cfg, params, telemetry_hub=hub,
        config={"dtype": "float32", "prefill_bucket": 8,
                "speculative": {"enabled": True, "max_draft_tokens": 3},
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 16, "block_size": 8}})
    eng.generate([[0, 1, 2, 3, 0, 1, 2, 3]], max_new_tokens=12)
    assert hub.serving_values["Serving/spec/accept_rate"] == 1.0
    assert hub.serving_values["Serving/spec/tokens_per_step"] == 4.0
    mon.close()
    path = tmp_path / "spec" / "events.jsonl"
    assert path.exists()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "scripts", "telemetry_report.py")
    out = subprocess.run([sys.executable, script, str(path), "--serving"],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "accept rate:            100.0%" in out.stdout
    assert "tokens per model step:  4.00" in out.stdout
    assert "speculative decoding report" in out.stdout
