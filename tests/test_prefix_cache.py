"""Prefix-aware KV-cache reuse tests (docs/serving.md): ref-counted
BlockedAllocator hardening, chain-hash prefix index + retained LRU,
shared-block decode parity, copy-on-write, eviction under pressure, and the
Serving/prefix_cache/* telemetry surface."""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference import (InferenceConfig, PrefixBlockIndex,
                                     SamplingParams, build_engine_v2)
from deepspeed_tpu.inference.ragged import BlockedAllocator, StateManager
from deepspeed_tpu.models import llama

SP = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(max_seq_len=256)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def build(tiny, prefix_on=True, blocks=64, block_size=16, slots=4, **kw):
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    return build_engine_v2(
        llama, cfg, params,
        config=dict({"dtype": "float32", "prefill_bucket": 16,
                     "prefix_cache": {"enabled": prefix_on},
                     "ragged": {"max_tracked_sequences": slots,
                                "max_ragged_batch_size": slots,
                                "memory_config_blocks": blocks,
                                "block_size": block_size}}, **kw))


# --------------------------------------------------------------------------- #
# allocator hardening + refcounts
# --------------------------------------------------------------------------- #
def test_allocator_free_hardening():
    """Satellite: double free / free-of-unallocated used to append duplicate
    ids onto the free list silently — now both raise with the block id."""
    alloc = BlockedAllocator(8)
    a = alloc.allocate(3)
    alloc.free(a)
    with pytest.raises(ValueError, match=str(a[0])):
        alloc.free([a[0]])                      # double free
    b = [x for x in range(1, 8) if x not in a][0]
    with pytest.raises(ValueError, match=str(b)):
        alloc.free([b])                         # never allocated
    with pytest.raises(ValueError):
        alloc.free([0])                         # trash block
    with pytest.raises(ValueError):
        alloc.free([99])                        # outside the pool
    assert alloc.free_blocks == 7               # free list uncorrupted


def test_allocator_refcounts():
    alloc = BlockedAllocator(8)
    (b,) = alloc.allocate(1)
    assert alloc.refcount(b) == 1
    assert alloc.incref(b) == 2
    alloc.free([b])                             # drops to 1 — still live
    assert alloc.refcount(b) == 1 and alloc.free_blocks == 6
    assert alloc.release(b) == 0                # retained, NOT freed
    assert alloc.free_blocks == 6
    assert alloc.incref(b) == 1                 # reactivate retained block
    assert alloc.release(b) == 0
    alloc.reclaim(b)                            # eviction endpoint
    assert alloc.free_blocks == 7
    with pytest.raises(ValueError):
        alloc.incref(b)                         # free blocks can't be shared
    with pytest.raises(ValueError):
        alloc.reclaim(b)                        # already free


def test_prefix_index_chain_hash_and_lru():
    idx = PrefixBlockIndex()
    h = PrefixBlockIndex.chain_hashes(list(range(12)), 4, 3)
    assert len(h) == len(set(h)) == 3
    # chain property: same chunk at a different position → different key
    h2 = PrefixBlockIndex.chain_hashes([9, 9, 9, 9] + list(range(8)), 4, 3)
    assert h[0] != h2[0] and h[1] != h2[1]
    assert idx.insert(5, h[0]) and idx.insert(6, h[1])
    assert not idx.insert(7, h[0])              # first canonical block wins
    assert idx.match(h) == [5, 6]               # longest indexed prefix
    assert idx.match(h2) == []
    idx.lru_add(5)
    idx.lru_add(6)
    idx.lru_add(5)                              # touch → 6 is now oldest
    assert idx.pop_lru() == 6
    assert idx.match(h) == [5]                  # evicted block unmatchable


# --------------------------------------------------------------------------- #
# state-manager protocol (host-only)
# --------------------------------------------------------------------------- #
def test_admit_prompt_hit_never_covers_full_prompt():
    sm = StateManager(4, 32, 4, 16, prefix_cache=True)
    prompt = list(range(16))                    # 4 exactly-full blocks
    d1, hit1 = sm.admit_prompt(1, prompt)
    assert hit1 == 0
    d1.seen_tokens = 16
    sm.mark_filled(d1)
    sm.retire(1)
    assert sm.retained_blocks == 4
    d2, hit2 = sm.admit_prompt(2, prompt)
    # one token must stay uncached to produce first-token logits: only
    # (16-1)//4 = 3 of the 4 full blocks may be reused
    assert hit2 == 12
    assert d2.blocks[:3] == d1.blocks[:3] and d2.blocks[3] != d1.blocks[3]
    sm.debug_check()


def test_eviction_under_admission_pressure():
    sm = StateManager(4, 8, 4, 8, prefix_cache=True)   # 7 usable blocks
    d1, _ = sm.admit_prompt(1, list(range(12)))        # 4 blocks
    d1.seen_tokens = 12
    sm.mark_filled(d1)
    sm.retire(1)
    assert sm.retained_blocks == 3 and sm.allocator.free_blocks == 4
    # 20-token prompt needs 6 blocks: free(4) is short, but can_admit counts
    # the retained pool and admit_prompt evicts before failing
    assert sm.can_admit(20)
    d2, hit = sm.admit_prompt(2, list(range(100, 120)))
    assert hit == 0 and len(d2.blocks) == 6
    assert sm.prefix_stats["evictions"] >= 2
    sm.debug_check()


def test_retained_pool_cap():
    sm = StateManager(4, 32, 4, 16, prefix_cache=True, max_retained_blocks=2)
    d, _ = sm.admit_prompt(1, list(range(20)))
    d.seen_tokens = 20
    sm.mark_filled(d)
    sm.retire(1)
    assert sm.retained_blocks == 2              # 5 full blocks, cap keeps 2
    sm.debug_check()


def test_state_fork_and_cow_accounting():
    sm = StateManager(4, 32, 4, 16, prefix_cache=True)
    d, _ = sm.admit_prompt(1, list(range(10)))
    d.seen_tokens = 10
    sm.mark_filled(d)
    c = sm.fork(1, 2)
    assert c.blocks == d.blocks
    assert all(sm.allocator.refcount(b) == 2 for b in d.blocks)
    pairs = sm.ensure_writable(c, 11)           # append into shared block 2
    assert len(pairs) == 1 and pairs[0][0] == d.blocks[2]
    assert c.blocks[2] == pairs[0][1] != d.blocks[2]
    assert sm.allocator.refcount(d.blocks[2]) == 1
    assert sm.ensure_writable(d, 11) == []      # now exclusively owned
    sm.retire(2)
    sm.retire(1)
    sm.debug_check()


def test_refcount_invariants_randomized_soak():
    """Satellite: randomized admit/decode/finish (+fork) soak — the
    free/live/retained accounting must hold after every operation."""
    rng = np.random.default_rng(0)
    sm = StateManager(6, 24, 4, 10, prefix_cache=True)
    live = []
    next_uid = 0
    for it in range(300):
        op = rng.integers(0, 4)
        if op == 0 and len(live) < 6:           # admit
            n = int(rng.integers(1, 20))
            if sm.can_admit(n):
                prompt = [int(t) for t in rng.integers(0, 3, n)]
                d, hit = sm.admit_prompt(next_uid, prompt)
                d.seen_tokens = len(prompt)
                sm.mark_filled(d)
                live.append(next_uid)
                next_uid += 1
        elif op == 1 and live:                  # decode one token
            d = sm.seqs[rng.choice(live)]
            if (d.seen_tokens + 1 + sm.block_size - 1) // sm.block_size \
                    + 1 <= sm.max_blocks_per_seq and sm.can_admit(1):
                sm.ensure_writable(d, d.seen_tokens + 1)
                sm.extend(d)
                d.tokens.append(int(rng.integers(0, 3)))
                d.seen_tokens += 1
                sm.mark_filled(d)
        elif op == 2 and live and len(live) < 6:  # fork
            if sm.allocator.free_blocks + sm.retained_blocks > 10:
                parent = int(rng.choice(live))
                sm.fork(parent, next_uid)
                live.append(next_uid)
                next_uid += 1
        elif op == 3 and live:                  # finish
            uid = live.pop(rng.integers(0, len(live)))
            sm.retire(uid)
        sm.debug_check()
    for uid in live:
        sm.retire(uid)
    sm.debug_check()
    assert sm.allocator.free_blocks + sm.retained_blocks == 23


# --------------------------------------------------------------------------- #
# engine-level parity
# --------------------------------------------------------------------------- #
def test_cache_off_is_default_and_matches_enabled_tokens(tiny):
    """prefix_cache defaults OFF (parity pin: the cache-less path runs the
    exact pre-cache programs), and greedy tokens are identical with it ON."""
    assert InferenceConfig().prefix_cache.enabled is False
    assert InferenceConfig.from_dict({}).prefix_cache.enabled is False
    rng = np.random.default_rng(1)
    cfg, _ = tiny
    prompts = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32)
               for n in (40, 23, 40)]
    default = build(tiny, prefix_on=False)
    assert default.state.prefix_cache is False
    want = default.generate(prompts, max_new_tokens=5)
    got = build(tiny, prefix_on=True).generate(prompts, max_new_tokens=5)
    assert got == want


def _drive_shared(tiny, enabled, pa, pb, steps=4, quantum=0):
    """Admit pa, decode a bit, admit pb (prefix-hits when enabled), decode
    both; return (tokens_a, tokens_b, stats)."""
    eng = build(tiny, prefix_on=enabled)
    eng.put(1, pa.tolist(), SP)
    if quantum:
        eng.step_many(quantum, SP)
    else:
        for _ in range(2):
            eng.step(SP)
    eng.put(2, pb.tolist(), SP)
    if quantum:
        eng.step_many(quantum, SP)
    else:
        for _ in range(steps):
            eng.step(SP)
    a, b = eng.finish(1), eng.finish(2)
    stats = dict(eng.state.prefix_stats)
    eng.state.debug_check()
    return a, b, stats


def test_shared_block_decode_parity_step(tiny):
    cfg, _ = tiny
    rng = np.random.default_rng(2)
    shared = rng.integers(0, cfg.vocab_size, (48,), dtype=np.int32)
    pa = np.concatenate([shared, rng.integers(0, cfg.vocab_size, (5,),
                                              dtype=np.int32)])
    pb = np.concatenate([shared, rng.integers(0, cfg.vocab_size, (9,),
                                              dtype=np.int32)])
    a0, b0, s0 = _drive_shared(tiny, False, pa, pb)
    a1, b1, s1 = _drive_shared(tiny, True, pa, pb)
    assert s0["hit_tokens"] == 0
    assert s1["hit_tokens"] == 48               # 3 full blocks of 16
    assert (a1, b1) == (a0, b0)


def test_shared_block_decode_parity_step_many(tiny):
    cfg, _ = tiny
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab_size, (32,), dtype=np.int32)
    pa = np.concatenate([shared, rng.integers(0, cfg.vocab_size, (7,),
                                              dtype=np.int32)])
    pb = np.concatenate([shared, rng.integers(0, cfg.vocab_size, (3,),
                                              dtype=np.int32)])
    a0, b0, s0 = _drive_shared(tiny, False, pa, pb, quantum=4)
    a1, b1, s1 = _drive_shared(tiny, True, pa, pb, quantum=4)
    assert s1["hit_tokens"] == 32 and s0["hit_tokens"] == 0
    assert (a1, b1) == (a0, b0)


def test_retained_reuse_after_retire_and_multiturn(tiny):
    """Retire → re-admit an extended prompt (multi-turn shape): the second
    turn reuses blocks from the first INCLUDING decode-generated blocks."""
    cfg, _ = tiny
    rng = np.random.default_rng(4)
    p = rng.integers(0, cfg.vocab_size, (40,), dtype=np.int32)
    ref = build(tiny, prefix_on=False)
    eng = build(tiny, prefix_on=True)
    want1 = ref.generate([p], max_new_tokens=10)[0]
    got1 = eng.generate([p], max_new_tokens=10)[0]
    assert got1 == want1
    assert eng.state.retained_blocks > 0
    # turn 2: history = prompt + model reply + a new user message
    p2 = np.concatenate([p, np.asarray(want1, np.int32),
                         rng.integers(0, cfg.vocab_size, (6,), np.int32)])
    want2 = ref.generate([p2], max_new_tokens=5)[0]
    got2 = eng.generate([p2], max_new_tokens=5)[0]
    assert got2 == want2
    # turn 1's KV (40 prompt + 10 generated = 48 tokens → 3 full blocks)
    # was resolved from the retained pool, not re-prefilled
    assert eng.state.prefix_stats["hit_tokens"] >= 48
    eng.state.debug_check()


def test_cow_partial_shared_block_mid_decode(tiny):
    """Fork shares a partially-filled tail block; when the forks diverge,
    copy-on-write must give the writer a private copy — BOTH continuations
    must match their single-sequence oracles (a missed copy corrupts the
    sibling's KV; a mis-copied block corrupts the writer's)."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, cfg.vocab_size, (20,), dtype=np.int32)
    eng = build(tiny, prefix_on=True)
    f0 = eng.put(1, prompt.tolist(), SP)
    f1 = eng.step(SP)[1]                        # seen=21: pos 21 is mid-block
    parent = eng.state.seqs[1]
    child = eng.fork(1, 2)
    tail = parent.blocks[1]                     # block 1 holds pos 16..31
    assert eng.state.allocator.refcount(tail) == 2
    # diverge the fork: inject a different pending token for the child
    inj = int((f1 + 1) % cfg.vocab_size)
    child.last_token = inj
    eng._slot_tokens[child.slot] = inj
    out = eng.step(SP)
    assert eng.state.prefix_stats["cow_copies"] == 1
    assert parent.blocks[1] != child.blocks[1]  # private copies
    eng.state.debug_check()
    nxt = eng.step(SP)
    assert eng.prefix_cache_events()[0][0].startswith("Serving/prefix_cache/")
    # oracles replay each fork's exact put/step trajectory in a fresh
    # unshared engine (decode-written KV, same programs — so tokens must be
    # IDENTICAL, not merely close; a missed/miscopied block flips them)
    op = build(tiny, prefix_on=False)
    assert op.put(11, prompt.tolist(), SP) == f0
    assert op.step(SP)[11] == f1
    assert op.step(SP)[11] == out[1]
    assert op.step(SP)[11] == nxt[1]
    oc = build(tiny, prefix_on=False)
    assert oc.put(12, prompt.tolist(), SP) == f0
    oc.step(SP)                                 # writes f0's KV, samples f1
    oc.state.seqs[12].last_token = inj          # replay the injection
    oc._slot_tokens[oc.state.seqs[12].slot] = inj
    oc._slot_src[oc.state.seqs[12].slot] = 0    # the host's copy stands
    assert oc.step(SP)[12] == out[2]
    assert oc.step(SP)[12] == nxt[2]


def test_split_prefill_starts_at_first_uncached_token(tiny):
    """Chunked (SplitFuse) admissions consult the cache too: a warm prefix
    skips its chunks entirely and the first token still matches."""
    cfg, _ = tiny
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, cfg.vocab_size, (64,), dtype=np.int32)
    eng = build(tiny, prefix_on=True, split_prefill_chunk=16)
    first_ref = eng.put(1, prompt.tolist(), SP)   # warms 3 full blocks (48)
    eng.finish(1)
    eng.put_split(2, prompt.tolist(), SP)
    assert eng.state.seqs[2].seen_tokens == 48    # chunks start at token 48
    out = eng.step(SP)                            # ONE chunk finishes prefill
    assert out[2] == first_ref
    eng.finish(2)
    eng.state.debug_check()


def test_prefill_tokens_saved_over_90pct_of_shared(tiny):
    """Acceptance: on a shared-system-prompt workload, prefill_tokens_saved
    >= 90% of the reusable shared-prefix tokens after warmup (here: every
    admission after the first hits the full shared prefix)."""
    cfg, _ = tiny
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab_size, (64,), dtype=np.int32).tolist()
    eng = build(tiny, prefix_on=True, blocks=96)
    n_admits = 6
    for uid in range(n_admits):
        tail = rng.integers(0, cfg.vocab_size, (8,), dtype=np.int32).tolist()
        eng.put(uid, shared + tail, SP)
        eng.step(SP)
        eng.finish(uid)
    stats = eng.state.prefix_stats
    reusable = 64 * (n_admits - 1)              # shared_len is block-aligned
    assert stats["prefill_tokens_saved"] >= 0.9 * reusable
    assert stats["hits"] == n_admits - 1
    eng.state.debug_check()


# --------------------------------------------------------------------------- #
# telemetry surface
# --------------------------------------------------------------------------- #
def test_hub_serving_event_and_engine_publish(tiny, tmp_path):
    from deepspeed_tpu.monitor.monitor import JSONLMonitor
    from deepspeed_tpu.telemetry import TelemetryHub

    class MonCfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "srv"

    class HubCfg:
        pass

    mon = JSONLMonitor(MonCfg())
    hub = TelemetryHub(HubCfg(), monitor=mon)
    cfg, params = tiny
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        llama, cfg, params, telemetry_hub=hub,
        config={"dtype": "float32", "prefill_bucket": 16,
                "prefix_cache": {"enabled": True},
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 32, "block_size": 16}})
    p = np.arange(40, dtype=np.int32) % cfg.vocab_size
    eng.put(1, p.tolist(), SP)
    eng.finish(1)
    eng.put(2, p.tolist(), SP)
    eng.finish(2)
    events = eng.publish_prefix_telemetry(step=3)
    assert hub.serving_values["Serving/prefix_cache/hit_tokens"] == 32.0
    assert ("Serving/prefix_cache/lookups", 2.0, 3) in events
    mon.close()
    assert (tmp_path / "srv" / "events.jsonl").exists()


def test_telemetry_report_serving(tmp_path):
    from deepspeed_tpu.monitor.monitor import JSONLMonitor

    class Cfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "job"

    mon = JSONLMonitor(Cfg())
    mon.write_events([("Serving/prefix_cache/lookups", 4.0, 1),
                      ("Serving/prefix_cache/hits", 1.0, 1),
                      ("Serving/prefix_cache/lookups", 10.0, 9),
                      ("Serving/prefix_cache/hits", 8.0, 9),
                      ("Serving/prefix_cache/hit_tokens", 512.0, 9),
                      ("Serving/prefix_cache/prefill_tokens_saved", 512.0, 9),
                      ("Serving/prefix_cache/evictions", 3.0, 9),
                      ("Serving/prefix_cache/retained_blocks", 7.0, 9),
                      ("Train/Samples/train_loss", 2.5, 9)])
    mon.close()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "scripts", "telemetry_report.py")
    out = subprocess.run(
        [sys.executable, script, str(tmp_path / "job" / "events.jsonl"),
         "--serving"], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "hit rate:               80.0%" in out.stdout
    assert "prefill tokens saved:   512" in out.stdout
    assert "retained blocks (now):  7" in out.stdout
    assert "evictions:              3" in out.stdout
