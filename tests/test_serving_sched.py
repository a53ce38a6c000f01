"""Continuous-batching scheduler, multi-replica router, and traffic
generator tests (docs/serving.md "Scheduler & router"): admission control
never over-commits KV blocks, preemption+resume is token-identical to an
uninterrupted run, the router places repeat sessions on the replica holding
their cached prefix, plus the park/resume engine seams, headroom
accounting, the consistent unknown-uid error, and the Serving/sched|router
telemetry surface."""

import functools
import math
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference import (ReplicaRouter, Request, RouterConfig,
                                     SamplingParams, SchedulerConfig,
                                     ServingScheduler, StateManager,
                                     TrafficGenerator, UnknownSequenceError,
                                     WorkloadConfig, build_engine_v2)
from deepspeed_tpu.inference.serving import DONE, REJECTED
from deepspeed_tpu.models import llama
from deepspeed_tpu.telemetry.schema import SERVING_SERIES, validate_events

SP = SamplingParams(greedy=True)


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny(max_seq_len=256)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def build(tiny, prefix_on=True, blocks=48, block_size=16, slots=4, **kw):
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
# traffic generator
# --------------------------------------------------------------------------- #
def test_workload_poisson_deterministic():
    mk = lambda: TrafficGenerator(WorkloadConfig(  # noqa: E731
        seed=5, rate_rps=20.0, prompt_len=(8, 24), gen_len=(4, 12),
        priorities=(0, 1, 2), deadline_ms=500.0))
    a1, a2 = mk().arrivals(3.0), mk().arrivals(3.0)
    assert len(a1) == len(a2) > 20           # ~60 expected at 20 rps × 3 s
    assert [(x.t, x.request.prompt, x.request.max_new_tokens,
             x.request.priority) for x in a1] == \
        [(x.t, x.request.prompt, x.request.max_new_tokens,
          x.request.priority) for x in a2]
    assert all(0 <= x.t < 3.0 for x in a1)
    assert all(x.t <= y.t for x, y in zip(a1, a1[1:]))
    assert all(8 <= len(x.request.prompt) <= 24 for x in a1)
    assert all(x.request.deadline_ms == 500.0 for x in a1)
    assert {x.request.priority for x in a1} <= {0, 1, 2}
    # distinct sessions, distinct prompts (vocab 256, length >= 8)
    assert len({x.session_id for x in a1}) == len(a1)


def test_workload_bursty_and_multiturn_followup():
    gen = TrafficGenerator(WorkloadConfig(
        seed=2, process="bursty", burst_size=3, burst_interval_s=1.0,
        turns=3, think_time_s=0.5, followup_len=4))
    arr = gen.arrivals(2.5)
    assert len(arr) == 9 and [a.t for a in arr] == [0.0] * 3 + [1.0] * 3 \
        + [2.0] * 3
    first = arr[0]
    f2 = gen.followup(first, [7, 8, 9], now_s=1.25)
    assert f2.turn == 2 and f2.session_id == first.session_id
    assert f2.t == 1.75
    # follow-up prompt = previous prompt + output + 4 fresh user tokens
    assert f2.request.prompt[:len(first.request.prompt)] == \
        first.request.prompt
    hist = len(first.request.prompt)
    assert f2.request.prompt[hist:hist + 3] == [7, 8, 9]
    assert len(f2.request.prompt) == hist + 3 + 4
    f3 = gen.followup(f2, [1], now_s=3.0)
    assert f3.turn == 3
    assert gen.followup(f3, [2], now_s=4.0) is None  # turns exhausted


def test_workload_prompt_kinds():
    g = TrafficGenerator(WorkloadConfig(seed=1, prompt_kind="shared_prefix",
                                        shared_len=12, prompt_len=(2, 6)))
    ps = [g.prompt_tokens() for _ in range(4)]
    assert all(p[:12] == g.shared_prefix for p in ps)
    assert all(14 <= len(p) <= 18 for p in ps)
    g = TrafficGenerator(WorkloadConfig(seed=1, prompt_kind="repetitive",
                                        pattern_len=3, prompt_len=9))
    p = g.prompt_tokens()
    assert len(p) == 9 and p[:3] == p[3:6] == p[6:9]
    with pytest.raises(ValueError, match="prompt_kind"):
        TrafficGenerator(WorkloadConfig(prompt_kind="nope"))


# --------------------------------------------------------------------------- #
# satellite: consistent unknown-uid error surface
# --------------------------------------------------------------------------- #
def test_finish_unknown_uid_consistent_error(tiny):
    """finish()/park()/fork() on an unknown or already-finished uid raise
    ONE message-bearing error type — not a bare KeyError from whichever
    internal dict happened to miss first."""
    eng = build(tiny)
    with pytest.raises(UnknownSequenceError, match="uid 42"):
        eng.finish(42)
    prompt = list(range(20))
    eng.put(1, prompt, SP)
    eng.finish(1)
    with pytest.raises(UnknownSequenceError, match="uid 1"):
        eng.finish(1)                         # already finished
    with pytest.raises(UnknownSequenceError, match="uid 7"):
        eng.park(7)
    with pytest.raises(UnknownSequenceError, match="uid 9"):
        eng.fork(9, 10)
    # subclasses KeyError, so pre-existing `except KeyError` callers work
    assert issubclass(UnknownSequenceError, KeyError)
    err = UnknownSequenceError(3)
    assert "uid 3" in str(err) and "not a tracked sequence" in str(err)


# --------------------------------------------------------------------------- #
# satellite: admission-pressure edge cases in ragged.py
# --------------------------------------------------------------------------- #
def test_can_admit_truthful_after_eviction():
    """can_admit must answer exactly what admit_prompt would do, including
    after prefix-cache eviction has reclaimed retained blocks under
    pressure: True ⇒ the admission succeeds, False ⇒ it raises."""
    sm = StateManager(4, 12, 4, 8, prefix_cache=True)   # 11 usable blocks
    d, _ = sm.admit_prompt(1, list(range(16)))          # 5 blocks
    d.seen_tokens = 16
    sm.mark_filled(d)
    sm.retire(1)                                        # 4 retained
    assert sm.retained_blocks == 4
    assert sm.headroom_blocks == 11
    base = 1000
    for n in range(1, 30):
        ok = sm.can_admit(n)
        try:
            sm.admit_prompt(base + n, [base + n + i for i in range(n)])
            succeeded = True
            sm.retire(base + n)
        except MemoryError:
            succeeded = False
        assert ok == succeeded, f"can_admit({n})={ok} but admit " \
            f"{'succeeded' if succeeded else 'failed'}"
        sm.debug_check()
    # now under LIVE pressure: admissions hold blocks, eviction drains the
    # retained pool, and can_admit keeps telling the truth as it empties
    live = []
    n = 9
    while sm.can_admit(n):
        uid = 2000 + len(live)
        sm.admit_prompt(uid, [uid + i for i in range(n)])
        live.append(uid)
        sm.debug_check()
    with pytest.raises(MemoryError):
        sm.admit_prompt(2999, list(range(3000, 3000 + n)))
    assert sm.can_admit(n) is False
    sm.debug_check()
    for uid in live:
        sm.retire(uid)
    sm.debug_check()


def test_headroom_and_growth_accounting():
    """headroom_blocks = free + retained; growth_blocks_short counts fresh
    tail blocks AND copy-on-write allocations for shared blocks."""
    sm = StateManager(4, 16, 4, 8, prefix_cache=True)   # 15 usable
    d, _ = sm.admit_prompt(1, list(range(10)))          # 4 blocks
    d.seen_tokens = 10
    sm.mark_filled(d)
    assert sm.headroom_blocks == 11
    assert sm.blocks_needed(10) == 4
    # 10 seen, 4 blocks = 16 token capacity: 1 more token needs 0 blocks,
    # 7 more need 1, 11 more need 2 — all within headroom
    assert sm.growth_blocks_short([d], n=1) == 0
    c = sm.fork(1, 2)
    # fork shares ALL blocks: the tail block (pos 8..11) is shared, so one
    # decode token needs a COW copy for whichever sequence writes first
    assert sm.growth_blocks_short([c], n=1) == 0     # headroom covers it
    # shrink headroom to zero by admitting fillers, then the COW need shows
    fillers = []
    while sm.allocator.free_blocks >= 4 and sm.free_slots:
        uid = 100 + len(fillers)
        sm.admit_prompt(uid, [uid * 50 + i for i in range(12)])
        fillers.append(uid)
    if sm.allocator.free_blocks == 0:
        assert sm.growth_blocks_short([c], n=1) >= 1
    sm.debug_check()


# --------------------------------------------------------------------------- #
# engine seams: park / resume / kv_headroom
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("prefix_on", [False, True])
def test_engine_park_resume_token_parity(tiny, prefix_on):
    """Acceptance: a greedy park/resume cycle produces a token stream
    IDENTICAL to an uninterrupted run — with the prefix cache on (retained
    blocks resolve the history) and off (full re-prefill)."""
    cfg, _ = tiny
    rng = np.random.default_rng(21)
    prompt = rng.integers(0, cfg.vocab_size, (40,)).tolist()
    other = rng.integers(0, cfg.vocab_size, (20,)).tolist()
    ref = build(tiny, prefix_on=prefix_on)
    ref.put(1, prompt, SP)
    for _ in range(6):
        ref.step(SP)
    want = ref.finish(1)
    eng = build(tiny, prefix_on=prefix_on)
    eng.put(1, prompt, SP)
    for _ in range(3):
        eng.step(SP)
    hr0 = eng.kv_headroom()
    parked = eng.park(1)
    eng.state.debug_check()
    assert eng.kv_headroom()["headroom_blocks"] > hr0["headroom_blocks"]
    assert parked["generated"] == want[:4]
    assert parked["history"] == prompt + want[:4]
    # pool churns while the victim is parked
    eng.put(2, other, SP)
    eng.step(SP)
    eng.finish(2)
    got_tok = eng.resume(parked)
    assert got_tok == [want[4]]
    for _ in range(2):
        eng.step(SP)
    assert eng.finish(1) == want
    eng.state.debug_check()


def test_park_resume_debug_check_invariants(tiny):
    """Satellite: park/resume cycles — including a mid-split-prefill park
    and a split resume — leave the allocator/index invariants clean after
    every operation."""
    cfg, _ = tiny
    rng = np.random.default_rng(22)
    prompt = rng.integers(0, cfg.vocab_size, (64,)).tolist()
    oracle = build(tiny, prefix_on=False)
    first_ref = oracle.put(9, prompt, SP)       # oracle for the first token
    eng = build(tiny, prefix_on=True, split_prefill_chunk=16)
    # a live decode keeps split prefill to one chunk per step (without one,
    # step() deliberately drains the whole prompt)
    eng.put(5, rng.integers(0, cfg.vocab_size, (10,)).tolist(), SP)
    eng.put_split(1, prompt, SP)
    eng.step(SP)                                # advances ONE of 4 chunks
    assert eng.state.seqs[1].prefilling
    assert 0 < eng.state.seqs[1].seen_tokens < len(prompt)
    parked = eng.park(1)                        # mid-prefill park
    eng.state.debug_check()
    assert parked["generated"] == [] and parked["history"] == prompt
    assert eng.resume(parked, split=True) == []     # chunked resume
    eng.state.debug_check()
    out = {}
    while 1 not in out:
        out = eng.step(SP)
        eng.state.debug_check()
    assert out[1] == first_ref                  # stream unchanged by cycle
    eng.finish(5)
    # park again mid-decode, resume one-shot, finish
    for _ in range(2):
        eng.step(SP)
        eng.state.debug_check()
    parked = eng.park(1)
    eng.state.debug_check()
    eng.resume(parked)
    eng.state.debug_check()
    toks = eng.finish(1)
    assert toks[0] == first_ref and len(toks) == 4
    eng.state.debug_check()


# --------------------------------------------------------------------------- #
# scheduler
# --------------------------------------------------------------------------- #
def _mk_requests(cfg, n, gen_len, seed=9, prompt_len=(8, 24), prios=(0,)):
    gen = TrafficGenerator(WorkloadConfig(
        seed=seed, vocab_size=cfg.vocab_size, prompt_len=prompt_len,
        gen_len=gen_len, priorities=prios, deadline_ms=60000.0))
    return [gen.request() for _ in range(n)]


def test_scheduler_never_overcommits_under_pressure(tiny):
    """Acceptance: on a seeded synthetic workload over a pool far too small
    for the offered load, admission control + the preemption guard keep
    every allocation inside headroom — no allocation failure ever surfaces
    to a request, every stream completes at full length, and the allocator
    invariants hold."""
    cfg, _ = tiny
    eng = build(tiny, blocks=14)                # 13 usable blocks, 4 slots
    sched = ServingScheduler(eng, SchedulerConfig())
    reqs = _mk_requests(cfg, 8, gen_len=40)
    handles = [sched.submit(r) for r in reqs]
    sched.run()                                 # raises if anything failed
    assert all(h.state == DONE for h in handles)
    assert all(len(h.tokens) == h.request.max_new_tokens for h in handles)
    assert sched.stats["completed"] == 8
    assert sched.stats["preempted"] >= 1        # pressure actually preempted
    assert sched.stats["resumed"] == sched.stats["preempted"]
    eng.state.debug_check()
    assert not eng.state.seqs                   # everything retired


@pytest.mark.parametrize("prefix_on", [False, True])
def test_scheduler_preempt_resume_stream_parity(tiny, prefix_on):
    """Acceptance: the preempting scheduler (tight pool) emits per-request
    token streams IDENTICAL to a no-pressure run of the same requests."""
    cfg, _ = tiny

    def run(blocks, prefix):
        eng = build(tiny, blocks=blocks, prefix_on=prefix)
        sched = ServingScheduler(eng, SchedulerConfig())
        handles = [sched.submit(r) for r in _mk_requests(cfg, 7, gen_len=40)]
        sched.run()
        eng.state.debug_check()
        return [h.tokens for h in handles], sched.stats

    want, s0 = run(blocks=96, prefix=False)     # ample pool: no preemption
    assert s0["preempted"] == 0
    got, s1 = run(blocks=14, prefix=prefix_on)
    assert s1["preempted"] >= 1
    assert got == want


def test_scheduler_priority_and_deadline_order(tiny):
    """With one sequence slot, a higher-priority (then earlier-deadline)
    request leaves the queue first even when submitted later."""
    cfg, _ = tiny
    rng = np.random.default_rng(3)
    mk = lambda **kw: Request(prompt=rng.integers(  # noqa: E731
        0, cfg.vocab_size, (12,)).tolist(),
        **{"max_new_tokens": 4, **kw})
    eng = build(tiny, slots=1)
    sched = ServingScheduler(eng, SchedulerConfig())
    running = sched.submit(mk(max_new_tokens=8))
    low = sched.submit(mk(priority=5))
    high = sched.submit(mk(priority=0))
    sched.run()
    assert all(h.state == DONE for h in (running, low, high))
    assert high.queue_wait_ms < low.queue_wait_ms
    # same priority → earlier absolute deadline wins
    eng = build(tiny, slots=1)
    sched = ServingScheduler(eng, SchedulerConfig())
    running = sched.submit(mk(max_new_tokens=8))
    late = sched.submit(mk(deadline_ms=60000.0))
    soon = sched.submit(mk(deadline_ms=1000.0))
    sched.run()
    assert soon.queue_wait_ms < late.queue_wait_ms


def test_scheduler_streaming_and_rejects(tiny):
    """drain()/on_token stream tokens in order; impossible requests are
    rejected at submit with a message instead of wedging the queue."""
    cfg, _ = tiny
    rng = np.random.default_rng(4)
    eng = build(tiny)
    sched = ServingScheduler(eng, SchedulerConfig())
    seen = []
    h = sched.submit(Request(prompt=rng.integers(
        0, cfg.vocab_size, (10,)).tolist(), max_new_tokens=6),
        on_token=seen.append)
    drained = []
    while not h.done:
        sched.tick()
        drained += h.drain()
    assert seen == drained == h.tokens and len(h.tokens) == 6
    # rejections: empty prompt / prompt past max_seq_len / footprint > pool
    r1 = sched.submit(Request(prompt=[]))
    assert r1.state == REJECTED and "empty" in r1.error
    r2 = sched.submit(Request(prompt=list(range(cfg.max_seq_len))))
    assert r2.state == REJECTED and "max_seq_len" in r2.error
    assert sched.stats["rejected"] == 2
    assert not sched.pending
    # on a tiny pool: a prompt too big to ever admit, and one that fits but
    # whose worst-case completion footprint can never (park/resume thrash)
    small = ServingScheduler(build(tiny, blocks=8), SchedulerConfig())
    r3 = small.submit(Request(prompt=list(range(100))))
    assert r3.state == REJECTED and "pool holds 7" in r3.error
    r4 = small.submit(Request(prompt=list(range(30)), max_new_tokens=200))
    assert r4.state == REJECTED and "never fit" in r4.error
    assert small.stats["rejected"] == 2


def test_scheduler_drop_expired_and_chunked_admission(tiny):
    cfg, _ = tiny
    rng = np.random.default_rng(5)
    # one slot is busy; a zero-deadline request expires in the queue
    eng = build(tiny, slots=1)
    sched = ServingScheduler(eng, SchedulerConfig(drop_expired=True))
    busy = sched.submit(Request(prompt=rng.integers(
        0, cfg.vocab_size, (10,)).tolist(), max_new_tokens=8))
    doomed = sched.submit(Request(prompt=rng.integers(
        0, cfg.vocab_size, (10,)).tolist(), deadline_ms=0.0))
    sched.run()
    assert busy.state == DONE and doomed.state == REJECTED
    assert "expired" in doomed.error and doomed.slo_met is False
    assert sched.stats["expired"] == 1
    # long prompts take the SplitFuse chunked path under the scheduler
    eng = build(tiny, split_prefill_chunk=16, blocks=64)
    sched = ServingScheduler(eng, SchedulerConfig())
    short = sched.submit(Request(prompt=rng.integers(
        0, cfg.vocab_size, (12,)).tolist(), max_new_tokens=4))
    long = sched.submit(Request(prompt=rng.integers(
        0, cfg.vocab_size, (60,)).tolist(), max_new_tokens=4))
    sched.run()
    assert sched.stats["chunked_admissions"] == 1
    assert short.state == DONE and long.state == DONE
    assert len(long.tokens) == 4
    eng.state.debug_check()


# --------------------------------------------------------------------------- #
# multi-replica router
# --------------------------------------------------------------------------- #
def test_router_prefix_affinity_places_repeat_session(tiny):
    """Acceptance: a repeat session lands on the replica holding its cached
    prefix blocks (chain-hash probe), not wherever load-balance would put
    it; unrelated traffic spreads by load."""
    cfg, _ = tiny
    rng = np.random.default_rng(6)
    scheds = [ServingScheduler(build(tiny)) for _ in range(2)]
    router = ReplicaRouter(scheds)
    p = rng.integers(0, cfg.vocab_size, (40,)).tolist()
    h1 = router.submit(Request(prompt=p, max_new_tokens=6, session_id=70))
    router.run()
    first = h1.replica
    # the session's turn-2 history extends turn 1 → only `first` can match
    p2 = p + h1.tokens + rng.integers(0, cfg.vocab_size, (5,)).tolist()
    assert router.affinity_tokens(first, p2) >= 32
    assert router.affinity_tokens(1 - first, p2) == 0
    h2 = router.submit(Request(prompt=p2, max_new_tokens=4, session_id=70))
    assert h2.replica == first
    assert router.stats["affinity_hits"] == 1
    router.run()
    # unrelated sessions spread across replicas by load
    for i in range(4):
        router.submit(Request(prompt=rng.integers(
            0, cfg.vocab_size, (24,)).tolist(), max_new_tokens=4,
            session_id=100 + i))
    assert all(s.queue_depth + s.live_count > 0 for s in scheds)
    router.run()
    assert router.stats["requests"] == 6


def test_router_affinity_yields_to_overload(tiny):
    """An affinity winner overloaded past load_slack loses to the least-
    loaded replica (load-based fallback)."""
    cfg, _ = tiny
    rng = np.random.default_rng(7)
    scheds = [ServingScheduler(build(tiny)) for _ in range(2)]
    router = ReplicaRouter(scheds, RouterConfig(load_slack=2))
    p = rng.integers(0, cfg.vocab_size, (40,)).tolist()
    h1 = router.submit(Request(prompt=p, max_new_tokens=4, session_id=1))
    router.run()
    first = h1.replica
    # pile queued work onto the affinity replica without ticking it
    for _ in range(4):
        scheds[first].submit(Request(prompt=rng.integers(
            0, cfg.vocab_size, (10,)).tolist(), max_new_tokens=2))
    h2 = router.submit(Request(prompt=list(p), max_new_tokens=2,
                               session_id=1))
    assert h2.replica == 1 - first
    assert router.stats["load_fallbacks"] == 1
    router.run()


def test_router_drain_rehomes_live_and_queued(tiny):
    """Replica loss: drain() parks the replica's live sequences and moves
    every request (same handle objects) to the survivors, where the streams
    complete."""
    cfg, _ = tiny
    rng = np.random.default_rng(8)
    scheds = [ServingScheduler(build(tiny)) for _ in range(2)]
    router = ReplicaRouter(scheds, RouterConfig(load_slack=100))
    handles = [router.submit(Request(prompt=rng.integers(
        0, cfg.vocab_size, (20,)).tolist(), max_new_tokens=6))
        for _ in range(6)]
    for _ in range(2):
        router.step()
    moved = router.drain(0)
    assert moved >= 1 and router.stats["drains"] == 1
    assert not scheds[0].engine.state.seqs      # replica 0 fully vacated
    router.run()
    assert all(h.state == DONE and len(h.tokens) == 6 for h in handles)
    assert all(h.replica == 1 for h in handles if h.preemptions)
    scheds[1].engine.state.debug_check()
    with pytest.raises(ValueError, match="last active replica"):
        router.drain(1)
    with pytest.raises(ValueError, match="already drained"):
        router.drain(0)


# --------------------------------------------------------------------------- #
# telemetry surface
# --------------------------------------------------------------------------- #
def test_sched_router_events_schema_and_hub(tiny, tmp_path):
    from deepspeed_tpu.monitor.monitor import JSONLMonitor
    from deepspeed_tpu.telemetry import TelemetryHub

    class MonCfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "sched"

    class HubCfg:
        pass

    cfg, params = tiny
    mon = JSONLMonitor(MonCfg())
    hub = TelemetryHub(HubCfg(), monitor=mon)
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        llama, cfg, params, telemetry_hub=hub,
        config={"dtype": "float32", "prefill_bucket": 16,
                "prefix_cache": {"enabled": True},
                "ragged": {"max_tracked_sequences": 2,
                           "max_ragged_batch_size": 2,
                           "memory_config_blocks": 32, "block_size": 16}})
    sched = ServingScheduler(eng, SchedulerConfig())
    router = ReplicaRouter([sched])
    rng = np.random.default_rng(9)
    router.submit(Request(prompt=rng.integers(
        0, cfg.vocab_size, (12,)).tolist(), max_new_tokens=3,
        deadline_ms=30000.0))
    router.run()
    sevents = sched.publish_sched_telemetry(step=2)
    revents = router.publish_router_telemetry(step=2)
    assert validate_events(sevents + revents) == []
    names = {n for n, _, _ in sevents + revents}
    assert names <= SERVING_SERIES
    assert hub.serving_values["Serving/sched/completed"] == 1.0
    assert hub.serving_values["Serving/sched/slo_met"] == 1.0
    assert hub.serving_values["Serving/router/requests"] == 1.0
    assert hub.serving_values["Serving/sched/goodput_frac"] == 1.0
    assert math.isfinite(hub.serving_values["Serving/sched/goodput_rps"])
    # the closed registry rejects an unregistered scheduler series
    assert validate_events([("Serving/sched/bogus", 1.0, 0)])
    mon.close()
    assert (tmp_path / "sched" / "events.jsonl").exists()


def test_telemetry_report_serving_sched_and_router(tmp_path):
    from deepspeed_tpu.monitor.monitor import JSONLMonitor

    class Cfg:
        enabled = True
        output_path = str(tmp_path)
        job_name = "job"

    mon = JSONLMonitor(Cfg())
    mon.write_events([
        ("Serving/sched/submitted", 20.0, 5),
        ("Serving/sched/admitted", 18.0, 5),
        ("Serving/sched/preempted", 3.0, 5),
        ("Serving/sched/resumed", 3.0, 5),
        ("Serving/sched/rejected", 1.0, 5),
        ("Serving/sched/completed", 17.0, 5),
        ("Serving/sched/slo_met", 15.0, 5),
        ("Serving/sched/slo_missed", 2.0, 5),
        ("Serving/sched/goodput_frac", 15.0 / 17.0, 5),
        ("Serving/sched/goodput_rps", 7.5, 5),
        ("Serving/sched/queue_depth", 2.0, 5),
        ("Serving/sched/queue_wait_ms_p50", 4.2, 5),
        ("Serving/sched/queue_wait_ms_p99", 41.0, 5),
        ("Serving/router/requests", 20.0, 5),
        ("Serving/router/affinity_hits", 8.0, 5),
        ("Serving/router/drains", 1.0, 5),
        ("Serving/router/replicas", 3.0, 5)])
    mon.close()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "scripts", "telemetry_report.py")
    out = subprocess.run(
        [sys.executable, script, str(tmp_path / "job" / "events.jsonl"),
         "--serving"], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "scheduler report" in out.stdout
    assert "preempted / resumed:    3 / 3" in out.stdout
    assert "goodput under SLO:      88.2% of completions" in out.stdout
    assert "queue depth (now):      2" in out.stdout
    assert "router report" in out.stdout
    assert "prefix-affinity hits:   8  (40.0% of placements)" in out.stdout
    assert "drains:                 1" in out.stdout


# --------------------------------------------------------------------------- #
# one program in flight (ISSUE 35): a tick launches program n+1 before it
# reads program n's tokens; every token once, in order, at most a tick later
# --------------------------------------------------------------------------- #
STOCHASTIC = SamplingParams(temperature=0.7, top_k=5, top_p=0.9)
OVERLAP_FAMILIES = {
    "llama": lambda: (llama, llama.LlamaConfig.tiny(max_seq_len=64)),
    "mixtral": lambda: _family("mixtral", "MixtralConfig"),
    # these cases are about the tick, not the ten-layer period
    # (tests/test_granite_hybrid.py keeps that): a run of each kind, repeated
    "granite_hybrid": lambda: _family(
        "granite_hybrid", "GraniteHybridConfig",
        layer_types=("mamba", "attention") * 2),
}


def _family(name, config, **kw):
    import importlib

    module = importlib.import_module(f"deepspeed_tpu.models.{name}")
    return module, getattr(module, config).tiny(max_seq_len=64, **kw)


@functools.cache
def _weights(family):
    """A family's module, configuration and seeded weights, once a module:
    every engine of it reads them and no program donates them."""
    module, cfg = OVERLAP_FAMILIES[family]()
    return module, cfg, module.init(cfg, jax.random.PRNGKey(0))


def _overlap_engine(family, **extra):
    """Four slots, chunks of 8: prompts over 8 tokens are split."""
    module, cfg, params = _weights(family)
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        module, cfg, params,
        config=dict({"dtype": "float32", "prefill_bucket": 8,
                     "split_prefill_chunk": 8,
                     "ragged": {"max_tracked_sequences": 4,
                                "max_ragged_batch_size": 4,
                                "memory_config_blocks": 96,
                                "block_size": 4}}, **extra))
    return eng


def _synchronous(sched):
    """The scheduler as it was before ISSUE 35: a tick runs ``engine.step()``
    - launch, then collect at once - and returns its own program's tokens."""
    def step_engine(seed):
        eng = sched.engine
        if not eng.state.seqs:
            return {}, {"decode_seqs": 0, "kv_tokens": 0}
        return eng.step(seed=seed), eng.last_step

    sched._step_engine = step_engine
    return sched


def _overlap_requests(vocab):
    """Split prompts (21, 30 tokens: three and four chunks), one-shot
    prompts (5, 7), a stochastic row of each kind, answers of 1 to 9 tokens
    - as many requests as slots, so none waits for an end to be noticed and
    a stochastic stream meets the same seeds in both runs -, then a greedy
    prompt of less than a chunk that waits for a slot. A short prompt
    admitted beside a program in flight (the last two: a tick admits two of
    these at most) rides the tick's program (ISSUE 37), where the
    synchronous ticks run it as a one-shot prefill."""
    rng = np.random.default_rng(35)
    mk = lambda n, m, sp=SP: Request(                       # noqa: E731
        prompt=rng.integers(1, vocab, (n,)).tolist(), max_new_tokens=m, sp=sp)
    return [mk(21, 6), mk(5, 9, STOCHASTIC), mk(30, 3, STOCHASTIC), mk(7, 1),
            mk(6, 4)]


def _run_streams(sched, reqs):
    """Submit, tick until done → (handles, what ``on_token`` saw, what the
    ticks returned, each tick's ``last_tick``)."""
    seen = {}
    handles = [sched.submit(r, on_token=seen.setdefault(i, []).append)
               for i, r in enumerate(reqs)]
    returned, ticks = {h.uid: [] for h in handles}, []
    one_shot = _one_shot_uids(sched.engine)
    while sched.pending:
        assert len(ticks) < 200
        for uid, toks in sched.tick().items():
            returned[uid].extend(toks)
        ticks.append(dict(sched.last_tick))
    # a tick returns what the engine's steps produced; a one-shot prompt's
    # first token went to its handle at admission
    for h in handles:
        if h.uid in one_shot:
            returned[h.uid].insert(0, h.tokens[0])
    return handles, seen, returned, ticks


def _one_shot_uids(eng):
    """The uids ``put_many`` prefills from here on (the list fills as the
    engine runs): the admissions that took the one-shot path."""
    uids, put_many = [], eng.put_many

    def spy(pairs, *args, **kwargs):
        uids.extend(uid for uid, _ in pairs)
        return put_many(pairs, *args, **kwargs)

    eng.put_many = spy
    return uids


@pytest.mark.parametrize("family", sorted(OVERLAP_FAMILIES))
def test_overlapped_ticks_stream_what_synchronous_steps_stream(family):
    """(a) Split prompts, one-shot prompts and stochastic rows through the
    scheduler's launch-then-collect ticks give, request for request, the
    streams the same requests give through synchronous ``engine.step()``
    calls with the same seeds: token for token, exactly ``max_new_tokens``,
    every token once and in order through ``on_token`` and the ticks'
    returns. A final chunk's first token is seated from the device, and the
    counter says how often a launch found the program before unread. (Until
    ISSUE 37 the requests of seven and six tokens, admitted beside a
    program in flight, were one-shot ``put``s that drained it; now their
    first tokens too are seated from a chunk, and nothing drains.)"""
    vocab = OVERLAP_FAMILIES[family]()[1].vocab_size
    ref_eng = _overlap_engine(family)
    want, *_ = _run_streams(
        _synchronous(ServingScheduler(ref_eng, SchedulerConfig())),
        _overlap_requests(vocab))
    assert ref_eng.overlapped_steps == 0        # (e) 0 through step() alone

    eng = _overlap_engine(family)
    sched = ServingScheduler(eng, SchedulerConfig())
    from_chunk = []
    launch = eng.launch

    def spy(*args, **kwargs):
        n = launch(*args, **kwargs)
        from_chunk.append(int((eng._slot_src == -2).sum()))
        return n

    eng.launch = spy
    handles, seen, returned, ticks = _run_streams(sched,
                                                  _overlap_requests(vocab))
    for i, (h, w) in enumerate(zip(handles, want)):
        assert h.state == DONE and w.state == DONE
        assert h.tokens == w.tokens, (i, h.tokens, w.tokens)
        assert len(h.tokens) == h.request.max_new_tokens
        assert seen[i] == returned[h.uid] == h.tokens
    # both split prompts' first tokens, and those of the two short prompts
    # that were admitted with a program in flight
    assert sum(from_chunk) == 4 == sched.stats["chunked_admissions"]
    assert eng.drains["put"] == 0 and all(t["drains"] == 0 for t in ticks)
    assert eng.in_flight == 0 and not eng.tokens_uncollected()
    assert not eng.state.seqs
    eng.state.debug_check()
    eng.debug_check_cache()
    # a tick's counts are the program it LAUNCHED: the rows add up to the
    # tokens that were decoded, and no row was launched past a count's end
    assert sum(t["decode_seqs"] for t in ticks) \
        == sum(r.max_new_tokens - 1 for r in _overlap_requests(vocab))
    assert 0 < eng.overlapped_steps < eng.steps
    events = dict((n, v) for n, v, _ in eng.engine_events())
    assert events["Serving/engine/overlapped_steps"] == eng.overlapped_steps
    assert validate_events(eng.engine_events()) == []


@pytest.mark.parametrize("family", sorted(OVERLAP_FAMILIES))
def test_overlapped_steps_counts_launches_over_an_unread_program(family):
    """(e) One one-shot prompt, admitted in the first tick, then split
    prompts alone: the first launch finds nothing in flight, every later
    ``decode_step`` launch finds the one before (``put_split`` reads
    nothing), and the last tick only collects."""
    vocab = OVERLAP_FAMILIES[family]()[1].vocab_size
    eng = _overlap_engine(family)
    sched = ServingScheduler(eng, SchedulerConfig())
    reqs = _overlap_requests(vocab)
    _, _, _, ticks = _run_streams(sched, [reqs[1], reqs[0], reqs[2]])
    decode_ticks = sum(t["decode_seqs"] > 0 for t in ticks)
    assert eng.overlapped_steps == decode_ticks - 1 > 0
    assert ticks[-1]["decode_seqs"] == 0 and eng.in_flight == 0


@pytest.mark.parametrize("family", sorted(OVERLAP_FAMILIES))
def test_an_eos_stream_ends_at_its_token_and_frees_the_dropped_row(family):
    """(b) A stream that ends on its ``eos_token_id`` mid-stream had a row
    in the program launched before the end was read: the stream stops AT the
    end token, the row's token is dropped, its block goes back with the
    sequence's, and the streams beside it are what they are without it."""
    vocab = OVERLAP_FAMILIES[family]()[1].vocab_size
    rng = np.random.default_rng(36)
    prompts = [rng.integers(1, vocab, (n,)).tolist() for n in (21, 6, 11)]
    # the stream that ends is sampled hot, so that its tokens differ (a tiny
    # random model repeats itself greedily); the same seeds meet it in both
    # runs up to its end, and the streams beside it are greedy
    hot = SamplingParams(temperature=1.5)
    mk = lambda eos=None: [Request(prompt=p, max_new_tokens=12,   # noqa: E731
                                   eos_token_id=eos if i == 1 else None,
                                   sp=hot if i == 1 else SP)
                           for i, p in enumerate(prompts)]
    full, *_ = _run_streams(
        ServingScheduler(_overlap_engine(family), SchedulerConfig()), mk())
    stream = full[1].tokens
    # an end token that first shows mid-stream
    at = next(i for i in range(2, 11) if stream[i] not in stream[:i])
    eng = _overlap_engine(family)
    sched = ServingScheduler(eng, SchedulerConfig())
    handles, seen, returned, _ = _run_streams(sched, mk(stream[at]))
    assert handles[1].tokens == stream[:at + 1] == seen[1] \
        == returned[handles[1].uid]
    assert [h.tokens for h in (handles[0], handles[2])] \
        == [full[0].tokens, full[2].tokens]
    assert eng.overlapped_steps > 0
    assert sched.stats["tokens_emitted"] + 1 == sum(   # one one-shot first
        len(h.tokens) for h in handles)
    assert not eng.state.seqs and eng.in_flight == 0
    assert eng.state.allocator.free_blocks == 95    # every block is back
    eng.state.debug_check()
    eng.debug_check_cache()


# --------------------------------------------------------------------------- #
# no admission drains the pipeline (ISSUE 37): a prompt that fits one chunk
# rides the tick's program where a one-shot prefill would have to read the
# program in flight first
# --------------------------------------------------------------------------- #
def _live_then_short(sched, vocab, short=(8, 3)):
    """Two streams live (a split prompt and a short one, one of them
    sampled) and, after three ticks, prompts of a chunk or less (the chunk's
    8 tokens; 3, most of its chunk padding) into the two free slots, one a
    tick
    → (handles, the ``last_tick`` of each admitting tick with the programs
    it found in flight, what each of those handles held when its admitting
    tick returned)."""
    rng = np.random.default_rng(37)
    mk = lambda n, m, sp=SP: Request(                       # noqa: E731
        prompt=rng.integers(1, vocab, (n,)).tolist(), max_new_tokens=m, sp=sp)
    handles = [sched.submit(r) for r in (mk(21, 12), mk(5, 14, STOCHASTIC))]
    for _ in range(3):
        sched.tick()
    admitting, held = [], []
    for n in short:
        handles.append(sched.submit(mk(n, 5)))
        flying = sched.engine.in_flight
        sched.tick()
        admitting.append(dict(sched.last_tick, found_in_flight=flying))
        held.append(list(handles[-1].tokens))
    sched.run()
    return handles, admitting, held


@pytest.mark.parametrize("family", sorted(OVERLAP_FAMILIES))
def test_a_short_prompt_beside_a_program_in_flight_rides_the_tick(family):
    """Streams live and a program in flight: a tick that admits a prompt of
    one chunk or less (8 = the chunk, 3 tokens) reads nothing for it -
    no ``put`` drain, ``last_tick["drains"]`` 0 -, counts it in
    ``chunked_admissions``, gives its first token with the NEXT tick's
    collect, and every stream, the admitted ones included, is token for
    token what synchronous ``step()`` ticks stream (which admit the same
    prompts as one-shot prefills: nothing is in flight there)."""
    vocab = OVERLAP_FAMILIES[family]()[1].vocab_size
    ref = ServingScheduler(_overlap_engine(family), SchedulerConfig())
    want, want_admitting, want_held = _live_then_short(_synchronous(ref),
                                                       vocab)
    assert [t["found_in_flight"] for t in want_admitting] == [0, 0]
    assert ref.stats["chunked_admissions"] == 1     # the split prompt alone
    # the first token at admission, the second from the same tick's step
    assert all(len(t) == 2 for t in want_held)

    eng = _overlap_engine(family)
    sched = ServingScheduler(eng, SchedulerConfig())
    launched = []
    launch_decode = eng._launch_decode
    eng._launch_decode = lambda live, seed, ch=None: (
        launched.append(ch), launch_decode(live, seed, ch))[1]
    handles, admitting, held = _live_then_short(sched, vocab)
    assert sched.stats["chunked_admissions"] == 3
    assert eng.drains["put"] == 0 == sum(eng.drains.values())
    for tick, tokens in zip(admitting, held):
        assert tick["found_in_flight"] == 1
        assert tick["admitted"] == 1 and tick["drains"] == 0
        assert tick["prefill_tokens"] in (8, 3)     # written by THIS launch
        assert tokens == []                     # it comes with the next read
    # each rode the tick's ONE program as a first-and-final chunk
    rode = [(len(ch.tokens), ch.ctx, ch.final) for ch in launched
            if ch is not None and ch.ctx == 0 and ch.final]
    assert rode == [(8, 0, True), (3, 0, True)]
    for i, (h, w) in enumerate(zip(handles, want)):
        assert h.state == DONE and w.state == DONE
        assert h.tokens == w.tokens, (i, h.tokens, w.tokens)
        assert len(h.tokens) == h.request.max_new_tokens
    assert eng.in_flight == 0 and not eng.state.seqs
    eng.state.debug_check()
    eng.debug_check_cache()


@pytest.mark.parametrize("case", ["idle", "quantum"])
def test_with_nothing_in_flight_a_short_prompt_stays_a_one_shot(case):
    """The one-shot prefill stays where it costs no drain: the first
    admission into an idle engine, and a scheduler of fused quanta (which
    reads its own program every tick) - the first token comes in the
    admitting tick."""
    vocab = OVERLAP_FAMILIES["llama"]()[1].vocab_size
    eng = _overlap_engine("llama")
    sched = ServingScheduler(eng, SchedulerConfig(
        decode_quantum=2 if case == "quantum" else 1))
    rng = np.random.default_rng(38)
    mk = lambda n, m: Request(                              # noqa: E731
        prompt=rng.integers(1, vocab, (n,)).tolist(), max_new_tokens=m)
    first = sched.submit(mk(6, 9))
    sched.tick()
    assert first.tokens and sched.last_tick["admitted"] == 1
    if case == "quantum":
        sched.tick()
        assert eng.in_flight == 0               # a quantum runs whole
        late = sched.submit(mk(5, 4))
        had = sched.stats["ticks"]
        sched.tick()
        assert sched.stats["ticks"] == had + 1 and late.tokens
    sched.run()
    assert sched.stats["chunked_admissions"] == 0
    assert sum(eng.drains.values()) == 0
    assert first.state == DONE and len(first.tokens) == 9


def test_a_gpt_prompt_that_fits_a_chunk_takes_the_chunk_lane():
    """Every family's tick carries a chunk in its decode program (ISSUE 46;
    ``gpt`` ran its chunks apart and read a final chunk's token where it
    was launched, so it kept the one-shot): a short ``gpt`` prompt beside a
    program in flight rides the next launch, no tick reads anything for an
    admission, and its first token comes with the next tick's collect."""
    from deepspeed_tpu.models import gpt

    cfg = gpt.GPTConfig.tiny(max_seq_len=64)
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        gpt, cfg, gpt.init(cfg, jax.random.PRNGKey(0)),
        config={"dtype": "float32", "prefill_bucket": 8,
                "split_prefill_chunk": 8,
                "ragged": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 4,
                           "memory_config_blocks": 96, "block_size": 4}})
    sched = ServingScheduler(eng, SchedulerConfig())
    handles, admitting, held = _live_then_short(sched, cfg.vocab_size)
    assert sched.stats["chunked_admissions"] == 3
    assert sum(eng.drains.values()) == 0
    assert [(t["found_in_flight"], t["drains"]) for t in admitting] \
        == [(1, 0), (1, 0)]
    assert held == [[], []]
    assert eng.mixed_steps >= 5     # three chunks of 21 tokens, and two
    assert all(h.state == DONE and len(h.tokens) == h.request.max_new_tokens
               for h in handles)


def test_moving_a_sequence_reads_what_is_in_flight_first(tiny):
    """(c) ``preempt`` (a park), ``evict_all`` and ``fork`` with a program
    in flight read it first: no token is lost with the move and none comes
    twice - the streams are the undisturbed run's."""
    cfg, _ = tiny
    reqs = lambda: _mk_requests(cfg, 3, gen_len=10)         # noqa: E731
    want = ServingScheduler(build(tiny, blocks=96), SchedulerConfig())
    want_handles, *_ = _run_streams(want, reqs())

    # preempt: park with a token in flight, resume, finish
    eng = build(tiny, blocks=96)
    sched = ServingScheduler(eng, SchedulerConfig())
    seen = {}
    handles = [sched.submit(r, on_token=seen.setdefault(i, []).append)
               for i, r in enumerate(reqs())]
    for _ in range(3):
        sched.tick()
    assert eng.in_flight == 1
    had = len(handles[0].tokens)
    sched.preempt(handles[0].uid)
    assert eng.in_flight == 0
    assert len(handles[0].tokens) == had + 1    # streamed before the park
    parked = next(e["parked"] for *_, e in sched._heap if e["valid"])
    assert parked["generated"] == handles[0].tokens
    returned = {}
    while sched.pending:
        for uid, toks in sched.tick().items():
            returned.setdefault(uid, []).extend(toks)
    for i, (h, w) in enumerate(zip(handles, want_handles)):
        assert h.tokens == w.tokens == seen[i]
        # what the ticks returned after the park: the rest, the drained
        # token first (it counted with the tick that returned next)
        assert h.tokens[-len(returned[h.uid]):] == returned[h.uid]
    eng.state.debug_check()

    # evict_all: the replica's drain re-homes whole streams
    eng = build(tiny, blocks=96)
    sched = ServingScheduler(eng, SchedulerConfig())
    handles = [sched.submit(r) for r in reqs()]
    for _ in range(4):
        sched.tick()
    assert eng.in_flight == 1
    moved = sched.evict_all()
    assert eng.in_flight == 0 and not eng.state.seqs
    for h, parked in moved:
        assert parked["generated"] == h.tokens
        assert parked["history"] == h.request.prompt + h.tokens
    other = ServingScheduler(build(tiny, blocks=96), SchedulerConfig())
    for h, parked in moved:
        other.accept(h, parked)
    other.run()
    assert [h.tokens for h in handles] == [w.tokens for w in want_handles]

    # fork: the child starts from the parent's newest token
    eng = build(tiny, blocks=96)
    eng.put(1, reqs()[0].prompt, SP)
    assert eng.launch(seed=1) == 1
    child = eng.fork(1, 2)
    assert eng.in_flight == 0
    assert child.last_token == eng.state.seqs[1].last_token \
        == eng.collect()[1][0] == want_handles[0].tokens[1]
    assert eng.step(seed=2)[2] == want_handles[0].tokens[2]


@pytest.mark.parametrize("family", sorted(OVERLAP_FAMILIES))
def test_a_quantum_of_one_tick_is_the_single_step(family):
    """``step_many`` clamps its quantum to what the longest live sequence
    has left of its context, so a quantum of ONE tick is an everyday call
    (and ``step_many(1)`` / ``generate(steps_per_sync=2, max_new_tokens=1)``
    ask for it outright): it runs the single step's program, whose result
    has that program's shape, and gives ``step()``'s tokens; the engine
    lives on after it."""
    vocab = OVERLAP_FAMILIES[family]()[1].vocab_size
    rng = np.random.default_rng(37)
    prompts = [rng.integers(1, vocab, (n,)).tolist() for n in (5, 21, 7)]
    eng, ref = _overlap_engine(family), _overlap_engine(family)
    for e in (eng, ref):
        e.put(1, prompts[0], SP)
        e.put_split(2, prompts[1], STOCHASTIC)
        e.put(3, prompts[2], STOCHASTIC)
    for seed in range(1, 7):
        want = ref.step(seed=seed)
        assert eng.step_many(1, seed=seed) == {u: [t]
                                               for u, t in want.items()}
    assert eng.step(seed=9) == ref.step(seed=9)
    assert eng.generate([prompts[0]], max_new_tokens=1, steps_per_sync=2) \
        == ref.generate([prompts[0]], max_new_tokens=1)
    eng.state.debug_check()
    eng.debug_check_cache()


@pytest.mark.parametrize("family", sorted(OVERLAP_FAMILIES))
def test_fused_quanta_run_to_the_context_end_as_single_steps_do(family):
    """A scheduler with ``decode_quantum=4`` whose streams run into the end
    of their context (64 tokens here): the quantum shrinks to 3, 2 and at
    last ONE tick as the longest stream nears it, and every greedy stream is
    token for token what synchronous single steps give."""
    vocab = OVERLAP_FAMILIES[family]()[1].vocab_size
    rng = np.random.default_rng(38)
    mk = lambda: [Request(prompt=rng_p, max_new_tokens=m)   # noqa: E731
                  for rng_p, m in zip(prompts, (100, 100, 9, 100))]
    prompts = [rng.integers(1, vocab, (n,)).tolist() for n in (23, 6, 5, 31)]
    want, *_ = _run_streams(
        _synchronous(ServingScheduler(_overlap_engine(family),
                                      SchedulerConfig())), mk())
    eng = _overlap_engine(family)
    ks = []
    quantum = eng._decode_quantum
    eng._decode_quantum = lambda k, *a: (ks.append(k), quantum(k, *a))[1]
    handles, seen, returned, _ = _run_streams(
        ServingScheduler(eng, SchedulerConfig(decode_quantum=4)), mk())
    assert {1, 4} <= set(ks)
    for i, (h, w) in enumerate(zip(handles, want)):
        assert h.state == DONE and w.state == DONE
        assert h.tokens == w.tokens == seen[i] == returned[h.uid], i
    # the context's end, not the budget, ended the three long streams
    assert [len(h.request.prompt) + len(h.tokens) for h in handles] \
        == [65, 65, 14, 65]
    assert eng.overlapped_steps == 0 and eng.in_flight == 0
    assert not eng.state.seqs
    eng.state.debug_check()
    eng.debug_check_cache()


def test_abandon_all_asks_the_device_for_nothing(tiny, monkeypatch):
    """A failed replica's program in flight is dropped UNREAD (a wedged
    device would never answer the sync): its tokens had reached no client,
    so the streams continue on a survivor from what their handles hold,
    token for token the undisturbed run's, each token once."""
    cfg, _ = tiny
    reqs = lambda: _mk_requests(cfg, 3, gen_len=10)         # noqa: E731
    want, *_ = _run_streams(
        ServingScheduler(build(tiny, blocks=96), SchedulerConfig()), reqs())
    eng = build(tiny, blocks=96)
    sched = ServingScheduler(eng, SchedulerConfig())
    seen = {}
    handles = [sched.submit(r, on_token=seen.setdefault(i, []).append)
               for i, r in enumerate(reqs())]
    for _ in range(4):
        sched.tick()
    assert eng.in_flight == 1
    had = [list(h.tokens) for h in handles]

    def wedged(fl):
        raise AssertionError("abandon_all read the program in flight")

    monkeypatch.setattr(eng, "_read", wedged)
    moved = sched.abandon_all()
    assert eng.in_flight == 0 and not eng.tokens_uncollected()
    assert not eng.state.seqs and not sched.pending
    assert [h.tokens for h in handles] == had
    for h, parked in moved:
        assert parked["generated"] == h.tokens
    eng.state.debug_check()
    other = ServingScheduler(build(tiny, blocks=96), SchedulerConfig())
    for h, parked in moved:
        other.accept(h, parked)
    other.run()
    for i, (h, w) in enumerate(zip(handles, want)):
        assert h.tokens == w.tokens == seen[i]
    # the abandoned engine starts over clean (its breaker may re-admit it)
    monkeypatch.undo()
    again, *_ = _run_streams(sched, reqs())
    assert [h.tokens for h in again] == [w.tokens for w in want]
