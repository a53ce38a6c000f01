"""Continuous-batching serving scheduler (docs/serving.md "Scheduler &
router").

Orca/FastGen-style request scheduling above ``InferenceEngineV2``: callers
``submit()`` requests and drive ``tick()`` (or ``run()``); the scheduler owns
admission, batch composition, preemption, and completion. Design points:

- **Priority/deadline queue.** A binary heap ordered by ``(priority,
  absolute deadline, arrival)`` — lower priority number is more urgent, ties
  break toward the earlier deadline, then FIFO. A bounded lookahead lets
  small requests bypass a blocked head-of-line request without starving it.
- **Admission control against KV headroom.** A request is admitted only when
  a sequence slot is free and ``StateManager.blocks_needed(prompt)`` fits the
  current ``headroom_blocks`` (free + retained-evictable) minus a configured
  reserve — budgeted cumulatively across a tick's admission burst, so a
  batched ``put_many`` can never over-commit the pool. Requests that could
  NEVER complete (prompt + generation outgrows the pool or ``max_seq_len``)
  are rejected at submit instead of thrashing forever.
- **SLO-aware batch composition.** With the engine's Dynamic-SplitFuse
  chunking enabled, long prompts are admitted via ``put_split`` so ongoing
  decodes never stall more than one chunk; a prompt that fits one chunk
  takes the same lane wherever a one-shot prefill would have to read a
  program in flight first (``_takes_chunk_lane``), and otherwise batches
  into one compiled ``put_many`` prefill per sampling config.
- **Decode preemption.** Before each decode quantum the scheduler asks
  ``StateManager.growth_blocks_short`` whether the next tokens' block needs
  (fresh tails AND copy-on-write) exceed headroom; if so, the least urgent
  live request is ``park()``-ed — its KV parks in the prefix cache's
  retained pool when enabled — and re-queued for ``resume()`` under its
  original priority/deadline. A greedy preempt/resume cycle is
  token-identical to an uninterrupted run (pinned by tests).
- **Streaming output.** Each submit returns a :class:`RequestHandle` whose
  ``drain()``/``on_token`` surface tokens as the engine emits them.
- **One program in flight.** A tick LAUNCHES its step's program and only
  then COLLECTS the program the tick before launched (``engine.launch`` /
  ``engine.collect``), so admission, preparation, dispatch, harvest and
  retirement run while the device works; a tick returns the tokens of the
  program launched one tick earlier. A stream that ends by count gets no row
  in the program launched past its end; whatever needs a token's value or
  moves a sequence reads what is in flight first (``_drain``) - no
  admission does: beside a program in flight a prompt rides the next
  launch as a chunk.

The scheduler drives the engine exclusively through its public API (``put``,
``put_split``, ``launch``, ``collect``, ``step``, ``step_many``, ``park``,
``resume``, ``finish``) — serving WITHOUT a scheduler runs the engine's
synchronous ``step()``: the same programs, launched and collected at once.
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import math
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ...telemetry.trace import percentiles
from ..sampling import SamplingParams

QUEUED = "queued"
RUNNING = "running"
PARKED = "parked"
DONE = "done"
REJECTED = "rejected"


@dataclasses.dataclass
class Request:
    """One serving request. ``priority`` is lower-is-more-urgent;
    ``deadline_ms`` is the end-to-end SLO measured from ``submit()`` (used
    for queue ordering, optional expiry, and goodput-under-SLO accounting).
    ``uid`` is assigned at submit when left ``None``."""

    prompt: List[int]
    max_new_tokens: int = 64
    priority: int = 0
    deadline_ms: float = math.inf
    session_id: Optional[int] = None
    eos_token_id: Optional[int] = None
    sp: SamplingParams = SamplingParams(greedy=True)
    uid: Optional[int] = None
    # fleet observability (telemetry/fleet.py; both default None — the
    # plain serving path never reads them): the billing/SLO tenant tag, and
    # the router-minted cross-replica TraceContext
    tenant: Optional[str] = None
    trace_ctx: Optional[Any] = None


class RequestHandle:
    """Streaming view of one submitted request: ``tokens`` grows as the
    engine emits, ``drain()`` returns the tokens since the last drain, and
    an optional ``on_token(token)`` callback fires per token. Terminal
    states set ``e2e_ms``/``slo_met``; ``error`` carries the rejection
    reason for :data:`REJECTED` handles."""

    def __init__(self, request: Request,
                 on_token: Optional[Callable[[int], None]] = None):
        self.request = request
        self.uid = request.uid
        self.state = QUEUED
        self.tokens: List[int] = []
        self.on_token = on_token
        self.error: Optional[str] = None
        self.queue_wait_ms: Optional[float] = None
        self.e2e_ms: Optional[float] = None
        self.slo_met: Optional[bool] = None
        self.preemptions = 0
        self.replica: Optional[int] = None   # stamped by ReplicaRouter
        self.kv_wire_bytes = 0   # disagg handoff wire traffic (router)
        self._cursor = 0
        self._submit_t: Optional[float] = None
        self._deadline_t = math.inf
        # fleet observability seams (telemetry/fleet.py): the tenant
        # accountant's streaming hook, its terminal-accounting latch, and
        # the last token-arrival time it stamped. All dormant (None/False)
        # unless a router with the obs plane enabled wires them.
        self._obs = None
        self._obs_done = False
        self._obs_last_t: Optional[float] = None

    @property
    def done(self) -> bool:
        return self.state in (DONE, REJECTED)

    def drain(self) -> List[int]:
        new = self.tokens[self._cursor:]
        self._cursor = len(self.tokens)
        return new

    def _emit(self, toks: List[int]) -> int:
        room = self.request.max_new_tokens - len(self.tokens)
        eos = self.request.eos_token_id
        emitted = 0
        for t in toks[:max(0, room)]:
            self.tokens.append(t)
            emitted += 1
            if self.on_token is not None:
                self.on_token(t)
            if eos is not None and t == eos:
                break
        if emitted and self._obs is not None:
            self._obs.on_tokens(self, emitted)
        return emitted

    @property
    def finished_stream(self) -> bool:
        eos = self.request.eos_token_id
        return len(self.tokens) >= self.request.max_new_tokens or \
            (eos is not None and bool(self.tokens) and self.tokens[-1] == eos)


@dataclasses.dataclass
class SchedulerConfig:
    max_live: int = 0                # concurrent sequences; 0 = engine slots
    reserve_blocks: int = 0          # headroom kept back from admissions
    decode_quantum: int = 1          # fused decode ticks per scheduler tick
    preempt: bool = True             # allow decode preemption under pressure
    admission_lookahead: int = 4     # queue entries scanned past a blocked head
    max_admissions_per_tick: int = 0  # 0 = unlimited
    drop_expired: bool = False       # reject queued requests past deadline
    clock: Callable[[], float] = time.monotonic  # injectable for tests


class ServingScheduler:
    """See module docstring. One scheduler owns one engine; multi-replica
    serving composes schedulers behind :class:`~.router.ReplicaRouter`."""

    def __init__(self, engine, config: Optional[SchedulerConfig] = None):
        self.engine = engine
        self.cfg = config or SchedulerConfig()
        self.tracer = engine.tracer
        self._trace_on = engine.tracer.enabled
        self._clock = self.cfg.clock
        self._heap: List[Tuple[int, float, int, dict]] = []
        self._arrival = itertools.count()
        self._uids = itertools.count(1)
        self.handles: Dict[int, RequestHandle] = {}   # queued + live
        self._live: Dict[int, RequestHandle] = {}
        self.stats: Dict[str, int] = {
            "submitted": 0, "admitted": 0, "resumed": 0, "preempted": 0,
            "rejected": 0, "expired": 0, "completed": 0, "slo_met": 0,
            "slo_missed": 0, "ticks": 0, "chunked_admissions": 0,
            "tokens_emitted": 0,
            # what the ticks did (the sched_tick span's arguments, summed):
            # prompt tokens whose KV a tick wrote, sequences in its decode
            # batch, ticks that carried prefill work
            "prefill_tokens": 0, "decode_seq_steps": 0, "chunk_ticks": 0}
        # the last tick's sched_tick arguments (read-only; the same numbers
        # a profiler session records on the span)
        self.last_tick: Dict[str, int] = {}
        self._admit_tokens = 0   # tokens the current tick's admissions streamed
        # tokens a drain streamed since the last tick returned (``_drain``):
        # they are in their handles already and count with the next tick
        self._early: Dict[int, List[int]] = {}
        self._queue_wait_ms: List[float] = []
        self._e2e_ms: List[float] = []
        self._t0 = self._clock()
        # overload degradation (fleet.DegradationLadder level 3): when set,
        # every admission's max_new_tokens is clamped to this many tokens
        # (never below what the stream already emitted). None = no clamp —
        # the default path never consults it.
        self.degrade_max_new_tokens: Optional[int] = None
        # fleet observability plane (telemetry/fleet.py), attached by a
        # ReplicaRouter whose serving.obs block is enabled. None = every
        # obs hook below is skipped — the plain path stays byte-identical.
        self.obs = None
        # online self-tuning (tuning/tuner.py; docs/tuning.md), attached by
        # a ReplicaRouter whose serving.tuning block is enabled (or a test
        # directly). None = tick() never consults it — the default token
        # stream is byte-identical to pre-tuning behavior.
        self.tuning = None

    # -- queue ----------------------------------------------------------- #
    @property
    def queue_depth(self) -> int:
        return sum(1 for *_, e in self._heap if e["valid"])

    @property
    def live_count(self) -> int:
        return len(self._live)

    @property
    def pending(self) -> bool:
        """Work remains: anything queued, parked, or live - or a program
        in flight whose tokens no tick has returned yet."""
        return bool(self._live) or self.queue_depth > 0 or \
            self.engine.in_flight > 0

    def _push(self, handle: RequestHandle,
              parked: Optional[Dict[str, Any]] = None) -> None:
        entry = {"handle": handle, "parked": parked, "valid": True}
        heapq.heappush(self._heap, (handle.request.priority,
                                    handle._deadline_t,
                                    next(self._arrival), entry))

    def submit(self, request: Request,
               on_token: Optional[Callable[[int], None]] = None
               ) -> RequestHandle:
        """Enqueue one request → its streaming handle. Requests that can
        never be served — empty prompt, prompt at/over ``max_seq_len``, or a
        worst-case completion footprint larger than the whole KV pool — are
        rejected immediately (``state=REJECTED``, reason in ``error``)
        instead of wedging the queue."""
        if request.uid is None:
            request.uid = next(self._uids)
        handle = RequestHandle(request, on_token=on_token)
        now = self._clock()
        handle._submit_t = now
        handle._deadline_t = now + request.deadline_ms / 1e3 \
            if math.isfinite(request.deadline_ms) else math.inf
        self.stats["submitted"] += 1
        reason = self._reject_reason(request)
        if reason is not None:
            handle.state = REJECTED
            handle.error = reason
            self.stats["rejected"] += 1
            if self.obs is not None:
                self.obs.request_done(handle)
            return handle
        if self.obs is not None:
            handle._obs = self.obs.accountant
        self.handles[request.uid] = handle
        self._push(handle)
        return handle

    def _reject_reason(self, req: Request) -> Optional[str]:
        st = self.engine.state
        max_len = self.engine.family.cfg.max_seq_len
        capacity = st.allocator.num_blocks - 1
        if not req.prompt:
            return "empty prompt"
        if len(req.prompt) >= max_len:
            return (f"prompt of {len(req.prompt)} tokens >= max_seq_len "
                    f"{max_len}")
        if st.blocks_needed(len(req.prompt)) > capacity:
            return (f"prompt needs {st.blocks_needed(len(req.prompt))} KV "
                    f"blocks but the pool holds {capacity}")
        # worst-case single-request footprint: a park right before the last
        # token resumes with a history of total-1 tokens — if even that
        # admission can't fit an EMPTY pool, the request would thrash
        # park/resume forever instead of completing
        total = min(len(req.prompt) + req.max_new_tokens, max_len)
        if st.blocks_needed(total - 1) > capacity:
            return (f"completion footprint of {total} tokens "
                    f"({st.blocks_needed(total - 1)} blocks worst-case) can "
                    f"never fit the {capacity}-block pool")
        return None

    # -- router drain support -------------------------------------------- #
    def evict_all(self) -> List[Tuple[RequestHandle,
                                      Optional[Dict[str, Any]]]]:
        """Drain this scheduler (replica removal): park every live sequence
        and pop every queued entry, returning ``(handle, parked)`` pairs the
        router re-homes on surviving replicas via :meth:`accept` — the SAME
        handle objects keep streaming, and parked histories re-prefill on
        the new replica (KV never crosses engines; token history does)."""
        out: List[Tuple[RequestHandle, Optional[Dict[str, Any]]]] = []
        self._drain("sched_evict")
        for uid, h in list(self._live.items()):
            parked = self.engine.park(uid)
            if h.request.trace_ctx is not None:
                # cross-replica move: close this engine's leg of the fleet
                # trace (park alone leaves it open for a SAME-engine resume)
                self.engine.release_trace(uid, reason="drain")
            h.state = PARKED
            h.preemptions += 1
            del self._live[uid]
            self.handles.pop(uid, None)
            out.append((h, parked))
        while self._heap:
            *_, entry = heapq.heappop(self._heap)
            if not entry["valid"]:
                continue
            h = entry["handle"]
            self.handles.pop(h.request.uid, None)
            out.append((h, entry["parked"]))
        return out

    def accept(self, handle: RequestHandle,
               parked: Optional[Dict[str, Any]] = None) -> None:
        """Enqueue a request that already has a handle (router re-homing
        after a drain or failover). Keeps the original submit time and
        deadline."""
        handle.state = QUEUED
        self.handles[handle.request.uid] = handle
        self._push(handle, parked=parked)

    def export_live(self, uid: int) -> Tuple[RequestHandle,
                                             Dict[str, Any]]:
        """Detach ONE live sequence for a disaggregated prefill→decode
        handoff (docs/serving.md "Disaggregated prefill/decode"): park it,
        close this replica's trace leg as a handoff, and hand back
        ``(handle, parked)`` for the router to :meth:`accept` on the
        decode-tier replica. The caller exports KV blocks BEFORE calling
        this — park retires the sequence, after which its uid is unknown
        here. Unlike :meth:`evict_all` this is the PLANNED move of the
        two-tier pipeline, not a preemption, so the handle's preemption
        count is untouched."""
        # a first token in flight goes out with its handle
        self._drain("sched_export")
        h = self._live.pop(uid)
        parked = self.engine.park(uid)
        if h.request.trace_ctx is not None:
            self.engine.release_trace(uid, reason="handoff")
        h.state = PARKED
        self.handles.pop(uid, None)
        return h, parked

    def abandon_all(self) -> List[Tuple[RequestHandle,
                                        Optional[Dict[str, Any]]]]:
        """Evict every request WITHOUT engine cooperation — the crash/hang
        failover counterpart of :meth:`evict_all` (docs/serving.md "Fleet
        fault tolerance"). Live continuations are reconstructed from each
        handle's CLIENT-VISIBLE stream (prompt + the tokens already emitted)
        instead of ``engine.park``, so a crashed or wedged engine is never
        asked to do anything on the failover path; its host bookkeeping is
        cleaned best-effort so a recovered replica starts empty. Streams
        that already emitted their full budget finalize as DONE here.
        Exactly-once delivery: the parked ``generated`` list carries every
        token the handle emitted, so ``engine.resume`` on the survivor
        continues the stream without re-emitting any of them — and a greedy
        replay of prompt + emitted history regenerates exactly the next
        stream token (token-identical failover, parity-pinned). A program
        the engine has in flight is NOT read (that would be asking a wedged
        device for a sync): no client has seen its tokens, and the survivor
        samples them again."""
        out: List[Tuple[RequestHandle, Optional[Dict[str, Any]]]] = []
        self.engine.forget_flight()     # host bookkeeping only
        for uid, h in list(self._live.items()):
            del self._live[uid]
            self.handles.pop(uid, None)
            # release BEFORE engine.finish: a stream leaving mid-flight must
            # end its replica leg tagged as a handoff, not as a normal
            # finish (finished streams keep the normal span-end path)
            if h.request.trace_ctx is not None and not h.finished_stream:
                try:
                    self.engine.release_trace(uid, reason="failover")
                except Exception:
                    pass
            try:
                self.engine.finish(uid)   # frees slot + blocks when the
            except Exception:             # engine still works (hang/slow);
                pass                      # a truly crashed engine may leak
                                          # until the breaker re-probes it
            if h.finished_stream:
                self._finalize(h)
                continue
            h.state = PARKED
            h.preemptions += 1
            out.append((h, {"uid": uid,
                            "history": list(h.request.prompt)
                            + list(h.tokens),
                            "generated": list(h.tokens),
                            "prompt_len": len(h.request.prompt),
                            "sp": h.request.sp}))
        while self._heap:
            *_, entry = heapq.heappop(self._heap)
            if not entry["valid"]:
                continue
            h = entry["handle"]
            self.handles.pop(h.request.uid, None)
            out.append((h, entry["parked"]))
        return out

    def shed(self, min_priority: int, reason: str) -> List[RequestHandle]:
        """Reject every QUEUED, not-yet-started request whose priority is
        ``min_priority`` or lower-urgency (higher number) — the degradation
        ladder's level-1 action. Requests that already consumed compute
        (parked/preempted histories) are spared: shedding admissions first
        loses the least work. Returns the shed handles."""
        out: List[RequestHandle] = []
        for *_, entry in self._heap:
            h = entry["handle"]
            if not entry["valid"] or entry["parked"] is not None or \
                    h.request.priority < min_priority:
                continue
            entry["valid"] = False
            self.handles.pop(h.request.uid, None)
            h.state = REJECTED
            h.error = reason
            h.slo_met = False
            self.stats["rejected"] += 1
            if self.obs is not None:
                self.obs.request_done(h)
            out.append(h)
        return out

    # -- the scheduling loop --------------------------------------------- #
    def tick(self, seed: Optional[int] = None) -> Dict[int, List[int]]:
        """One scheduler quantum: expire (optional) → admit/resume →
        preempt-guard → LAUNCH this tick's engine step → COLLECT the step
        the tick before launched (a fused ``decode_quantum`` or a
        speculative step runs whole) → stream tokens → retire completions.
        Returns {uid: tokens emitted this tick} for the requests that
        produced output: the collected program's, and what a drain streamed
        since the last tick returned."""
        self.stats["ticks"] += 1
        if seed is None:
            seed = self.stats["ticks"]
        span, eng = self.tracer.span, self.engine
        with span("sched_tick", cat="serving",
                  tick=self.stats["ticks"]) as tick:
            now = self._clock()
            wrote = eng.prefill_tokens_written
            drains = sum(eng.drains.values())
            released = eng.state.window_blocks_released
            self._admit_tokens = 0
            with span("sched_expire", cat="serving"):
                if self.cfg.drop_expired:
                    self._expire(now)
            with span("sched_admit", cat="serving"):
                n_adm = self._admit(now, seed)
            with span("sched_preempt_guard", cat="serving"):
                n_pre = self._preempt_guard()
            with span("sched_step_engine", cat="serving"):
                out, did = self._step_engine(seed)
            with span("sched_harvest", cat="serving"):
                emitted, self._early = self._early, {}
                for uid, toks in self._harvest(out).items():
                    emitted.setdefault(uid, []).extend(toks)
            with span("sched_retire", cat="serving"):
                self._retire()
            self.last_tick = {
                "tick": self.stats["ticks"], "admitted": n_adm,
                "preempted": n_pre, "live": len(self._live),
                "queued": self.queue_depth,
                "prefill_tokens": eng.prefill_tokens_written - wrote,
                "decode_seqs": did["decode_seqs"],
                "kv_tokens": did["kv_tokens"],
                # every token streamed to a client this tick: an admission's
                # first token (one-shot prefill, resume) and the harvest's
                "tokens_out": self._admit_tokens
                + sum(len(v) for v in emitted.values()),
                # the drains that read a program during the tick
                "drains": sum(eng.drains.values()) - drains}
            if eng.state.window_kinds:
                # blocks the window layers' kind gave back behind the window
                self.last_tick["window_blocks_released"] = \
                    eng.state.window_blocks_released - released
            if eng.family.latent_kind:
                # the latent pool: the cached rows ONE layer's decode rows
                # read this tick, the blocks held and the blocks there are
                self.last_tick.update(
                    kv_tokens_latent=did["kv_tokens"],
                    latent_blocks_live=eng.blocks_live(),
                    latent_blocks=eng.state.allocator.num_blocks - 1)
            tick.set(**self.last_tick)
        self.stats["prefill_tokens"] += self.last_tick["prefill_tokens"]
        self.stats["decode_seq_steps"] += self.last_tick["decode_seqs"]
        self.stats["chunk_ticks"] += self.last_tick["prefill_tokens"] > 0
        if self.tuning is not None:
            # sched-tick seam: the only point a serving knob may flip —
            # between ticks no request is mid-admission or mid-harvest, and
            # (``_step_engine``) no program is in flight
            self.tuning.on_sched_tick(self)
        return emitted

    def run(self, max_ticks: int = 100000) -> None:
        """Drive ticks until every submitted request is done (or the tick
        budget, a runaway backstop, is spent)."""
        ticks = 0
        while self.pending and ticks < max_ticks:
            self.tick()
            ticks += 1
        if self.pending:
            raise RuntimeError(
                f"scheduler did not drain within {max_ticks} ticks "
                f"({len(self._live)} live, {self.queue_depth} queued)")

    def _expire(self, now: float) -> None:
        for *_, entry in self._heap:
            h = entry["handle"]
            if entry["valid"] and now > h._deadline_t:
                entry["valid"] = False
                self.handles.pop(h.request.uid, None)
                h.state = REJECTED
                h.error = "deadline expired in queue"
                h.slo_met = False
                self.stats["expired"] += 1
                self.stats["slo_missed"] += 1
                if self.obs is not None:
                    self.obs.request_done(h)

    def _admit(self, now: float, seed: int) -> int:
        """Admit while slots + block headroom allow, most urgent first with
        bounded lookahead past a blocked head. A prompt (or a resume's
        history) enters one of two ways, ``_takes_chunk_lane`` says which:
        through ``put_split``, chunk by chunk inside the ticks' own
        programs, so live decodes keep ticking and nothing is read; or as a
        one-shot prefill, batched into one ``put_many`` per sampling config,
        whose first token streams in this tick. The block budget decrements
        per admission, so the whole burst can never over-commit the pool."""
        eng, cfg = self.engine, self.cfg
        st = eng.state
        max_live = cfg.max_live or st.max_sequences
        budget = st.headroom_blocks - cfg.reserve_blocks
        slots = st.free_slots
        batches: Dict[SamplingParams, List[Tuple[int, List[int]]]] = {}
        stash: List[Tuple[int, float, int, dict]] = []
        admitted = 0
        skipped = 0
        while self._heap and slots > 0 and len(self._live) + admitted \
                < max_live:
            if cfg.max_admissions_per_tick and \
                    admitted >= cfg.max_admissions_per_tick:
                break
            item = heapq.heappop(self._heap)
            entry = item[3]
            if not entry["valid"]:
                continue
            h = entry["handle"]
            parked = entry["parked"]
            if self.degrade_max_new_tokens is not None:
                # overload clamp (degradation level 3): shorten what this
                # admission may generate, never below what it already
                # emitted — the stream stays exactly-once, just shorter
                h.request.max_new_tokens = min(
                    h.request.max_new_tokens,
                    max(self.degrade_max_new_tokens, len(h.tokens)))
            tokens = parked["history"] if parked else h.request.prompt
            need = st.blocks_needed(len(tokens))
            if need > budget:
                stash.append(item)
                skipped += 1
                if skipped > cfg.admission_lookahead:
                    break
                continue
            budget -= need
            slots -= 1
            admitted += 1
            uid = h.request.uid
            if h.request.trace_ctx is not None:
                eng.adopt_trace(uid, h.request.trace_ctx)
            h.state = RUNNING
            self._live[uid] = h
            if h.queue_wait_ms is None:
                h.queue_wait_ms = (now - h._submit_t) * 1e3
                self._queue_wait_ms.append(h.queue_wait_ms)
            lane = self._takes_chunk_lane(len(tokens))
            if parked is not None:
                toks = eng.resume(parked, seed=seed, split=lane)
                self._admit_tokens += h._emit(toks)
                self.stats["resumed"] += 1
            elif lane:
                eng.put_split(uid, tokens, h.request.sp)
                self.stats["chunked_admissions"] += 1
                self.stats["admitted"] += 1
            else:
                batches.setdefault(h.request.sp, []).append((uid, tokens))
                self.stats["admitted"] += 1
        for item in stash:
            heapq.heappush(self._heap, item)
        for sp, pairs in batches.items():
            first = eng.put_many(pairs, sp, seed=seed)
            for uid, tok in first.items():
                self._admit_tokens += self.handles[uid]._emit([tok])
        return admitted

    def _takes_chunk_lane(self, n_tokens: int) -> bool:
        """Whether a prompt of ``n_tokens`` enters through ``put_split``
        (with SplitFuse chunking on): one that outgrows a chunk always, and
        one that FITS a chunk wherever the one-shot prefill would have to
        read a program in flight first (``engine.drain("put")``) - the
        prompt then rides the next launch's decode program as a
        first-and-final chunk, its first token comes with the collect
        after, and the device never waits for the host. With nothing in
        flight (an idle engine, a fused quantum, a speculative step, an
        attached tuner: each read its own program before this tick's
        admissions) the one-shot costs no drain and streams its first token
        in the admitting tick, so it stays."""
        eng = self.engine
        split = eng.config.split_prefill_chunk
        if split <= 0:
            return False
        from ..engine import _round_up
        if n_tokens > _round_up(split, eng.config.prefill_bucket):
            return True
        return eng.in_flight > 0

    def _preempt_guard(self) -> int:
        """Park the least urgent live requests until the next decode
        quantum's block needs fit headroom — admission control's runtime
        counterpart: with the guard, a decode step can never surface a
        pool-exhausted allocation to a request."""
        if not self.cfg.preempt:
            return 0
        st = self.engine.state
        n = max(1, self.cfg.decode_quantum)
        preempted = 0
        while len(self._live) > 1 and st.growth_blocks_short(n=n) > 0:
            victim = self._pick_victim()
            if victim is None:
                break
            preempted += 1
            self._park_to_queue(victim)
        return preempted

    def _pick_victim(self) -> Optional[RequestHandle]:
        """Least urgent live request: highest priority number, then latest
        deadline, then most recently admitted (prefilling sequences are
        spared — parking one discards chunk work for no freed decode
        pressure)."""
        best = None
        for uid, h in self._live.items():
            d = self.engine.state.seqs.get(uid)
            if d is None or d.prefilling:
                continue
            key = (h.request.priority, h._deadline_t, uid)
            if best is None or key > best[0]:
                best = (key, h)
        return best[1] if best else None

    def preempt(self, uid: int) -> None:
        """Explicitly park one live request and re-queue it (tests,
        draining, manual intervention)."""
        h = self._live.get(uid)
        if h is None:
            from ..ragged import UnknownSequenceError

            raise UnknownSequenceError(uid)
        self._park_to_queue(h)

    def _drain(self, cause: str) -> None:
        """Before a sequence moves (a park, a hand-off, a replica's drain):
        read what the engine has in flight - a drain, under the ``cause``
        its call site has in ``telemetry.schema.DRAIN_CAUSES`` - and stream
        it, so the move loses and doubles no token. The tokens are in their
        handles at once and count with the tick that returns next."""
        self.engine.drain(cause)
        for uid, toks in self._harvest(self.engine.collect()).items():
            self._early.setdefault(uid, []).extend(toks)

    def _park_to_queue(self, h: RequestHandle) -> None:
        uid = h.request.uid
        self._drain("sched_park")
        parked = self.engine.park(uid)
        del self._live[uid]
        h.state = PARKED
        h.preemptions += 1
        self.stats["preempted"] += 1
        self._push(h, parked=parked)
        if self._trace_on:
            self.tracer.instant("sched_preempt", cat="serving", uid=uid,
                                kv_tokens=len(parked["history"]))

    def _step_engine(self, seed: int):
        """Launch this tick's engine step, collect the one before → the
        collected tokens and what the LAUNCHED step does
        (``engine.last_step``; zeros when there was nothing to step). A
        fused quantum and a speculative step read what is in flight and run
        whole; with the tuner attached the tick collects its own program,
        so that a knob flips with nothing in flight."""
        eng = self.engine
        if not eng.state.seqs:
            return eng.collect(), {"decode_seqs": 0, "kv_tokens": 0}
        if self.cfg.decode_quantum > 1 and not eng._spec_on:
            out = eng.step_many(self.cfg.decode_quantum, seed=seed)
        elif eng._spec_on:
            out = eng.step(seed=seed)
        else:
            ahead = eng.launch(seed, hold=self._at_their_end())
            out = eng.collect(0 if self.tuning is not None else ahead)
        return out, eng.last_step

    def _at_their_end(self) -> set:
        """The live streams that end by COUNT with the tokens already
        launched for them (``max_new_tokens``, ``max_seq_len``): known at
        launch, so the program launched now has no row for them. A stream
        that may end on its ``eos_token_id`` keeps its row: should the
        token in flight be the end, the one after it is dropped
        (``RequestHandle._emit``) and its KV row goes with the sequence's
        blocks."""
        eng = self.engine
        unread = eng.tokens_uncollected()
        max_len = eng.family.cfg.max_seq_len
        hold = set()
        for uid, h in self._live.items():
            d = eng.state.seqs.get(uid)
            if d is None or d.prefilling:
                continue
            if h.finished_stream or d.seen_tokens >= max_len or \
                    len(h.tokens) + unread.get(uid, 0) \
                    >= h.request.max_new_tokens:
                hold.add(uid)
        return hold

    def _harvest(self, out) -> Dict[int, List[int]]:
        emitted: Dict[int, List[int]] = {}
        for uid, t in out.items():
            h = self._live.get(uid)
            if h is None:
                continue
            toks = list(t) if isinstance(t, list) else [t]
            n = h._emit(toks)
            if n:
                emitted[uid] = h.tokens[-n:]
                self.stats["tokens_emitted"] += n
        return emitted

    def _retire(self) -> None:
        max_len = self.engine.family.cfg.max_seq_len
        # a ``finish`` below may read the program in flight: its tokens
        # move to the next collect and stay counted
        unread = self.engine.tokens_uncollected()
        for uid, h in list(self._live.items()):
            d = self.engine.state.seqs.get(uid)
            if d is None:
                continue
            if d.prefilling:
                continue
            # a stream at the end of its context waits for its last token;
            # one that has ENDED (a count, its eos) goes now, and a token
            # still in flight for it is read by ``finish`` and dropped
            if h.finished_stream or (
                    d.seen_tokens >= max_len and uid not in unread):
                self.engine.finish(uid)
                del self._live[uid]
                self.handles.pop(uid, None)
                self._finalize(h)

    def _finalize(self, h: RequestHandle) -> None:
        """Mark a stream complete: terminal state, e2e latency, SLO and
        goodput accounting (shared by :meth:`_retire` and
        :meth:`abandon_all`)."""
        h.state = DONE
        h.e2e_ms = (self._clock() - h._submit_t) * 1e3
        h.slo_met = h.e2e_ms <= h.request.deadline_ms
        self._e2e_ms.append(h.e2e_ms)
        self.stats["completed"] += 1
        self.stats["slo_met" if h.slo_met else "slo_missed"] += 1
        if self.obs is not None:
            self.obs.request_done(h)

    # -- telemetry -------------------------------------------------------- #
    def sched_events(self, step: int = 0):
        """``Serving/sched/*`` telemetry events: cumulative scheduler
        counters, the queue-depth gauge, queue-wait percentiles, and
        goodput-under-SLO (requests completed within their deadline, as a
        fraction of completions and as a rate). All names are registered in
        ``telemetry/schema.py SERVING_SERIES``."""
        vals: Dict[str, float] = {k: float(v) for k, v in self.stats.items()}
        vals["queue_depth"] = float(self.queue_depth)
        qw = percentiles(self._queue_wait_ms, (50, 90, 99))
        for k, v in qw.items():
            vals[f"queue_wait_ms_{k}"] = float(v)
        vals["queue_wait_ms_count"] = float(len(self._queue_wait_ms))
        done = self.stats["completed"]
        vals["goodput_frac"] = (self.stats["slo_met"] / done) if done else 0.0
        elapsed = max(self._clock() - self._t0, 1e-9)
        vals["goodput_rps"] = self.stats["slo_met"] / elapsed
        return [(f"Serving/sched/{k}", float(v), step)
                for k, v in sorted(vals.items())]

    def publish_sched_telemetry(self, step: int = 0):
        events = self.sched_events(step)
        hub = getattr(self.engine, "_hub", None)
        if hub is not None:
            for name, value, s in events:
                hub.serving_event(name, value, s)
        return events

    def queue_wait_summary(self) -> Dict[str, float]:
        out = percentiles(self._queue_wait_ms, (50, 90, 99))
        out["count"] = float(len(self._queue_wait_ms))
        return out
