"""A launched program's life on one clock (ISSUE 36).

Every program the serving engine launches takes the next number; it is the
``seq`` of its launch span and of the ``engine_wait`` that reads it (the
``engine_emit`` of those tokens is the span that follows it), wherever
that nests. Every read of what is in flight AHEAD of the tick's own
``collect`` is a drain: one ``engine_drain{cause}`` span around the reads
and one count in ``engine.drains[cause]``, under the
cause its call site has in ``telemetry.schema.DRAIN_CAUSES``
(docs/observability.md "Every drain has a cause") - and with nothing in
flight it is nothing, so the synchronous paths stay event-free.

The spans are taken where the profiler would take them: a stand-in for
``jax.profiler.TraceAnnotation`` on the engine's tracer (ring off), which
records each annotation with the one it was opened under.
"""

import jax
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference import build_engine_v2
from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                             ServingScheduler)
from deepspeed_tpu.models import gpt, llama
from deepspeed_tpu.telemetry import schema
from deepspeed_tpu.telemetry.trace import TIMELINE_PREFIX

LAUNCHES = ("decode_step", "prefill_chunk", "prefill_batch", "decode_quantum",
            "spec_verify")
READS = ("engine_wait", "engine_emit")
SLOTS, BLOCK, CHUNK = 4, 4, 8


class _Timeline:
    """What a profiler session would record of the program's spans:
    ``records`` in the order they were opened, each ``{name, args, parent}``
    with ``parent`` the record it was opened under (or None)."""

    def __init__(self):
        self.records, self._open = [], []

    def __call__(self, name, **args):
        assert name.startswith(TIMELINE_PREFIX)
        return _Annotation(self, name[len(TIMELINE_PREFIX):], args)

    def since(self, mark=0):
        return self.records[mark:]

    def mark(self):
        return len(self.records)


class _Annotation:
    def __init__(self, timeline, name, args):
        self._timeline = timeline
        self._record = {"name": name, "args": dict(args), "parent": None}

    def __enter__(self):
        t = self._timeline
        self._record["parent"] = t._open[-1] if t._open else None
        t.records.append(self._record)
        t._open.append(self._record)
        return self

    def __exit__(self, *exc):
        assert self._timeline._open.pop() is self._record   # innermost first
        return False

    def set_metadata(self, **args):
        self._record["args"].update(args)


def _engine(family=llama, **extra):
    cfg = (family.LlamaConfig if family is llama else family.GPTConfig).tiny(
        max_seq_len=64)
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        family, cfg, family.init(cfg, jax.random.PRNGKey(0)),
        config=dict({"dtype": "float32", "prefill_bucket": CHUNK,
                     "split_prefill_chunk": CHUNK,
                     "ragged": {"max_tracked_sequences": SLOTS,
                                "max_ragged_batch_size": SLOTS,
                                "memory_config_blocks": 48,
                                "block_size": BLOCK}}, **extra))
    assert not eng.tracer.enabled               # the ring stays off
    eng.tracer._annotate = eng.timeline = _Timeline()
    return eng


@pytest.fixture(scope="module")
def engines():
    """One engine a kind for the module: what a case leaves behind is a
    higher ``seq`` and higher counts, which every case reads as a change."""
    made = {}

    def get(kind):
        if kind not in made:
            made[kind] = {
                "llama": lambda: _engine(),
                "spec": lambda: _engine(speculative={
                    "enabled": True, "max_draft_tokens": 2}),
                # learned positions; and an engine that splits no prompt,
                # whose every admission is a one-shot ``put``
                "gpt": lambda: _engine(gpt),
                "unsplit": lambda: _engine(split_prefill_chunk=0)}[kind]()
        eng = made[kind]
        for uid in list(eng.state.seqs):        # a clean slate, event-free
            eng.finish(uid)
        eng.collect()
        return eng

    return get


def _prompt(n, start=3):
    return [(start + 7 * i) % 50 + 1 for i in range(n)]


def _chain(records, first):
    """Holds the records to the rule and returns ``(launched, read)`` seqs in
    the order they happened: launches number on from ``first`` without a
    gap or a repeat; every wait names a program launched before it and is
    followed by that program's emit, its next sibling, which carries no
    number of its own; no program is read twice."""
    launched = [r["args"]["seq"] for r in records if r["name"] in LAUNCHES]
    assert launched == list(range(first, first + len(launched)))
    reads = [r for r in records if r["name"] in READS]
    waits, emits = reads[0::2], reads[1::2]
    assert [r["name"] for r in waits] == ["engine_wait"] * len(waits)
    assert [r["name"] for r in emits] == ["engine_emit"] * len(emits)
    read = [r["args"]["seq"] for r in waits]
    assert all("seq" not in e["args"] and e["parent"] is w["parent"]
               for w, e in zip(waits, emits))
    assert len(set(read)) == len(read) and read == sorted(read)
    position = {id(r): i for i, r in enumerate(records)}
    launch_at = {r["args"]["seq"]: position[id(r)] for r in records
                 if r["name"] in LAUNCHES}
    for w in waits:
        n = w["args"]["seq"]
        assert n < first or launch_at[n] < position[id(w)], n
    return launched, read


def _ticks(records):
    return [r for r in records if r["name"] == "sched_tick"]


def _under(record, name):
    while record is not None:
        if record["name"] == name:
            return record
        record = record["parent"]
    return None


# --------------------------------------------------------------------------- #
# (a) a sequence number from launch to read
# --------------------------------------------------------------------------- #
def _scheduler_ticks(eng):
    sched = ServingScheduler(eng, SchedulerConfig())
    for n, m in ((20, 4), (5, 3), (11, 5), (3, 2)):   # split and one-shot
        sched.submit(Request(prompt=_prompt(n, n), max_new_tokens=m))
    while sched.pending:
        sched.tick()
    return sched


def _synchronous_steps(eng):
    eng.put(1, _prompt(5))
    eng.put_split(2, _prompt(19))
    for seed in range(4):
        eng.step(seed=seed)


def _lone_prompt(eng):
    eng.put_split(2, _prompt(19))   # nothing decodes beside its chunks
    for seed in range(2):
        eng.step(seed=seed)


def _puts(eng):
    eng.put(1, _prompt(5))
    eng.put_many([(2, _prompt(3)), (3, _prompt(6))])
    eng.launch()
    eng.put(4, _prompt(4))          # reads what is in flight, then prefills
    eng.collect()


def _quanta(eng):
    eng.put(1, _prompt(5))
    eng.put(2, _prompt(6))
    eng.step_many(3)
    eng.launch()
    eng.step_many(2)                # reads what is in flight first


def _preempt_and_resume(eng):
    sched = ServingScheduler(eng, SchedulerConfig())
    handles = [sched.submit(Request(prompt=_prompt(n, n), max_new_tokens=6))
               for n in (5, 12)]
    for _ in range(4):
        sched.tick()
    sched.preempt(handles[0].request.uid)       # a token is in flight
    while sched.pending:
        sched.tick()
    assert all(h.done and len(h.tokens) == 6 for h in handles)
    return sched


def _speculative_steps(eng):
    eng.put(1, [7, 8, 9, 7, 8, 9, 7, 8])        # a prompt that drafts
    eng.put(2, _prompt(6))
    for seed in range(4):
        eng.step(seed=seed)


SCENARIOS = {"scheduler_ticks": ("llama", _scheduler_ticks),
             "synchronous_steps": ("llama", _synchronous_steps),
             "puts": ("llama", _puts),
             "quanta": ("llama", _quanta),
             "preempt_and_resume": ("llama", _preempt_and_resume),
             "speculative_steps": ("spec", _speculative_steps),
             "chunks_apart": ("gpt", _lone_prompt)}


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_seq_runs_from_every_launch_to_its_read(devices8, engines, scenario):
    kind, run = SCENARIOS[scenario]
    eng = engines(kind)
    first, mark = eng._seq + 1, eng.timeline.mark()
    overlapped = eng.overlapped_steps
    sched = run(eng)
    records = eng.timeline.since(mark)
    launched, read = _chain(records, first)
    assert launched and launched[-1] == eng._seq    # one counter an engine
    assert read
    assert {r["name"] for r in records} <= schema.TRACER_SPANS
    if scenario == "synchronous_steps":
        # every program read where it was launched, in order
        assert read == launched and eng.overlapped_steps == overlapped
    if scenario == "speculative_steps":
        assert "spec_verify" in {r["name"] for r in records}
    if scenario == "chunks_apart":
        # a chunk that does not end its prompt is launched and never read
        names = {r["args"]["seq"]: r["name"] for r in records
                 if r["name"] in LAUNCHES}
        unread = sorted(set(launched) - set(read))
        assert unread and {names[n] for n in unread} == {"prefill_chunk"}
    if sched is None:
        return
    for tick in _ticks(records):        # the tick counts the drains under it
        assert tick["args"]["drains"] == sum(
            r["name"] == "engine_drain" and _under(r, "sched_tick") is tick
            for r in records)
    assert set(launched) == set(read)               # nothing is left unread


def test_a_scheduler_tick_reads_the_program_the_tick_before_launched(
        devices8, engines):
    """The chain across ticks: ``decode_step{seq=n}`` in tick t, then
    ``engine_wait{seq=n}`` + ``engine_emit`` in tick t+1, under
    ``sched_step_engine`` after the launch of n+1 - or under the
    ``engine_drain`` of whatever read it first."""
    eng = engines("llama")
    mark = eng.timeline.mark()
    _scheduler_ticks(eng)
    records = eng.timeline.since(mark)
    ticks = _ticks(records)
    tick_of = lambda r: ticks.index(_under(r, "sched_tick"))
    launch = {r["args"]["seq"]: r for r in records if r["name"] in LAUNCHES}
    at = {id(r): i for i, r in enumerate(records)}
    crossed = 0
    for w in (r for r in records if r["name"] == "engine_wait"):
        l = launch[w["args"]["seq"]]
        if l["name"] == "prefill_batch":
            assert w["parent"] is l             # synchronous: its own child
            continue
        assert w["parent"]["name"] in ("sched_step_engine", "engine_drain")
        if tick_of(w) == tick_of(l) + 1:
            crossed += 1
            # the tick's own collect comes after the tick's launch
            assert all(at[id(r)] < at[id(w)] for r in launch.values()
                       if r["parent"] is w["parent"])
        else:                                   # the run's last tick reads
            assert tick_of(w) == tick_of(l)     # its own: nothing is ahead
    assert crossed >= 4


# --------------------------------------------------------------------------- #
# (b) every drain a span with its cause
# --------------------------------------------------------------------------- #
def _in_flight(eng, uids=(1, 2)):
    """Sequences decoding and ONE program launched, unread."""
    for uid in uids:
        eng.put(uid, _prompt(4 + uid, uid))
    assert eng.launch() == 1 and eng.in_flight == 1


def _scheduler_in_flight(eng):
    sched = ServingScheduler(eng, SchedulerConfig())
    handles = [sched.submit(Request(prompt=_prompt(n, n), max_new_tokens=8))
               for n in (5, 6)]
    for _ in range(3):
        sched.tick()
    assert eng.in_flight == 1
    return sched, [h.request.uid for h in handles]


def _spec(eng, flying):
    eng.put(1, _prompt(6))          # no n-gram repeats: nothing is drafted,
    if flying:                      # so a step is the plain decode, launched
        eng.launch()
        assert eng.in_flight == 1
    eng.launch()                    # a speculative step reads history


def _site(call):
    def run(eng, flying):
        if flying:
            _in_flight(eng)
        else:
            eng.put(1, _prompt(5))
            eng.put(2, _prompt(6, 2))
        call(eng)
    return run


def _sched_site(call):
    def run(eng, flying):
        sched, uids = _scheduler_in_flight(eng)
        if not flying:      # the tick's ordinary read lands it first
            for uid, toks in sched._harvest(eng.collect()).items():
                sched._early.setdefault(uid, []).extend(toks)
        call(sched, uids)
    return run


# cause -> (engine kind, how its call site is reached)
SITES = {
    "put": ("llama", _site(lambda e: e.put(5, _prompt(4)))),
    "spec": ("spec", _spec),
    "quantum": ("llama", _site(lambda e: e.step_many(2))),
    "finish": ("llama", _site(lambda e: e.finish(1))),
    "park": ("llama", _site(lambda e: e.park(1))),
    "fork": ("llama", _site(lambda e: e.fork(1, 7))),
    "prefix_hash": ("llama", _site(lambda e: e.kv_chain_hashes(1))),
    "export": ("llama", _site(lambda e: e.export_kv_blocks(1))),
    "sched_park": ("llama", _sched_site(lambda s, u: s.preempt(u[0]))),
    "sched_evict": ("llama", _sched_site(lambda s, u: s.evict_all())),
    "sched_export": ("llama", _sched_site(lambda s, u: s.export_live(u[0]))),
}


def test_the_sites_are_the_closed_list():
    assert tuple(SITES) == schema.DRAIN_CAUSES
    assert "engine_drain" in schema.TRACER_SPANS


@pytest.mark.parametrize("cause", schema.DRAIN_CAUSES)
def test_a_drain_with_a_program_in_flight_is_one_span_and_one_count(
        devices8, engines, cause):
    kind, reach = SITES[cause]
    eng = engines(kind)
    before, mark = dict(eng.drains), eng.timeline.mark()
    reach(eng, True)
    records = eng.timeline.since(mark)
    drains = [r for r in records if r["name"] == "engine_drain"]
    assert [d["args"] for d in drains] == [{"cause": cause}]
    assert eng.drains == {**before, cause: before[cause] + 1}
    # the reads lie inside it, each program's wait then its emit
    inside = [r for r in records if r["parent"] is drains[0]]
    assert inside and [r["name"] for r in inside] \
        == list(READS) * (len(inside) // 2)
    waits = [r["args"]["seq"] for r in inside if r["name"] == "engine_wait"]
    assert waits == sorted(set(waits))
    # nothing is left unread, but by the step that went on to launch
    assert eng.in_flight == (cause == "spec")


@pytest.mark.parametrize("cause", schema.DRAIN_CAUSES)
def test_a_drain_of_nothing_opens_no_span_and_counts_nothing(
        devices8, engines, cause):
    kind, reach = SITES[cause]
    eng = engines(kind)
    before, mark = dict(eng.drains), eng.timeline.mark()
    reach(eng, False)
    assert not [r for r in eng.timeline.since(mark)
                if r["name"] == "engine_drain"]
    assert eng.drains == before


@pytest.mark.parametrize("cause", ["because", "final_chunk"])
def test_an_unknown_cause_is_refused(devices8, engines, cause):
    """``final_chunk`` went with the program that read its token where it
    was launched (ISSUE 46): a name like any other unknown one."""
    eng = engines("llama")
    _in_flight(eng)
    with pytest.raises(KeyError):
        eng.drain(cause)
    assert eng.in_flight == 1                   # and nothing was read


def test_the_ticks_own_reads_are_no_drain(devices8, engines):
    """``launch`` keeps one program unread and ``collect`` reads it: the
    tick's ordinary reads. ``put_split`` reads nothing and has no cause."""
    eng = engines("llama")
    before, mark = dict(eng.drains), eng.timeline.mark()
    _in_flight(eng)
    eng.put_split(8, _prompt(2 * CHUNK + 1))
    assert eng.in_flight == 1
    for _ in range(4):
        eng.launch()
        eng.collect(ahead=1)
    eng.collect()
    records = eng.timeline.since(mark)
    assert not [r for r in records if r["name"] == "engine_drain"]
    assert eng.drains == before
    _chain(records, min(r["args"]["seq"] for r in records
                        if r["name"] in LAUNCHES))


@pytest.mark.parametrize("kind", ["llama", "gpt", "unsplit"])
def test_last_tick_counts_the_drains_of_its_tick(devices8, engines, kind):
    """Re-stated by ISSUE 37 and ISSUE 46: a short prompt admitted beside a
    program in flight is a one-shot ``put``, whose drain the tick counts,
    only where no prompt is split (``split_prefill_chunk=0``); in every
    family that splits it rides the tick's program and the tick counts no
    drain."""
    eng = engines(kind)
    sched, uids = _scheduler_in_flight(eng)
    put = eng.drains["put"]
    sched.submit(Request(prompt=_prompt(3), max_new_tokens=2))
    sched.tick()
    # the admission's, cause put - where the admission reads at all
    assert sched.last_tick["drains"] == eng.drains["put"] - put \
        == (kind == "unsplit")
    sched.tick()
    assert sched.last_tick["drains"] == 0
    sched.preempt(uids[0])          # between ticks: the next tick's count
    assert eng.drains["sched_park"] >= 1
    sched.tick()
    assert sched.last_tick["drains"] == 0       # a tick counts its own


# --------------------------------------------------------------------------- #
# what it must not cost
# --------------------------------------------------------------------------- #
def test_with_no_ring_and_no_session_nothing_is_recorded(devices8):
    """Defaults-OFF parity (PR 24's pin, with the drains in): a disabled
    tracer's spans are annotations, which record nothing outside a profiler
    session; no ring event, no lifecycle, no timer - drains and all."""
    cfg = llama.LlamaConfig.tiny(max_seq_len=64)
    mesh_lib.set_mesh(None)
    eng = build_engine_v2(
        llama, cfg, llama.init(cfg, jax.random.PRNGKey(0)),
        config={"dtype": "float32", "prefill_bucket": CHUNK,
                "split_prefill_chunk": CHUNK,
                "ragged": {"max_tracked_sequences": SLOTS,
                           "max_ragged_batch_size": SLOTS,
                           "memory_config_blocks": 48, "block_size": BLOCK}})
    assert eng.tracer._annotate is jax.profiler.TraceAnnotation
    sched, uids = _scheduler_in_flight(eng)
    sched.submit(Request(prompt=_prompt(3), max_new_tokens=3))
    sched.tick()
    sched.preempt(uids[0])
    while sched.pending:
        sched.tick()
    # (re-stated by ISSUE 37: the short prompt and the resume beside a
    # program in flight ride the ticks' programs, so the park's drain is
    # the one there is)
    assert eng.drains["sched_park"] == 1 == sum(eng.drains.values())
    assert sched.stats["chunked_admissions"] == 1
    assert not eng.tracer.enabled and len(eng.tracer) == 0
    assert eng._req == {} and all(not v for v in eng._lat.values())
    assert eng._seq > 0 and eng.in_flight == 0
