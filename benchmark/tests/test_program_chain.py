"""A launched program as one chain and the host's own time a tick
(``harness/program_chain.py`` and the three readers on it: ``host_busy_ms_p50``,
``launch_lead_ms_p50``, ``idle_drain_share``): on hand-built spans and device
intervals - a tick that overlaps, a tick that drains, a window's edge that
cuts a chain in two, a gap that must give nothing - and on the small trace
``record_program_trace_2.py`` recorded on the chip. The older recordings
hold no ``seq``: every new reader reports nothing there.

Times in the hand-built lists are nanoseconds scaled by 1e6, so a "1" reads
as a millisecond in the readers' output.
"""

import json
import os
import types

import pytest

from benchmark.harness import manifest
from benchmark.harness import program_chain as pc
from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.harness.program_spans import Span
from benchmark.harness.trace import Op, Trace

HERE = os.path.dirname(__file__)
RECORDED = os.path.join(HERE, "recorded_program_2.xplane.pb")
OLDER = [os.path.join(HERE, "recorded_program_1.xplane.pb"),
         os.path.join(HERE, "recorded_1.xplane.pb")]
MS = 1e6
LAUNCHES = ["decode_step", "prefill_chunk"]


def span(name, a, b, **stats):
    return Span(name, a * MS, b * MS, {k: str(v) for k, v in stats.items()}, 1)


def overlapped_tick(t0, seq, **more):
    """20 ms: launches ``seq``, then reads ``seq - 1`` (10 ms blocked)."""
    return [
        span("sched_tick", t0, t0 + 20, drains=0, **more),
        span("sched_expire", t0, t0 + 0.5),
        span("sched_admit", t0 + 0.5, t0 + 1.5),
        span("sched_preempt_guard", t0 + 1.5, t0 + 2),
        span("sched_step_engine", t0 + 2, t0 + 17),
        span("decode_step", t0 + 2.5, t0 + 5.5, seq=seq, overlapped=1),
        span("engine_prep", t0 + 2.5, t0 + 3.5),
        span("engine_dispatch", t0 + 3.5, t0 + 5.5),
        span("engine_wait", t0 + 6, t0 + 16, seq=seq - 1),
        span("engine_emit", t0 + 16, t0 + 17),
        span("sched_harvest", t0 + 17, t0 + 18),
        span("sched_retire", t0 + 18, t0 + 19.5),
    ]


def draining_tick(t0, seq):
    """30 ms: its admission reads ``seq - 1`` (a drain, cause ``put``), runs
    a one-shot prefill as ``seq`` and reads it; the launch of ``seq + 1``
    then finds nothing in flight and nothing is left to collect."""
    return [
        span("sched_tick", t0, t0 + 30, drains=1),
        span("sched_expire", t0, t0 + 0.5),
        span("sched_admit", t0 + 0.5, t0 + 22),
        span("engine_drain", t0 + 1, t0 + 8, cause="put"),
        span("engine_wait", t0 + 1, t0 + 7, seq=seq - 1),
        span("engine_emit", t0 + 7, t0 + 8),
        span("prefill_batch", t0 + 9, t0 + 21, seq=seq),
        span("engine_prep", t0 + 9, t0 + 10),
        span("engine_dispatch", t0 + 10, t0 + 12),
        span("engine_wait", t0 + 12, t0 + 20, seq=seq),
        span("engine_emit", t0 + 20, t0 + 21),
        span("sched_preempt_guard", t0 + 22, t0 + 22.5),
        span("sched_step_engine", t0 + 22.5, t0 + 27),
        span("decode_step", t0 + 23, t0 + 26, seq=seq + 1, overlapped=0),
        span("engine_prep", t0 + 23, t0 + 24),
        span("engine_dispatch", t0 + 24, t0 + 26),
        span("sched_harvest", t0 + 27, t0 + 28),
        span("sched_retire", t0 + 28, t0 + 29.5),
    ]


def timeline():
    """Four ticks inside a window of 0..100 ms. The session opens with
    program 4 in flight (launched before it): tick 1 launches 5 and reads
    4, tick 2 launches 6 and reads 5, tick 3 drains 6, prefills as 7 and
    launches 8 onto an idle device, tick 4 launches 9 and reads 8; program
    9 is still running when the session ends. A run ends where the wait
    that reads it does, and the next starts there: the device has one
    queue. Runs of ``jit_decode``: 4 (cut at its head), 5, 6, 8, 9 (cut at
    its tail); ``jit_prefill`` ran 7."""
    spans = ps.link(overlapped_tick(0, 5) + overlapped_tick(20, 6)
                    + draining_tick(40, 7) + overlapped_tick(70, 9))
    runs = [("jit_decode(1)", 0.0, 16.0), ("jit_decode(1)", 16.0, 36.0),
            ("jit_decode(1)", 36.0, 47.0), ("jit_prefill(2)", 51.5, 60.0),
            ("jit_decode(1)", 65.5, 86.0), ("jit_decode(1)", 86.0, 99.0)]
    modules = [(n, a * MS, b * MS) for n, a, b in runs]
    ops = [Op("fusion.1", a, b, "xla") for _, a, b in modules]
    trace = Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules},
                  [("window", 0.0, 100 * MS)])
    return spans, trace


class _Cell:
    name = "hand-built"
    role = {}


def ctx_of(spans, trace):
    return {"cell": _Cell(), "trace": trace,
            "program_spans": ps.Program(spans, {})}


def read(ctx, reader, **params):
    return manifest.reader(reader).read(ctx, **params)


def test_host_groups_are_the_idle_groups_with_the_wait_apart():
    assert set(pc.HOST_GROUPS) == set(ps.IDLE_GROUPS) | {pc.WAIT}
    flat = lambda g: sorted(n for names in g.values() for n in names)
    assert flat(pc.HOST_GROUPS) == flat(ps.IDLE_GROUPS)
    assert "engine_wait" not in pc.HOST_GROUPS["dispatch"]


def test_an_overlapped_ticks_host_time_is_the_tick_less_its_wait():
    spans, trace = timeline()
    rows = pc.host_ms_by_tick(spans, trace.window())
    assert len(rows) == 4
    first = rows[0]
    assert first["tick"] == pytest.approx(20) and first["wait"] == pytest.approx(10)
    assert first["busy"] == pytest.approx(10)
    assert first["admit"] == pytest.approx(0.5 + 1 + 0.5)
    assert first["dispatch"] == pytest.approx(1 + 2)
    assert first["emit"] == pytest.approx(1 + 1 + 1.5)
    # what sched_tick (0.5), sched_step_engine (0.5 + 0.5) and decode_step
    # (nothing here) keep for themselves is in no group
    assert first["admit"] + first["dispatch"] + first["emit"] \
        == pytest.approx(first["busy"] - 1.5)
    for row in rows:                    # tick by tick, exactly
        assert row["busy"] + row["wait"] == pytest.approx(row["tick"])
        assert row["admit"] + row["dispatch"] + row["emit"] <= row["busy"]


def test_a_draining_ticks_reads_and_prefill_count_innermost_first():
    spans, trace = timeline()
    drained = pc.host_ms_by_tick(spans, trace.window())[2]
    assert drained["tick"] == pytest.approx(30)
    assert drained["wait"] == pytest.approx(6 + 8)    # the drain's, the prefill's
    # the one-shot prefill's prep and dispatch are ``dispatch`` though they
    # lie under sched_admit; both emits are ``emit``; the admission keeps its
    # own sliver, the engine_drain span's (0) and prefill_batch's (0)
    assert drained["dispatch"] == pytest.approx((1 + 2) + (1 + 2))
    assert drained["emit"] == pytest.approx(1 + 1 + 1 + 1.5)
    assert drained["admit"] == pytest.approx(0.5 + (0.5 + 1 + 1) + 0.5)


def test_the_readers_report_the_median_tick_in_ms():
    ctx = ctx_of(*timeline())
    assert read(ctx, "host_busy_ms_p50") == pytest.approx(10)   # of 10 10 16 10
    assert read(ctx, "host_busy_ms_p50", group="dispatch") \
        == pytest.approx(3)
    assert read(ctx, "host_busy_ms_p50", group="emit") == pytest.approx(3.5)
    assert read(ctx, "host_busy_ms_p50", group="admit") == pytest.approx(2)
    # a tick the window cuts is no whole tick
    narrow = Trace(ctx["trace"].devices, ctx["trace"].modules,
                   [("window", 10 * MS, 65 * MS)])
    assert read(ctx_of(ctx["program_spans"].spans, narrow),
                "host_busy_ms_p50") == pytest.approx(10)   # tick 2 alone


def test_a_launch_is_paired_with_its_run_through_seq_and_order():
    spans, trace = timeline()
    runs = [(a, b) for n, a, b in trace.modules["/device:TPU:0"]
            if n.startswith("jit_decode")]
    paired, (low, high) = pc.pair_runs(spans, runs, LAUNCHES, 4 * MS)
    # here a read returns the moment its run ends, and program 8 starts 1.5
    # ms after its dispatch did: the skew (0) lies in the band, at its top
    assert (low / MS, high) == (-1.5, 0)
    # program 4's launch lies before the session: its read bounds nothing.
    # 7 is the one-shot prefill: not decode-shaped, and no gap in the count.
    assert {seq: (a / MS, b / MS) for seq, (a, b) in paired.items()} == {
        5: (16.0, 36.0), 6: (36.0, 47.0), 8: (65.5, 86.0), 9: (86.0, 99.0)}
    leads = pc.queue_leads(spans, trace, LAUNCHES, "^jit_decode")
    # 5, 6 and 9 queue behind the program before for the program's time
    # less the host's; 8 follows a drain and starts on an idle device (5.5
    # ms after the one-shot prefill ended) while its dispatch is still
    # returning
    assert {seq: lead for seq, _, lead, _ in leads} \
        == pytest.approx({5: 10.5, 6: 10.5, 8: -0.5, 9: 10.5})
    assert [seq for seq, _, _, idle in leads if idle] == [8]
    # in the device's queue: what a launch reads above the idle one's
    ctx = ctx_of(spans, trace)
    assert read(ctx, "launch_lead_ms_p50", spans=LAUNCHES,
                pattern="^jit_decode") == pytest.approx(10.5 + 0.5)


def test_a_windows_edge_cuts_a_chain_in_two_and_the_rest_still_pair():
    spans, trace = timeline()
    runs = [(a, b) for n, a, b in trace.modules["/device:TPU:0"]
            if n.startswith("jit_decode")]
    # the session ended before program 9 ran: its launch pairs with nothing
    paired, _ = pc.pair_runs(spans, runs[:-1], LAUNCHES, 4 * MS)
    assert sorted(paired) == [5, 6, 8]
    # ... and began after program 4 had started: the trace holds no run of it
    paired, _ = pc.pair_runs(spans, runs[1:], LAUNCHES, 4 * MS)
    assert sorted(paired) == [5, 6, 8, 9]
    # the reader's median is over the launches inside the window
    narrow = Trace(trace.devices, trace.modules, [("window", 41 * MS, 100 * MS)])
    assert read(ctx_of(spans, narrow), "launch_lead_ms_p50", spans=LAUNCHES,
                pattern="^jit_decode") == pytest.approx(5.5)    # 8 and 9
    # the idle launch before the window still says what an idle launch reads
    late = Trace(trace.devices, trace.modules, [("window", 70 * MS, 100 * MS)])
    assert read(ctx_of(spans, late), "launch_lead_ms_p50", spans=LAUNCHES,
                pattern="^jit_decode") == pytest.approx(11.0)   # 9
    # ... and a trace in which no launch found the device idle says nothing
    calm = ps.link(overlapped_tick(0, 5) + overlapped_tick(20, 6))
    assert [idle for *_, idle in pc.queue_leads(
        calm, trace, LAUNCHES, "^jit_decode")] == [False, False]
    assert read(ctx_of(calm, trace), "launch_lead_ms_p50", spans=LAUNCHES,
                pattern="^jit_decode") is None


def test_the_two_clocks_skew_drops_out_of_the_lead():
    """The profiler aligns the two clocks to a millisecond or two, by
    session. No program ends after the wait that read it returned, and none
    starts before its dispatch did: the join finds the band the skew lies
    in, and the lead - a difference of two readings off the same two clocks
    - does not move with it."""
    spans, trace = timeline()
    runs = [(a, b) for n, a, b in trace.modules["/device:TPU:0"]
            if n.startswith("jit_decode")]
    for off in (-2.0, 3.0):         # the device's clock early, late
        moved = [(a + off * MS, b + off * MS) for a, b in runs]
        paired, (low, high) = pc.pair_runs(spans, moved, LAUNCHES, 4 * MS)
        assert low <= -off * MS == pytest.approx(high)
        assert paired[6] == (moved[2][0], moved[2][1])
        modules = [("jit_decode(1)", a, b) for a, b in moved]
        ops = [Op(o.name, o.start + off * MS, o.end + off * MS, o.category)
               for o in trace.devices["/device:TPU:0"]]
        shifted = Trace({"/device:TPU:0": ops}, {"/device:TPU:0": modules},
                        trace.host)
        assert read(ctx_of(spans, shifted), "launch_lead_ms_p50",
                    spans=LAUNCHES, pattern="^jit_decode") \
            == pytest.approx(11.0)
    # further off than the join allows for (here 4 ms; never more than 0.45
    # of a run): nothing, not a pairing one program off
    late = [(a + 12 * MS, b + 12 * MS) for a, b in runs]
    assert pc.pair_runs(spans, late, LAUNCHES, 4 * MS) is None
    assert pc.pair_runs(spans, [(a + 3 * MS, b + 3 * MS) for a, b in runs],
                        LAUNCHES, 1 * MS) is None


def test_where_the_count_disagrees_the_join_gives_nothing_not_a_guess():
    spans, trace = timeline()
    runs = [(a, b) for n, a, b in trace.modules["/device:TPU:0"]
            if n.startswith("jit_decode")]
    # a run the trace lost in the middle (that of 6): under every offset
    # some program would end after it was read
    assert pc.pair_runs(spans, runs[:2] + runs[3:], LAUNCHES, 4 * MS) is None
    # a launch span the trace lost (that of 6)
    lost = ps.link([s for s in timeline()[0]      # link() writes its spans
                    if not (s.name == "decode_step" and s.arg("seq") == 6)])
    assert pc.pair_runs(lost, runs, LAUNCHES, 4 * MS) is None
    # launches of a module the pattern does not name (a family without a
    # mixed call runs its chunks as another program)
    assert read(ctx_of(spans, trace), "launch_lead_ms_p50", spans=LAUNCHES,
                pattern="^jit_chunk_prefill") is None
    # a program that numbers no launch
    bare = ps.link([Span(s.name, s.start, s.end, {}, s.line) for s in spans])
    assert pc.pair_runs(bare, runs, LAUNCHES, 4 * MS) is None
    assert read(ctx_of(bare, trace), "launch_lead_ms_p50", spans=LAUNCHES,
                pattern="^jit_decode") is None


def test_idle_time_inside_the_ticks_that_drained():
    spans, trace = timeline()
    assert pc.drained_ticks(spans) == [(40 * MS, 70 * MS)]
    # idle: 47-51.5 and 60-65.5, both inside tick 3 (40-70): the exposed
    # read and the exposed launch after it; and 99-100, no drain's
    ctx = ctx_of(spans, trace)
    assert read(ctx, "idle_drain_share") == pytest.approx(4.5 + 5.5)
    idle = 100 * tr.idle_share(trace)
    assert idle == pytest.approx(4.5 + 5.5 + 1)
    assert read(ctx, "idle_drain_share") <= idle
    assert read(ctx, "span_arg", span="sched_tick", arg="drains",
                how="share_positive") == pytest.approx(25.0)
    # no tick drained: 0, not nothing ...
    calm = ps.link(overlapped_tick(0, 5) + overlapped_tick(20, 6))
    assert pc.drained_ticks(calm) == []
    assert read(ctx_of(calm, trace), "idle_drain_share") == 0.0
    # ... and a program whose ticks carry no ``drains`` reports nothing
    bare = ps.link([Span(s.name, s.start, s.end, {}, s.line) for s in calm])
    assert pc.drained_ticks(bare) is None
    assert read(ctx_of(bare, trace), "idle_drain_share") is None
    assert read(ctx_of(bare, trace), "span_arg", span="sched_tick",
                arg="drains", how="share_positive") is None


def test_the_manifest_names_the_new_metrics_for_the_four_serve_cells():
    b = manifest.manifest()
    serve = next(m for m in b["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")["workloads"]
    new = {"serve_host_busy_ms_p50": "host_busy_ms_p50",
           "serve_host_dispatch_ms_p50": "host_busy_ms_p50",
           "serve_host_emit_ms_p50": "host_busy_ms_p50",
           "serve_host_admit_ms_p50": "host_busy_ms_p50",
           "serve_launch_lead_ms_p50": "launch_lead_ms_p50",
           "serve_idle_drain_share": "idle_drain_share",
           "serve_drain_tick_share": "span_arg"}
    by = {m["name"]: m for m in b["per_layer"]}
    assert [m["name"] for m in b["per_layer"]][-len(new):] == list(new)
    for name, reader in new.items():
        assert manifest.metric_definition(name)["reader"] == reader
        assert by[name]["source"] == "program_span"
        assert by[name]["layer"] == "Serve engine + scheduler"
        assert by[name]["workloads"] == serve


# -- the recordings --------------------------------------------------------- #
def _recorded_ctx(monkeypatch, path):
    trace = tr.load(path)
    monkeypatch.setattr(tr, "find_xplane", lambda folder: path)
    cell = types.SimpleNamespace(name="recorded", role={}, model={})
    return {"cell": cell, "trace": trace}


NEW = [("host_busy_ms_p50", {}),
       ("launch_lead_ms_p50", {"spans": LAUNCHES, "pattern": "^jit_decode"}),
       ("idle_drain_share", {}),
       ("span_arg", {"span": "sched_tick", "arg": "drains",
                     "how": "share_positive"})]


@pytest.mark.parametrize("reader,params", NEW[1:])
@pytest.mark.parametrize("path", OLDER)
def test_a_program_without_seq_or_drains_gives_the_new_readers_nothing(
        monkeypatch, path, reader, params):
    ctx = _recorded_ctx(monkeypatch, path)
    assert read(ctx, reader, **params) is None
    assert read({"cell": ctx["cell"], "trace": None}, reader, **params) is None


def test_the_host_split_needs_nothing_new_of_the_program(monkeypatch):
    """``recorded_program_1`` (PR 24): three hand-made ticks, one program
    read inside each - the tick is all host but its wait."""
    ctx = _recorded_ctx(monkeypatch, OLDER[0])
    spans = ps.load(ctx).spans
    rows = pc.host_ms_by_tick(spans, ctx["trace"].window())
    assert len(rows) == 3
    for row in rows:
        assert row["busy"] + row["wait"] == pytest.approx(row["tick"])
        assert 0.5 <= row["admit"] < 1.5 and 0.6 <= row["emit"] < 2.6
    assert read(ctx, "host_busy_ms_p50") == pytest.approx(
        sorted(r["busy"] for r in rows)[1])
    assert read(_recorded_ctx(monkeypatch, OLDER[1]),
                "host_busy_ms_p50") is None         # no program span at all


def test_the_new_readers_on_the_new_recording(monkeypatch):
    """``recorded_program_2`` (``record_program_trace_2.py``): six hand-made
    ticks through the program's own ``Tracer``, one program in flight; the
    fourth tick's admission drains (cause ``put``) and runs a one-shot
    prefill, so its launch finds nothing in flight. What the host did in a
    tick is sleeps of known length; the program runs ~3 ms."""
    with open(os.path.join(HERE, "recorded_program_2.json")) as f:
        made = json.load(f)
    ctx = _recorded_ctx(monkeypatch, RECORDED)
    spans = ps.load(ctx).spans
    window = ctx["trace"].window()
    ticks = ps.named(spans, "sched_tick", window)
    assert [t.arg("drains") for t in ticks] == made["drains"]
    rows = pc.host_ms_by_tick(spans, window)
    sleeps = made["sleep_ms"]
    for row, drained in zip(rows, made["drains"]):
        assert row["busy"] + row["wait"] == pytest.approx(row["tick"])
        assert row["admit"] + row["dispatch"] + row["emit"] <= row["busy"]
        # a sleep on that machine overshoots by 0.1-0.9 ms
        n = 2 if drained else 1         # the one-shot prefill's prep, emit
        assert sleeps["admit"] <= row["admit"] < sleeps["admit"] + 1.5
        assert n * sleeps["prep"] <= row["dispatch"] \
            < n * (sleeps["prep"] + 1.5)
    assert read(ctx, "host_busy_ms_p50") == pytest.approx(
        (sorted(r["busy"] for r in rows)[2]
         + sorted(r["busy"] for r in rows)[3]) / 2)
    parts = [read(ctx, "host_busy_ms_p50", group=g)
             for g in ("admit", "dispatch", "emit")]
    assert sum(parts) <= read(ctx, "host_busy_ms_p50") + 1e-9
    # the chain: every wait's seq has its launch (the first apart, launched
    # before the window) and every decode-shaped launch its run
    runs = [(a, b) for n, a, b in next(iter(ctx["trace"].modules.values()))
            if n.startswith("jit_decode")]
    paired, (low, high) = pc.pair_runs(spans, runs, LAUNCHES, 4 * MS)
    launched = [seq for seq, _, _ in pc.launches(spans, LAUNCHES)]
    assert launched == made["decode_seqs"] and sorted(paired) == launched
    # in this session the device's clock is early: a run seems to start
    # before its dispatch did and every read to return a millisecond or two
    # after its program ended - the skew lies between the two
    assert 0.5 < low / MS < high / MS < 2.5
    leads = pc.queue_leads(spans, ctx["trace"], LAUNCHES, "^jit_decode")
    # 2 and 6 were launched onto an idle device (the session's start, the
    # drain) and run as their dispatch returns; the others queue behind the
    # program before for the program's ~7 ms less the host's tick
    assert [seq for seq, _, _, idle in leads if idle] == [2, 6]
    base = sum(lead for _, _, lead, idle in leads if idle) / 2
    queued = {seq: lead - base for seq, _, lead, _ in leads}
    assert all(abs(queued[n]) < 0.5 for n in (2, 6))
    assert all(0.5 < queued[n] < 4.0 for n in (3, 4, 7, 8))
    assert read(ctx, "launch_lead_ms_p50", spans=LAUNCHES,
                pattern="^jit_decode") == pytest.approx(
        sum(sorted(queued.values())[2:4]) / 2)
    drains = [s for s in spans if s.name == "engine_drain"]
    assert [s.stats["cause"] for s in drains] == ["put"]
    assert read(ctx, "span_arg", span="sched_tick", arg="drains",
                how="share_positive") == pytest.approx(100 / 6)
    share = read(ctx, "idle_drain_share")
    assert 0.0 < share <= 100 * tr.idle_share(ctx["trace"]) + 1e-9
