#!/usr/bin/env python3
"""One traced run of a serving cell, ``span_report.py``'s lines, and then
what the chain of a launched program says (ISSUE 36; JSON lines):

    python3 benchmark/tools/chain_report.py --workload <name> --seed <n> --seconds <s>

``chain``: the ``seq`` of every launch span in the trace by name; the
``engine_wait`` spans inside the window whose ``seq`` has no launch span in
the trace (the window's first read is of the program launched before the
session opened: its launch cannot be there); the drains of the window by
``cause``, and those whose cause the program's closed list
(``telemetry.schema.DRAIN_CAUSES``) does not hold. ``ticks``: for each
``sched_tick`` of the window its duration, the host's busy time and its
parts (``program_chain.host_ms_by_tick``) and ``drains``. ``leads``:
``{seq: ms}`` from the end of a decode-shaped launch's ``engine_dispatch``
to the start of its run on the device, less the median of what the
launches that found the device idle (``idle_launches``) read there; beside
them the band in which the skew of the device's clock lies.
``idle``: the device's idle seconds in the window, those inside the ticks
that drained, and those by the cause of the tick's first drain.
PERF.md section 5's host split is written from these lines; the metrics
themselves come from ``run.py``."""

from __future__ import annotations

import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

LAUNCH_SPANS = ("decode_step", "prefill_chunk", "prefill_batch",
                "decode_quantum", "spec_verify")


def report(cell_name: str) -> None:
    from benchmark.harness import manifest
    from benchmark.harness import program_chain as pc
    from benchmark.harness import program_spans as ps
    from benchmark.harness import trace as tr
    from deepspeed_tpu.telemetry import schema

    path = tr.find_xplane(os.path.join(ROOT, "benchmark_out", cell_name,
                                       "trace"))
    trace = tr.load(path)
    program = ps.read(path)
    say = lambda **kw: print(json.dumps(kw), flush=True)
    if program is None:
        say(phase="chain_report", chain=None)
        return
    spans, window = program.spans, trace.window()
    inside = lambda s: s.start >= window[0] and s.end <= window[1]
    launched = {name: sorted(int(s.arg("seq")) for s in spans
                             if s.name == name and s.arg("seq") is not None)
                for name in LAUNCH_SPANS}
    known = {n for seqs in launched.values() for n in seqs}
    waits = [s for s in spans if s.name == "engine_wait" and inside(s)]
    causes = {}
    for s in spans:
        if s.name == pc.DRAIN and inside(s):
            causes[s.stats.get("cause")] = causes.get(s.stats.get("cause"), 0) + 1
    closed = getattr(schema, "DRAIN_CAUSES", ())
    say(phase="chain_report", launched={k: v for k, v in launched.items() if v},
        waits_in_window=len(waits),
        waits_without_seq=sum(s.arg("seq") is None for s in waits),
        waits_without_launch=sorted(
            int(s.arg("seq")) for s in waits
            if s.arg("seq") is not None and int(s.arg("seq")) not in known),
        drains_by_cause=causes,
        causes_outside_the_closed_list=sorted(
            str(c) for c in causes if c not in closed))
    ticks = ps.named(spans, pc.TICK, window)
    say(phase="chain_report", ticks=[
        {**{k: round(v, 4) for k, v in row.items()},
         "drains": t.arg("drains")}
        for t, row in zip(ticks, pc.host_ms_by_tick(spans, window))])
    params = dict(manifest.metric_definition(
        "serve_launch_lead_ms_p50")["params"])
    names, pattern = params.pop("spans"), params.pop("pattern")
    joined = pc.join(spans, trace, names, pattern,
                     params.get("slack_ms", 4.0))
    leads = pc.queue_leads(spans, trace, names, pattern, **params) or []
    idle = {seq: lead for seq, _, lead, was_idle in leads if was_idle}
    base = statistics.median(idle.values()) if idle else None
    say(phase="chain_report",
        device_clock_skew_band_ms=joined and [x / 1e6 for x in joined[1]],
        idle_launches={seq: round(lead, 4) for seq, lead in idle.items()},
        leads=None if base is None else {
            seq: round(lead - base, 4) for seq, launch, lead, _ in leads
            if inside(launch)})
    drained = pc.drained_ticks(spans)
    first_cause = {}
    owner = pc.tick_of(spans)
    for i, s in enumerate(spans):
        if s.name == pc.DRAIN and owner[i] is not None:
            first_cause.setdefault(owner[i], s.stats.get("cause"))
    for plane, ops in trace.devices.items():
        idle = tr.gaps(tr.busy_intervals(ops, window), window)
        under = lambda ivs: tr.total(tr.intersect(
            idle, tr.union(tr.clip(ivs, window)))) / 1e9
        by_cause = {}
        for i, cause in first_cause.items():
            by_cause[cause] = by_cause.get(cause, 0.0) \
                + under([(spans[i].start, spans[i].end)])
        say(phase="chain_report", plane=plane, window_s=(window[1] - window[0]) / 1e9,
            idle_s=tr.total(idle) / 1e9,
            idle_in_drained_ticks_s=None if drained is None else under(drained),
            idle_by_first_cause_s=by_cause)


def main(argv=None) -> int:
    from benchmark.tools import span_report

    spans = span_report.report

    def both(cell_name, programs):
        spans(cell_name, programs)
        report(cell_name)

    span_report.report = both       # one traced run, both reports
    try:
        return span_report.main(argv)
    finally:
        span_report.report = spans


if __name__ == "__main__":
    sys.exit(main())
