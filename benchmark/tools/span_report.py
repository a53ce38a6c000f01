#!/usr/bin/env python3
"""One traced run of a cell, and what the program's own spans and names say
about it, printed as JSON lines after the run's own output:

    python3 benchmark/tools/span_report.py --workload <name> --seed <n> --seconds <s>

``spans``: for each span name inside the traced window the count, the
median duration and the median self time, in ms. ``idle``: the device's idle
seconds in the window by span group (``program_spans.IDLE_GROUPS``, with
``engine_wait`` and ``unattributed`` apart; under ``engine_wait`` split at
the device's first operation inside the span) and ``clock_check``, which
says how far that split can be trusted. ``sched_tick``: what each traced tick did by the
program's own count, beside the series the benchmark derives from outside.
``scopes``: device seconds by named
scope. ``ops``: the twelve operations with the most device time, each with
the ``op_name`` the trace carries for it. PERF.md section 5 is written from
these lines; the metrics themselves come from ``run.py``.
"""

from __future__ import annotations

import json
import os
import shutil
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def apart():
    """``program_spans.IDLE_GROUPS`` with ``engine_wait`` as a group of its
    own (the metrics count it with ``dispatch``: see there)."""
    from benchmark.harness import program_spans as ps

    groups = dict(ps.IDLE_GROUPS)
    groups["dispatch"] = tuple(n for n in groups["dispatch"]
                               if n != "engine_wait")
    groups["wait"] = ("engine_wait",)
    return groups


def wait_idle(idle, spans, busy):
    """Idle seconds under ``engine_wait`` before the device's first busy
    moment inside the span (the launch) and after its last (the read-back)."""
    from benchmark.harness import trace as tr

    head = tail = 0.0
    for s in spans:
        if s.name != "engine_wait":
            continue
        inside = tr.intersect([(s.start, s.end)], busy)
        gaps = tr.intersect([(s.start, s.end)], idle)
        first = inside[0][0] if inside else s.end
        head += tr.total(tr.clip(gaps, (s.start, first)))
        tail += tr.total(tr.clip(gaps, (first, s.end)))
    return {"before_first_op_s": head / 1e9, "after_s": tail / 1e9}


def clock_check(trace, spans):
    """How far the profiler's device clock may be off the host's, in ms. A
    wait drains the device, so what the device does next cannot start before
    the next ``engine_dispatch`` does (``launch``: first busy moment after a
    drained wait, less that dispatch's start; negative = the device clock is
    early by at least that), and the device cannot still be busy when the
    wait returns (``back``: the wait's end less the last busy moment before
    it). ``[least, median]`` of each."""
    import bisect

    from benchmark.harness import trace as tr

    ops = next(iter(trace.devices.values()), [])
    busy = tr.busy_intervals(ops, (float("-inf"), float("inf")))
    ends = [b for _, b in busy]
    launch, back = [], []
    drained = None          # end of the last wait, until a dispatch follows
    for s in sorted(spans, key=lambda s: s.start):
        if s.name == "engine_wait":
            i = bisect.bisect_right(ends, s.end) - 1
            if i >= 0:
                back.append((s.end - ends[i]) / 1e6)
            drained = s.end
        elif s.name == "engine_dispatch" and drained is not None:
            i = bisect.bisect_right(ends, drained)
            if i < len(busy):
                launch.append((busy[i][0] - s.start) / 1e6)
            drained = None
    q = lambda v: [min(v), statistics.median(v)] if v else None
    return {"launch_ms": q(launch), "back_ms": q(back)}


def report(cell_name: str, programs) -> None:
    from benchmark.harness import program_spans as ps
    from benchmark.harness import trace as tr

    path = tr.find_xplane(os.path.join(ROOT, "benchmark_out", cell_name,
                                       "trace"))
    trace = tr.load(path)
    program = ps.read(path, trace, programs)
    window = trace.window()
    say = lambda **kw: print(json.dumps(kw), flush=True)
    if program is None:
        say(phase="span_report", spans=None)
        return
    by_name = {}
    for s in program.spans:
        if s.start >= window[0] and s.end <= window[1]:
            by_name.setdefault(s.name, []).append(s)
    say(phase="span_report", window_s=(window[1] - window[0]) / 1e9,
        spans={n: [len(v), statistics.median(s.seconds for s in v) * 1e3,
                   statistics.median(s.self_ns for s in v) / 1e6]
               for n, v in sorted(by_name.items())})
    say(phase="span_report", clock_check=clock_check(trace, program.spans))
    ticks = ps.named(program.spans, "sched_tick", window)
    series = os.path.join(ROOT, "benchmark_out", cell_name, "series.json")
    if ticks and os.path.exists(series):
        with open(series) as f:
            seen = json.load(f)
        a, b = seen["traced"]
        say(phase="span_report", traced_ticks=[a, b],
            sched_tick={k: [t.arg(k) for t in ticks] for k in
                        ("prefill_tokens", "decode_seqs", "kv_tokens",
                         "tokens_out")},
            benchmark_series={k: seen[k][a:b] for k in
                              ("prefilled", "decoding", "kv_tokens",
                               "generated")})
    for plane, ops in trace.devices.items():
        busy = tr.busy_intervals(ops, window)
        idle = tr.gaps(busy, window)
        say(phase="span_report", plane=plane, idle_s=tr.total(idle) / 1e9,
            idle_under_engine_wait=wait_idle(idle, program.spans, busy),
            idle={g: v / 1e9 for g, v in
                  ps.split_idle(idle, program.spans, apart()).items()},
            scopes=ps.scope_seconds(program.ops.get(plane, []), window))
        named = {}
        for op, op_name in program.ops.get(plane, []):
            named.setdefault(op.label or op.name, op_name)
        say(phase="span_report", plane=plane,
            ops=[[label, seconds, named.get(label, "")] for label, seconds
                 in sorted(tr.self_times(ops, window).items(),
                           key=lambda kv: -kv[1])[:12]])


def main(argv=None) -> int:
    from benchmark import run
    from benchmark.harness import device as dev

    args = run.parse((argv or sys.argv[1:]) + ["--trace", "1"])
    programs = []
    record = dev.record_compiled

    def record_and_keep():
        programs.append(record())
        return programs[0]

    remove, shutil.rmtree = shutil.rmtree, lambda *a, **k: None
    dev.record_compiled = record_and_keep
    try:   # keep the trace and the programs until the report has read them
        code = run.main((argv or sys.argv[1:]) + ["--trace", "1"])
    finally:
        shutil.rmtree, dev.record_compiled = remove, record
    if code == 0:
        report(args.workload, programs[0])
    shutil.rmtree(os.path.join(ROOT, "benchmark_out", args.workload, "trace"),
                  ignore_errors=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
