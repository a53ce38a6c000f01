#!/usr/bin/env python3
"""One traced run of a benchmark cell, then EVERY device operation under one
named scope of its programs:

    python3 scripts/scope_ops_report.py --scope delta_chunk \\
        --workload <cell> --seed <n> --seconds 40

``benchmark/tools/span_report.py`` runs the cell (all of its lines come
first: the result, the spans, the twelve dearest operations of the whole
window); one more JSON line follows, ``phase: "scope_ops"``: the traced
ticks, the scope's device seconds in the window (each operation its own
time only, as ``program_spans.scope_seconds`` counts) and the operations
under it, dearest first, as ``[label, seconds, calls]`` - the list a kernel
PR is written from (PERF.md section 5, item 9, was).
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
OTHER = "(outside the scope)"


def main(argv) -> int:
    import argparse
    import dataclasses

    from benchmark.harness import program_spans as ps
    from benchmark.harness import trace as tr
    from benchmark.tools import span_report

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--scope", required=True)
    mine, argv = ap.parse_known_args(argv)
    report = span_report.report

    def report_and_list(cell_name, programs):
        report(cell_name, programs)
        path = tr.find_xplane(os.path.join(ROOT, "benchmark_out", cell_name,
                                           "trace"))
        trace = tr.load(path)
        program = ps.read(path, trace, programs)
        if program is None:
            print(json.dumps({"phase": "scope_ops", "ops": None}))
            return
        window = trace.window()
        ticks = len(ps.named(program.spans, "sched_tick", window))
        for plane, ops in program.ops.items():
            under = [dataclasses.replace(op, label=op.label or op.name)
                     if f"/{mine.scope}/" in f"/{op_name}/"
                     else dataclasses.replace(op, label=OTHER)
                     for op, op_name in ops]
            calls = {}
            for op in under:
                if op.label != OTHER and window[0] <= op.start <= window[1]:
                    calls[op.label] = calls.get(op.label, 0) + 1
            seconds = tr.self_times(under, window)
            seconds.pop(OTHER, None)
            print(json.dumps({
                "phase": "scope_ops", "plane": plane, "scope": mine.scope,
                "sched_ticks": ticks, "scope_s": sum(seconds.values()),
                "ops": [[label, s, calls.get(label, 0)] for label, s in
                        sorted(seconds.items(), key=lambda kv: -kv[1])]}),
                flush=True)

    span_report.report = report_and_list
    try:
        return span_report.main(argv)
    finally:
        span_report.report = report


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
