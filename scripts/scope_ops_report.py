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
PR is written from (PERF.md section 5, item 9, was). With ``--span NAME
--args a,b,..`` one more line, ``phase: "span_args"``: those arguments of
every ``NAME`` span in the window that carries the first of them, a list an
argument (``--span decode_step --args
chunk_attn_tiles_live,chunk_attn_tiles_grid,chunk_attn_kv_tile``: whether
the chunk's walk fetched its own pages - the two counts are then equal -
and at which tile).
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
    ap.add_argument("--span")
    ap.add_argument("--args", default="")
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
        keys = [k for k in mine.args.split(",") if k]
        if mine.span and keys:
            spans = [s for s in ps.named(program.spans, mine.span, window)
                     if s.arg(keys[0]) is not None]
            print(json.dumps({"phase": "span_args", "span": mine.span,
                              "spans": len(spans),
                              **{k: [s.arg(k) for s in spans] for k in keys}}),
                  flush=True)
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
