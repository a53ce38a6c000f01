#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
``--trace 1``, ``breakdown``). With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics. Earlier lines
are JSON objects too and say what the run saw on the way. Without the chips
the cell asks for it exits non-zero and prints no result; ``--rehearse``
runs the same path at the configuration's tiny rehearsal size on whatever
device JAX has and prints no ``metrics`` key, so that nothing can take a
rehearsal for a measurement. See ``benchmark/README.md``.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes, any device, no 'metrics' in the result")
    return ap.parse_args(argv)


def per_layer_metrics(cell, record, device, peaks, trace, programs) -> dict:
    from benchmark.harness import manifest

    ctx = {"cell": cell, "trace": trace, "peaks": peaks, "device": device,
           "programs": programs, **record.context}
    out = {}
    for m in cell.metrics("per_layer"):
        definition = manifest.metric_definition(m["name"])
        value = manifest.reader(definition["reader"]).read(
            ctx, **definition.get("params", {}))
        if value is not None:     # a reader that finds nothing reports none
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    from benchmark.harness import device as dev
    from benchmark.harness import manifest, peaks
    from benchmark.harness import trace as tr
    from benchmark.traffic_kinds.common import Run

    import deepspeed_tpu
    if not os.path.abspath(deepspeed_tpu.__file__).startswith(ROOT + os.sep):
        print(f"deepspeed_tpu was imported from {deepspeed_tpu.__file__}, "
              f"not from this checkout", file=sys.stderr)
        return 2
    cell = manifest.Cell(args.workload, rehearsal=args.rehearse)
    try:
        dev.compile_cache_dir()
        device = dev.check(cell.chips, args.rehearse)
    except dev.Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    out_dir = os.path.join(ROOT, "benchmark_out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), out_dir=out_dir, t_process=T_PROCESS,
              device=device)
    programs = dev.record_compiled()
    record = cell.kind.run(run)
    device["memory_peak_bytes"] = dev.memory_peak_bytes(programs)
    t_open = T_PROCESS + record.end_to_end["setup_s"]
    before = [s for s in run.spans.records if s[2] <= t_open]
    by_span = {}
    for name, a, b in before:
        by_span[name] = by_span.get(name, 0.0) + (b - a)
    run.say(phase="setup", to_first_span_s=before[0][1] - T_PROCESS,
            seconds_by_span=by_span)
    result = {"correct": record.correct, "attempted": record.attempted,
              "failed": record.failed}
    if record.why_not_correct:
        run.say(phase="correct", why_not=record.why_not_correct)
    end_to_end = {m["name"]: {"value": record.end_to_end[m["name"]],
                              "unit": m["unit"]}
                  for m in cell.metrics("end_to_end")}
    if args.trace:
        run.say(phase="end_to_end_of_traced_run", **end_to_end)
        trace = tr.load(tr.find_xplane(record.trace_dir))
        window = trace.window()
        device["busy_s"] = tr.busy_seconds(trace, window)
        device["window_s"] = (window[1] - window[0]) / 1e9
        run.say(phase="trace", clock_skew_ns=tr.clock_skew(trace),
                programs=tr.module_summary(trace, window))
        known = None if args.rehearse else peaks.peaks_for(device["kind"])
        metrics = per_layer_metrics(cell, record, device, known, trace,
                                    programs)
        result["breakdown"] = {
            "device_ops": tr.top_ops(trace, 10, window),
            "idle_gaps": tr.idle_gaps_by_span(trace, window, 10)}
        shutil.rmtree(record.trace_dir, ignore_errors=True)
    else:
        metrics = end_to_end
    result["rehearsal" if args.rehearse else "metrics"] = metrics
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
