#!/usr/bin/env python3
"""Runs one cell several times in one call, each run a new process, and
gathers what came out: how the builder of a benchmark PR measures spreads
on the chip (``chiprun --chips N -- python3 benchmark/tools/sets.py ...``).

    sets.py --workload W --seeds 11,12,13 --seconds 30 [--trace 0|1] [--tag T]

This parent never imports JAX, so each child gets the chip. Every run's
output goes to ``chiprun_out/<tag>/<workload>/run<i>.log`` and its series
beside it; the last lines, and the quartile spread of each metric over the
runs, are printed at the end.
"""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark.harness import stats  # noqa: E402  (no JAX in there)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--tag", default="sets")
    ap.add_argument("--root", default=ROOT,
                    help="checkout to run from (a git-archive copy)")
    args = ap.parse_args()
    args.root = os.path.abspath(args.root)
    out = os.path.join(ROOT, "chiprun_out", args.tag, args.workload)
    os.makedirs(out, exist_ok=True)
    results = []
    for i, seed in enumerate(args.seeds.split(",")):
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, os.path.join(args.root, "benchmark", "run.py"),
             "--workload", args.workload, "--seed", seed,
             "--seconds", str(args.seconds), "--trace", str(args.trace)],
            cwd=args.root, capture_output=True, text=True)
        stem = os.path.join(out, f"run{i}_t{args.trace}")
        with open(stem + ".log", "w") as f:
            f.write(proc.stdout + "\n--- stderr ---\n" + proc.stderr[-20000:])
        series = os.path.join(args.root, "benchmark_out", args.workload,
                              "series.json")
        if os.path.exists(series):
            shutil.copy(series, stem + ".series.json")
        lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("{")]
        print(f"== run {i} seed {seed} rc {proc.returncode} "
              f"wall {time.time() - t0:.1f}s")
        for ln in lines[-4:]:
            print(ln[:3000])
        if proc.returncode:
            print(proc.stderr[-3000:])
            continue
        results.append(json.loads(lines[-1]))
    by = {}
    for res in results:
        for k, v in res.get("metrics", {}).items():
            by.setdefault(k, []).append(v["value"])
    for k, vs in by.items():
        line = {"metric": k, "values": vs, "median": statistics.median(vs)}
        if len(vs) >= 2:
            line["quartile_spread"] = stats.quartile_spread(vs)
            line["range_share"] = (max(vs) - min(vs)) / statistics.median(vs)
        print(json.dumps(line))
    return 0 if len(results) == len(args.seeds.split(",")) else 1


if __name__ == "__main__":
    sys.exit(main())
