#!/usr/bin/env python3
"""One run of a benchmark cell, then where its set-up went.

    python3 scripts/setup_account_report.py --workload <cell> --seed <n> \\
        --seconds 40 --trace <0|1> [--top 3] [--rehearse]

Runs ``benchmark/run.py`` with the same arguments in this process (all of
its lines come first) and prints one more JSON line, ``phase:
"setup_account"``: the process's compile account
(``deepspeed_tpu/telemetry/compile.py CompileAccount``) over the events that
ended before the window opened - the totals the five ``setup_*`` metrics
read - and its ``--top`` costliest functions (``by_program``), with the
compile monitor's own analysis by program. The benchmark's result is the
line before it. On a commit without the account the line says so.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main(argv) -> int:
    import argparse

    import run as bench
    from benchmark.readers.setup_account import COMPLETIONS

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--top", type=int, default=3)
    mine, argv = ap.parse_known_args(argv)
    rc = bench.main(argv)
    if rc:
        return rc
    try:
        from deepspeed_tpu.telemetry.compile import process_account
    except ImportError:
        print(json.dumps({"phase": "setup_account", "account": None}))
        return 0
    cell = bench.parse(argv).workload
    with open(os.path.join(ROOT, "benchmark_out", cell, "series.json")) as f:
        series = json.load(f)
    t_open = series[COMPLETIONS[series["kind"]]][
        series.get("window", [0])[0]]
    account = process_account()
    rows = account.by_program(before=t_open, top=10 ** 6)
    analysis = sorted(((r["program"], r["monitor_analysis_s"]) for r in rows
                       if r["monitor_analysis_s"] > 0),
                      key=lambda r: -r[1])
    print(json.dumps({
        "phase": "setup_account", "workload": cell,
        "events_seen": account.events_seen,
        "before_window": account.totals(before=t_open),
        "by_program": rows[:mine.top],
        "monitor_analysis_by_program": analysis[:mine.top]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
