#!/usr/bin/env python3
"""One run of a training cell of the benchmark with its ``program_options``
overridden: the A/B that decides which rematerialization policies stand on
the ladder (``runtime/activation_checkpointing/checkpointing.py LADDER``).

    python3 scripts/remat_ladder_ab.py --options '{"remat_policy": "save_big_matmuls"}' \\
        --workload mistral-7b.train-2k --seed <n> --seconds 15 --trace 0

``--options`` is a JSON object merged over the role's ``program_options``
(the family config's fields: ``{"remat": false}`` is the ``none`` rung, a
``remat_policy`` pins a rung by name, ``{}`` is the cell as it stands); the
other arguments are ``benchmark/run.py``'s own, which runs in this process
with nothing else changed. One more JSON line follows the benchmark's,
``phase: "remat_ab"``: the options, the median step, the largest compiled
program's bytes and the choice the engine published, where it made one;
``--hlo-out FILE`` also writes that program's optimized text.
Nothing under ``benchmark/`` is edited; the numbers this prints are for
``PERF.md``'s table, not for the ledger.
"""

import dataclasses
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmark"))


def main(argv) -> int:
    import argparse

    import run as bench
    from benchmark.harness import device, manifest

    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--options", type=json.loads, default={})
    ap.add_argument("--hlo-out", help="write the largest compiled program's "
                    "optimized text here (under chiprun_out/ on the chip)")
    mine, argv = ap.parse_known_args(argv)

    init = manifest.Cell.__init__

    def with_options(self, *args, **kwargs):
        init(self, *args, **kwargs)
        self.role["program_options"] = {**self.role["program_options"],
                                        **mine.options}

    manifest.Cell.__init__ = with_options
    # every program compiled from here on (run.py asks again: both see them)
    programs = device.record_compiled()
    rc = bench.main(argv)
    if rc:
        return rc
    cell = bench.parse(argv).workload
    with open(os.path.join(ROOT, "benchmark_out", cell, "series.json")) as f:
        series = json.load(f)
    times = series["step_completion_s"]
    gaps = [b - a for a, b in zip(times, times[1:])]
    from deepspeed_tpu.runtime.activation_checkpointing import \
        checkpointing as ac

    choice = ac.last_choice()
    largest = max(programs, key=device.program_bytes, default=None)
    if mine.hlo_out and largest is not None:
        os.makedirs(os.path.dirname(mine.hlo_out) or ".", exist_ok=True)
        with open(mine.hlo_out, "w") as f:
            f.write(largest.as_text())
    print(json.dumps({
        "phase": "remat_ab", "workload": cell, "options": mine.options,
        "median_step_ms": 1e3 * statistics.median(gaps) if gaps else None,
        "compiled_gb": device.program_bytes(largest) / 1e9
        if largest is not None else None,
        "remat": choice and dict(
            dataclasses.asdict(choice), headroom_bytes=choice.headroom_bytes,
            predicted_peak_bytes=choice.predicted_peak_bytes)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
