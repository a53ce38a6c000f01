#!/usr/bin/env python3
"""Serves one serving cell's correctness probes for many seeds in one
process, with longer answers, and prints every position's distance below
the reference's top beside its routing margin: the evidence the tolerances
in ``traffic_kinds/closed_loop.py`` are set from
(``chiprun -- python3 benchmark/tools/probe_sweep.py ...``).

    probe_sweep.py --workload W --seeds 11,12,13 [--steps 48] [--tag T]

Nothing is measured and no result line is printed; the lines also go to
``chiprun_out/<tag>/<workload>.jsonl``.
"""

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--tag", default="probes")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()

    import numpy as np

    from benchmark.harness import device as dev
    from benchmark.harness import manifest
    from benchmark.traffic_kinds import closed_loop
    from benchmark.traffic_kinds.common import Run

    cell = manifest.Cell(args.workload, rehearsal=args.rehearse)
    dev.compile_cache_dir()
    device = dev.check(cell.chips, args.rehearse)
    out_dir = os.path.join(ROOT, "chiprun_out", args.tag)
    os.makedirs(out_dir, exist_ok=True)
    vocab = cell.model["vocab_size"]
    with open(os.path.join(out_dir, cell.name + ".jsonl"), "w") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            r = Run(cell=cell, seed=seed, seconds=0.0, trace=False,
                    out_dir=out_dir, t_process=t0, device=device)
            eng = closed_loop.build(r)
            rng = np.random.default_rng([seed, 0x9B0BE])
            for i, (n, _) in enumerate(cell.traffic["probes"]):
                p = closed_loop.probe_tokens(
                    r, eng, rng.integers(0, vocab, n).tolist(), args.steps,
                    uid=10 ** 6 + i)
                line = json.dumps({"seed": seed, **p})
                print(line, flush=True)
                f.write(line + "\n")
            del eng
            gc.collect()
            print(json.dumps({"seed": seed,
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
