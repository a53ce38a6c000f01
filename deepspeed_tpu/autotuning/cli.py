"""Autotuning CLI entry (reference ``deepspeed --autotuning`` path,
``launcher/runner.py:407``): ``dstpu --autotuning tune job.json``.

Job spec (JSON)::

    {"model": {"family": "llama", "config": {...Config kwargs...}},
     "config": {...base deepspeed_tpu config (train_batch_size etc.)...},
     "model_info": {"num_params": ..., "hidden_size": ..., ...},  # optional
     "tuner": "model_based" | "gridsearch" | "random",
     "micro_batches": [1, 2, 4], "zero_stages": [0, 1, 2, 3],
     "max_trials": 8, "trial_steps": 3, "seq_len": 128,
     "output": "autotune_best.json"}

Every trial runs in its own worker process (``trial_worker``) — fresh XLA
client/jit cache, OOM-survivable, per-trial timeout. The chip belongs to one
process at a time, so this parent never imports JAX. The best full config is
written to ``output`` and printed as one JSON line.
"""

from __future__ import annotations

import json
import sys
from typing import List, Optional

from ..utils.logging import logger
from .autotuner import Autotuner


def autotune_main(job_path: str, extra_args: Optional[List[str]] = None) -> int:
    if extra_args:
        raise ValueError(
            f"unexpected arguments after the job JSON: {extra_args} — all "
            f"autotuning options (max_trials, tuner, ...) live in the job "
            f"file")
    with open(job_path) as f:
        job = json.load(f)
    if "model" not in job or "family" not in job["model"]:
        raise ValueError(
            "autotuning job needs model.family (+ model.config) so trials "
            "can rebuild the model in isolated worker processes")
    kw = {}
    for src, dst in (("tuner", "tuner_type"), ("micro_batches", None),
                     ("zero_stages", None), ("trial_steps", None),
                     ("seq_len", None), ("model_info", None),
                     ("trial_timeout_s", None)):
        if src in job:
            kw[dst or src] = job[src]
    tuner = Autotuner(None, job.get("config", {}),
                      model_desc=job["model"], **kw)
    best = tuner.tune(max_trials=job.get("max_trials"))
    best_cfg = tuner.best_ds_config()
    out_path = job.get("output", "autotune_best.json")
    report = {
        "best_config": best_cfg,
        "best_point": best.config,
        "samples_per_sec": best.samples_per_sec,
        "trials": [{"point": r.config, "samples_per_sec": r.samples_per_sec,
                    "error": r.error} for r in tuner.results],
    }
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    logger.info(f"autotuning: best config written to {out_path}")
    print(json.dumps({"best": best.config,
                      "samples_per_sec": best.samples_per_sec,
                      "output": out_path}))
    return 0


if __name__ == "__main__":
    sys.exit(autotune_main(sys.argv[1]))
