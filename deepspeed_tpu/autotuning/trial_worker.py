"""One autotuning trial in an isolated process.

The reference runs every autotuning experiment as a real launcher job
(``deepspeed/autotuning/scheduler.py``) so an OOM kills only that trial and
no jit/alloc state leaks between configurations. This is the TPU analog:
``python -m deepspeed_tpu.autotuning.trial_worker job.json`` builds a fresh
engine in a fresh process (fresh XLA client, fresh jit cache), times
``trial_steps`` train steps on synthetic tokens, and prints ONE JSON line
``{"samples_per_sec": ..., "step_time_s": ...}``.
``--describe-devices`` instead prints ``{"n_chips": ..., "hbm_bytes": ...}``
for the parent, which must not touch JAX itself.

Job spec (JSON file)::

    {"model": {"family": "llama", "config": {...Config kwargs...}},
     "trial_config": {<full deepspeed_tpu config for this trial>},
     "trial_steps": 3, "seq_len": 128}
"""

from __future__ import annotations

import json
import sys
import time


def run_job(job: dict) -> dict:
    import jax
    import numpy as np

    import deepspeed_tpu as dst
    from ..models.hf_import import resolve_module

    model = job["model"]
    module = resolve_module(model["family"])
    cfg_cls = next(v for k, v in vars(module).items()
                   if k.endswith("Config") and isinstance(v, type))
    mcfg = cfg_cls(**model.get("config", {}))
    spec = module.model_spec(mcfg)
    engine, *_ = dst.initialize(model=spec, config=job["trial_config"])
    seq = int(job.get("seq_len", min(128, mcfg.max_seq_len)))
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(
        0, mcfg.vocab_size, (engine.train_batch_size(), seq + 1),
        dtype=np.int32)}
    steps = int(job.get("trial_steps", 3))
    jax.block_until_ready(engine.train_batch(batch).loss)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(steps):
        out = engine.train_batch(batch)
    jax.block_until_ready(out.loss)
    dt = (time.perf_counter() - t0) / steps
    return {"samples_per_sec": engine.train_batch_size() / dt,
            "step_time_s": dt}


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    if argv[0] == "--describe-devices":
        # the autotuning parent stays off JAX; this child answers for it
        from .autotuner import describe_devices

        print(json.dumps(describe_devices()))
        return 0
    with open(argv[0]) as f:
        job = json.load(f)
    try:
        result = run_job(job)
    except Exception as e:
        print(json.dumps({"samples_per_sec": 0.0,
                          "step_time_s": float("inf"),
                          "error": str(e)[-500:]}))
        return 0  # the JSON line IS the report
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
