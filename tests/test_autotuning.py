"""Autotuner tests (reference model: ``tests/unit/autotuning``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.autotuning import (Autotuner, GridSearchTuner,
                                      ModelBasedTuner, RandomTuner)
from deepspeed_tpu.autotuning.autotuner import estimate_memory_per_chip
from deepspeed_tpu.models import llama


def _quadratic_space():
    space = [{"x": i} for i in range(10)]
    metric = lambda c: -(c["x"] - 7) ** 2  # noqa: E731  best at x=7
    return space, metric


@pytest.mark.parametrize("cls", [GridSearchTuner, RandomTuner, ModelBasedTuner])
def test_tuners_find_optimum_exhaustively(cls):
    space, metric = _quadratic_space()
    tuner = cls(space, metric)
    best_cfg, best_val = tuner.tune()
    assert best_cfg == {"x": 7} and best_val == 0


def test_model_based_tuner_budgeted():
    space, metric = _quadratic_space()
    tuner = ModelBasedTuner(space, metric, warmup=3, seed=1)
    best_cfg, _ = tuner.tune(max_trials=7)
    assert len(tuner.records) == 7
    assert abs(best_cfg["x"] - 7) <= 2  # surrogate homes in


def test_memory_model_monotonic_in_stage():
    kw = dict(num_params=8_000_000_000, n_chips=64, micro_batch=1,
              seq_len=4096, hidden=4096, num_layers=32)
    ests = [estimate_memory_per_chip(zero_stage=s, **kw) for s in (0, 1, 2, 3)]
    assert ests[0] > ests[1] > ests[2] > ests[3]
    # 8B params at stage 0 needs >128GB/chip: must exceed any real HBM
    assert ests[0] > 128 << 30
    # remat shrinks activations
    assert estimate_memory_per_chip(zero_stage=3, remat=True, **kw) < ests[3]


def test_space_pruning(devices8):
    cfg = llama.LlamaConfig.tiny()
    spec = llama.model_spec(cfg, compute_dtype=jnp.float32)
    at = Autotuner(spec, {"train_batch_size": 16,
                          "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
                   model_info={"num_params": cfg.num_params, "seq_len": 32,
                               "hidden_size": cfg.hidden_size,
                               "num_layers": cfg.num_layers},
                   hbm_bytes_per_chip=1 << 40,
                   micro_batches=(1, 2, 3), zero_stages=(0, 3))
    space = at.build_space()
    # mb=3 never divides 16/8 chips; mb in {1,2} × stages {0,3}
    assert {(p["micro_batch"], p["zero_stage"]) for p in space} == \
        {(1, 0), (1, 3), (2, 0), (2, 3)}
    assert all(p["micro_batch"] * p["gas"] * 8 == 16 for p in space)
    # tiny HBM prunes everything
    at2 = Autotuner(spec, {"train_batch_size": 16},
                    model_info={"num_params": cfg.num_params, "seq_len": 32,
                                "hidden_size": cfg.hidden_size,
                                "num_layers": cfg.num_layers},
                    hbm_bytes_per_chip=1 << 10)
    assert at2.build_space() == []


def test_autotuner_end_to_end_trials(devices8):
    cfg = llama.LlamaConfig.tiny()
    spec = llama.model_spec(cfg, compute_dtype=jnp.float32)
    at = Autotuner(spec, {"train_batch_size": 16,
                          "optimizer": {"type": "adamw", "params": {"lr": 1e-3}}},
                   trial_steps=2, tuner_type="gridsearch",
                   micro_batches=(1, 2), zero_stages=(1,))

    def data_fn(bs):
        t = np.random.randint(0, cfg.vocab_size, (bs, 33)).astype(np.int32)
        return {"tokens": t}

    best = at.tune(data_fn)
    assert best.samples_per_sec > 0
    assert len(at.results) == 2
    ds_cfg = at.best_ds_config()
    assert ds_cfg["zero_optimization"]["stage"] == 1
    assert ds_cfg["train_micro_batch_size_per_gpu"] in (1, 2)


# slow: the CLI's sweep starts a worker process a trial (30-40 s of JAX
# start-ups); no cell and no safety property runs the autotuner, and
# `test_autotuner_end_to_end_trials` holds the same search in process
@pytest.mark.slow
def test_autotuning_cli_subprocess_trials(tmp_path):
    """End-to-end CLI (reference launcher/runner.py:407 --autotuning): a job
    JSON → isolated per-trial worker processes (fresh jit cache each; an OOM
    would kill only its trial) → best-config JSON on disk."""
    import json
    import os
    import subprocess
    import sys

    job = {
        "model": {"family": "llama",
                  "config": {"vocab_size": 256, "hidden_size": 32,
                             "intermediate_size": 64, "num_layers": 2,
                             "num_heads": 4, "num_kv_heads": 2,
                             "max_seq_len": 64}},
        "config": {"train_batch_size": 8,
                   "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                   "steps_per_print": 0},
        "tuner": "gridsearch",
        "micro_batches": [1, 2],
        "zero_stages": [0, 1],
        "max_trials": 4,
        "trial_steps": 2,
        "seq_len": 32,
        "output": str(tmp_path / "best.json"),
    }
    job_path = str(tmp_path / "job.json")
    with open(job_path, "w") as f:
        json.dump(job, f)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"  # trial_worker honors this via config update
    r = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.runner",
         "--autotuning", "tune", job_path],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    assert line["samples_per_sec"] > 0
    report = json.load(open(job["output"]))
    assert report["best_config"]["train_micro_batch_size_per_gpu"] == 1
    # mb=2 x dp=8 does not divide the global batch 8 -> pruned; two stages run
    assert len(report["trials"]) == 2
    assert all(t["error"] is None for t in report["trials"]), report["trials"]
