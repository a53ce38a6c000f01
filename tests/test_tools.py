"""Tool-tier tests: memory introspection, NVMe sweep (reference model:
``tests/unit/ops/aio``, ds_nvme_tune smoke)."""

import numpy as np
import pytest

from deepspeed_tpu.nvme.sweep import io_sweep
from deepspeed_tpu.utils.memory import memory_stats, see_memory_usage


def test_see_memory_usage_runs():
    s = see_memory_usage("unit-test probe")
    assert isinstance(s, dict)  # CPU backend may return {}


def test_io_sweep_roundtrip(tmp_path):
    rows = io_sweep(str(tmp_path), nbytes=1 << 20, block_sizes=(256 << 10,),
                    thread_counts=(1, 2), trials=1)
    assert len(rows) == 2
    assert all(r["read_GBps"] > 0 and r["write_GBps"] > 0 for r in rows)
    # sorted ascending by combined bandwidth
    assert rows[-1]["read_GBps"] + rows[-1]["write_GBps"] >= \
        rows[0]["read_GBps"] + rows[0]["write_GBps"]


def test_elastic_cli(tmp_path, capsys):
    """dstpu_elastic resolves an elastic config from a ds_config JSON."""
    import json

    from deepspeed_tpu.elasticity.elasticity import main

    cfg = {"elasticity": {"enabled": True, "max_train_batch_size": 64,
                          "micro_batch_sizes": [2, 4], "min_gpus": 1,
                          "max_gpus": 16, "version": 0.2}}
    f = tmp_path / "ds_config.json"
    f.write_text(json.dumps(cfg))
    assert main(["-c", str(f)]) == 0
    out = capsys.readouterr().out
    assert "final batch size" in out
    assert "compatible chip counts" in out
    assert main(["-c", str(f), "-w", "7"]) == 1  # incompatible world size


def test_ssh_cli_local_fallback(tmp_path):
    """dstpu_ssh with no hostfile runs the command locally."""
    from deepspeed_tpu.launcher.ssh import main

    rc = main(["-H", str(tmp_path / "missing_hostfile"), "true"])
    assert rc == 0


def test_to_universal_cli(tmp_path, devices8):
    """dstpu_to_universal converts a saved engine checkpoint."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.runtime.checkpoint.universal import main
    from deepspeed_tpu.runtime.engine import ModelSpec

    import jax
    import jax.numpy as jnp

    mesh_lib.set_mesh(None)

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    spec = ModelSpec(
        loss_fn=loss_fn,
        init_fn=lambda k: {"w": jax.random.normal(k, (8, 8)) * 0.1},
        pipeline_capable=False)
    engine, *_ = dst.initialize(model=spec, config={
        "train_batch_size": 8,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 1}})
    engine.train_batch({"x": np.ones((8, 8), np.float32),
                        "y": np.zeros((8, 8), np.float32)})
    engine.save_checkpoint(str(tmp_path), tag="t1")
    rc = main(["--input_folder", str(tmp_path), "--tag", "t1"])
    assert rc == 0
    assert (tmp_path / "t1" / "universal").exists()


# slow: two subprocesses that train, checkpoint and serve a toy model from
# the shipped example scripts (60-80 s of start-up and compiles); no cell and
# no safety property runs `examples/`, and what they call is held elsewhere
@pytest.mark.slow
def test_examples_run(tmp_path):
    """The shipped examples execute end-to-end on CPU (the switching-user
    smoke: train a few steps + checkpoint, then serve)."""
    import os
    import subprocess
    import sys

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "train_llama.py"),
         "--tiny", "--steps", "4", "--ckpt", str(tmp_path / "ck")],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "final loss" in r.stdout and (tmp_path / "ck").exists()
    r = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "serve_llama.py"),
         "--max-new-tokens", "8"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "tok/s" in r.stdout
    r = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "long_context.py"),
         "--seq", "128", "--steps", "2"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "fpdt train" in r.stdout and "splitfuse serve" in r.stdout
    r = subprocess.run(
        [sys.executable, os.path.join(root, "examples", "compress_model.py"),
         "--tiny", "--steps", "8"],
        capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "COMPRESS_EXAMPLE_OK" in r.stdout and "sparse" in r.stdout
