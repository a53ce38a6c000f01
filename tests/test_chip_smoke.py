"""``chip_smoke.py``: it refuses a machine without a chip, and its phases —
rehearsed here at a tiny size on the CPU, kernels interpreted, the Mosaic
checks off — still run through the entry points they name. What the script
is FOR (the chip, at Mistral-7B widths) only a chip run can show."""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run(code_or_script, env_extra, timeout):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           **env_extra}
    return subprocess.run([sys.executable] + code_or_script,
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=REPO)


def test_chip_smoke_refuses_a_machine_without_a_chip():
    """Non-zero exit and ``"ok": false`` in the last line, within seconds:
    the device phase fails before any model is built."""
    r = _run([os.path.join(REPO, "chip_smoke.py")], {"XLA_FLAGS": ""}, 120)
    assert r.returncode != 0, r.stdout[-500:]
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and "no TPU" in last["error"], last
    assert last["device"] is None
    # the device line names the platform it found; no phase after it ran
    assert [json.loads(l).get("phase") for l in lines[:-1]] == ["device"]
    assert json.loads(lines[0])["platform"] == "cpu"


def _tiny():
    import chip_smoke
    from deepspeed_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(max_seq_len=512)
    train = chip_smoke.TrainSize(layers=2, batch=8, seq=64, steps=4)
    serve = chip_smoke.ServeSize(
        layers=2, block_size=16, pool_blocks=96, slots=8, prefill_chunk=32,
        prompt_lens=(8, 20, 32, 40, 64, 100), late_prompt=12, new_tokens=6,
        probes=((20, 3), (90, 3)))
    return chip_smoke, cfg, train, serve


def test_chip_smoke_train_phase_tiny(capsys):
    chip_smoke, cfg, train, _ = _tiny()
    chip_smoke.phase_train(train, seed=0, mosaic=False,
                           cfg=dataclasses.replace(cfg, remat=True))
    row = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert row["phase"] == "train" and row["compiles"] == 1


def test_chip_smoke_serve_phase_tiny(capsys):
    chip_smoke, cfg, _, serve = _tiny()
    chip_smoke.phase_serve(serve, seed=0, mosaic=False, cfg=cfg)
    row, overlap = map(json.loads,
                       capsys.readouterr().out.strip().splitlines()[-2:])
    assert row["phase"] == "serve" and row["requests"] == 6
    assert len(row["logit_checks"]) == 2
    # overlapped ticks against synchronous steps: sampled rows, an eos
    # ending, a preemption with a token in flight, a prompt shorter than a
    # chunk admitted beside the live streams (no ``put`` drain), fused quanta
    assert overlap["phase"] == "serve_overlap"
    assert overlap["streams_equal"] == overlap["requests"] == 7
    assert overlap["late_prompt_tokens"] == 12 and overlap["put_drains"] == 0
    assert overlap["eos_stream_tokens"] < 6 and overlap["preempted"] == 1
    assert overlap["overlapped_steps"] > 0


def test_chip_smoke_four_chip_phase_on_four_virtual_devices():
    """The ``--chips 4`` phase on four virtual CPU devices (its own process:
    the device count is fixed when JAX starts), the ops resolved as on the
    chip: the engine's own trace runs the interpreted kernels per device."""
    code = (
        "import dataclasses, chip_smoke\n"
        "from deepspeed_tpu.models import llama\n"
        "from deepspeed_tpu.ops import registry\n"
        "registry.on_tpu = lambda: True\n"
        "cfg = llama.LlamaConfig.tiny(max_seq_len=512, remat=True)\n"
        "chip_smoke.phase_four_chips(chip_smoke.TrainSize(layers=2, batch=4,"
        " seq=64), seed=0, mosaic=False, cfg=cfg)\n")
    r = _run(["-c", code],
             {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"}, 600)
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-3000:]
    row = json.loads(r.stdout.strip().splitlines()[-1])
    assert row["phase"] == "four_chips" and row["mesh"] == {"data": 4}
    assert len(row["state_bytes_per_device"]) == 4
