"""Config-system tests (reference model: batch-math assertions in
``tests/unit/runtime/test_ds_config_dict.py``)."""

import json
import os

import pytest

from deepspeed_tpu.runtime.config import parse_config


def test_batch_math_all_given():
    cfg = parse_config({
        "train_batch_size": 32,
        "train_micro_batch_size_per_gpu": 2,
        "gradient_accumulation_steps": 2,
    }, world_size=8)
    assert cfg.train_batch_size == 32


def test_batch_math_derive_gas():
    cfg = parse_config({"train_batch_size": 64, "train_micro_batch_size_per_gpu": 2},
                       world_size=8)
    assert cfg.gradient_accumulation_steps == 4


def test_batch_math_derive_train_batch():
    cfg = parse_config({"train_micro_batch_size_per_gpu": 4,
                        "gradient_accumulation_steps": 2}, world_size=8)
    assert cfg.train_batch_size == 64


def test_batch_math_mismatch_raises():
    with pytest.raises(ValueError):
        parse_config({
            "train_batch_size": 33,
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
        }, world_size=8)


def test_batch_math_defaults():
    cfg = parse_config({}, world_size=4)
    assert cfg.train_micro_batch_size_per_gpu == 1
    assert cfg.gradient_accumulation_steps == 1
    assert cfg.train_batch_size == 4


def test_zero_and_precision_parsing():
    cfg = parse_config({
        "bf16": {"enabled": True},
        "zero_optimization": {"stage": 3, "offload_optimizer": {"device": "cpu"}},
        "gradient_clipping": 1.0,
    }, world_size=1)
    assert cfg.bf16.enabled and not cfg.fp16.enabled
    assert cfg.zero_config.stage == 3
    assert cfg.zero_config.offload_optimizer.device == "cpu"
    assert cfg.compute_dtype == "bfloat16"
    assert cfg.gradient_clipping == 1.0


def test_fp16_dynamic_loss_scale():
    cfg = parse_config({"fp16": {"enabled": True, "initial_scale_power": 12}},
                       world_size=1)
    assert cfg.fp16.dynamic_loss_scale
    assert cfg.fp16.initial_scale_power == 12


def test_fp16_bf16_conflict():
    with pytest.raises(ValueError):
        parse_config({"fp16": {"enabled": True}, "bf16": {"enabled": True}})


def test_json_path_roundtrip(tmp_path):
    p = tmp_path / "ds_config.json"
    p.write_text(json.dumps({"train_batch_size": 8, "zero_optimization": {"stage": 2}}))
    cfg = parse_config(str(p), world_size=8)
    assert cfg.zero_config.stage == 2
    assert cfg.train_micro_batch_size_per_gpu == 1


def test_reference_config_keys_accepted():
    # a config written for the reference framework parses without error
    cfg = parse_config({
        "train_batch_size": 16,
        "steps_per_print": 100,
        "optimizer": {"type": "AdamW", "params": {"lr": 1e-4}},
        "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 10}},
        "bfloat16": {"enabled": True},
        "zero_allow_untested_optimizer": True,
        "wall_clock_breakdown": False,
    }, world_size=8)
    assert cfg.optimizer.type == "AdamW"
    assert cfg.bf16.enabled
    assert cfg.scheduler.type == "WarmupLR"


def test_mesh_axis_sizes():
    cfg = parse_config({"mesh": {"tensor": 2, "seq": 2}}, world_size=8)
    sizes = cfg.mesh.axis_sizes(8)
    assert sizes == {"data": 2, "expert": 1, "pipe": 1, "seq": 2, "tensor": 2}


@pytest.mark.parametrize("placed", [True, False],
                         ids=["placed_from_outside", "not_placed"])
def test_compile_cache_rule(placed, tmp_path, devices8, monkeypatch):
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set the engine sets no other
    directory in code; where it is not, the cache is ``<checkout>/.xla_cache``
    (``utils/compile_cache.py`` — the engine, ``chip_smoke.py``, ``bench.py``
    and the scripts all go through it)."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.runtime.engine import ModelSpec

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = str(tmp_path / "whatever_was_configured")
    if placed:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "out"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    mesh_lib.set_mesh(None)
    spec = ModelSpec(loss_fn=lambda p, b: (jnp.sum((p["w"] * b["x"]) ** 2), {}),
                     init_fn=lambda k: {"w": jnp.ones((4,))},
                     pipeline_capable=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", before)
        dst.initialize(model=spec, config={
            "train_batch_size": 8,
            "optimizer": {"type": "sgd", "params": {"lr": 0.1}},
            "steps_per_print": 0})
        assert jax.config.jax_compilation_cache_dir == (
            before if placed else os.path.join(repo, ".xla_cache"))
    finally:
        # process-global jax config must not leak into later tests
        jax.config.update("jax_compilation_cache_dir", prev)
