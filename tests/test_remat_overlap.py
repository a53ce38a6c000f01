"""Fine-grained compute/collective overlap + selective remat (the MFU-gap
tentpole): the ZeRO-3 per-layer all-gather prefetch
(``comms_overlap.layer_prefetch`` → ``comm/overlap.py prefetch_scan``) and
the named selective-remat policy registry
(``runtime/activation_checkpointing/checkpointing.py`` ``save_attn_out`` /
``save_big_matmuls``).

Pins:
- ``prefetch_scan`` == ``lax.scan`` bit-for-bit (values AND grads, any depth);
- stage-3 + prefetch training reproduces the stage-0 replicated trajectory
  (the prefetch constraint pins each layer's gather — exact parity with the
  replicated reference);
- the default config arms nothing (plain-scan path, pre-PR program);
- remat policies are loss/grad bit-identical to each other;
- saved-residual bytes order: none ≥ save_big_matmuls > save_attn_out > full;
- the remat-policy lint: every checkpoint name a registered policy saves is
  actually emitted by the model families (jaxpr-checked — a model edit
  cannot silently turn a policy into a no-op);
- ``Train/overlap/*`` / ``Train/remat/*`` live in a closed schema registry,
  flow through ``TelemetryHub.train_event``, and render in
  ``telemetry_report.py --comm-efficiency``.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import deepspeed_tpu as dst
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.comm import overlap as ov
from deepspeed_tpu.models import gpt, llama, mixtral
from deepspeed_tpu.ops import registry
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ac
from deepspeed_tpu.telemetry import schema

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MCFG = llama.LlamaConfig.tiny(use_pipeline=False)


@pytest.fixture(autouse=True)
def _reset_prefetch():
    """The engine publishes layer-prefetch state process-wide; never leak it
    into other tests."""
    yield
    ov.reset_layer_prefetch()


def _engine(stage=3, extra=None, mcfg=MCFG):
    mesh_lib.set_mesh(None)
    config = {
        "train_batch_size": 16,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-2}},
        "zero_optimization": {"stage": stage},
        "steps_per_print": 0,
    }
    for key, val in (extra or {}).items():
        if isinstance(val, dict) and isinstance(config.get(key), dict):
            config[key] = {**config[key], **val}
        else:
            config[key] = val
    spec = llama.model_spec(mcfg, compute_dtype=jnp.float32)
    engine, *_ = dst.initialize(model=spec, config=config)
    return engine


def _batch(step):
    rs = np.random.RandomState(100 + step)
    return {"tokens": rs.randint(0, 256, (16, 33)).astype(np.int32)}


def _losses(engine, steps=2):
    return [float(engine.train_batch(_batch(s)).loss) for s in range(steps)]


# --------------------------------------------------------------------------- #
# prefetch_scan: the unit
# --------------------------------------------------------------------------- #
def test_prefetch_scan_matches_lax_scan_bitwise():
    rs = np.random.RandomState(0)
    layers = {"w": jnp.asarray(rs.randn(5, 8, 8).astype(np.float32)),
              "b": jnp.asarray(rs.randn(5, 8).astype(np.float32))}
    x0 = jnp.asarray(rs.randn(2, 8).astype(np.float32))

    def body(x, layer):
        y = jnp.tanh(x @ layer["w"] + layer["b"])
        return y, jnp.sum(y)

    ref, ys_ref = lax.scan(body, x0, layers)
    for depth in (1, 2, 3, 5, 99):  # 99 clamps to n_layers
        out, ys = ov.prefetch_scan(body, x0, layers, depth=depth,
                                   shardings=None)
        assert bool(jnp.all(out == ref)) and bool(jnp.all(ys == ys_ref)), depth

    # gradients are the plain scan's too (the ordering barrier has a
    # pass-through VJP)
    def loss(x0, fn):
        out, _ = fn(body, x0, layers)
        return jnp.sum(out ** 2)

    g_ref = jax.grad(lambda x: loss(x, lax.scan))(x0)
    g_pre = jax.grad(lambda x: loss(
        x, lambda b, i, l: ov.prefetch_scan(b, i, l, depth=2,
                                            shardings=None)))(x0)
    assert bool(jnp.all(g_ref == g_pre))


def test_prefetch_global_config_roundtrip():
    assert not ov.layer_prefetch_active()
    ov.configure_layer_prefetch(True, depth=3)
    assert ov.layer_prefetch_active() and ov.layer_prefetch_depth() == 3
    ov.reset_layer_prefetch()
    assert not ov.layer_prefetch_active()
    assert ov.layer_prefetch_depth() == 1


# --------------------------------------------------------------------------- #
# engine integration: gating + parity
# --------------------------------------------------------------------------- #
def test_stage3_overlap_requires_layer_prefetch(devices8):
    with pytest.raises(ValueError, match="layer_prefetch"):
        _engine(stage=3, extra={"comms_overlap": {"enabled": True}})


def test_default_engine_arms_nothing(devices8):
    engine = _engine(stage=3)
    assert not engine._layer_prefetch_on
    assert not ov.layer_prefetch_active()
    assert engine.telemetry.train_values == {}


def test_stage3_prefetch_matches_replicated_trajectory(devices8):
    """The T3 acceptance pin: ZeRO-3 + per-layer prefetch trains the exact
    stage-0 replicated trajectory (the per-layer gather constraint pins the
    layout; on the CPU mesh this is bit-level-close where the un-pinned
    stage-3 program may drift)."""
    base0 = _losses(_engine(stage=0), steps=3)
    ov.reset_layer_prefetch()
    engine = _engine(stage=3, extra={"comms_overlap": {
        "enabled": True, "layer_prefetch": True}})
    assert engine._layer_prefetch_on and ov.layer_prefetch_active()
    pre = _losses(engine, steps=3)
    np.testing.assert_allclose(pre, base0, rtol=1e-6)
    # Train/overlap/* gauges registered + schema-clean
    tv = engine.telemetry.train_values
    assert tv["Train/overlap/prefetch_depth"] == 1.0
    assert tv["Train/overlap/prefetch_layers"] == float(MCFG.num_layers)
    assert tv["Train/overlap/prefetch_bytes"] > 0
    events = [(n, v, 0) for n, v in tv.items()]
    assert schema.validate_events(events) == []


def test_prefetch_depth2_and_remat_compose(devices8):
    import dataclasses

    base0 = _losses(_engine(stage=0), steps=2)
    ov.reset_layer_prefetch()
    mcfg = dataclasses.replace(MCFG, remat=True,
                               remat_policy="save_big_matmuls")
    engine = _engine(stage=3, mcfg=mcfg, extra={"comms_overlap": {
        "enabled": True, "layer_prefetch": True, "prefetch_depth": 2}})
    np.testing.assert_allclose(_losses(engine, steps=2), base0, rtol=1e-6)


def test_prefetch_noop_below_stage3(devices8):
    """layer_prefetch needs gather-on-use params: at stage 2 the engine logs
    and keeps the plain scan (and the grad-overlap engine still runs)."""
    engine = _engine(stage=2, extra={"comms_overlap": {
        "enabled": True, "layer_prefetch": True}})
    assert not engine._layer_prefetch_on
    assert not ov.layer_prefetch_active()
    assert engine._overlap_active()


# --------------------------------------------------------------------------- #
# selective remat: registry semantics
# --------------------------------------------------------------------------- #
def test_unknown_policy_raises():
    with pytest.raises(ValueError, match="unknown remat policy"):
        ac.get_policy("definitely_not_a_policy")


def test_policy_saved_names_mapping():
    assert ac.POLICY_SAVED_NAMES["save_attn_out"] == ("attn_out",)
    assert set(ac.POLICY_SAVED_NAMES["save_big_matmuls"]) == \
        set(ac.MATMUL_CHECKPOINT_NAMES) | set(ac.KERNEL_CHECKPOINT_NAMES)
    # every mapped policy resolves in the registry
    for name in ac.POLICY_SAVED_NAMES:
        assert ac.get_policy(name) is not None
    # and the schema's closed per-policy series list matches the registry
    assert set(schema.REMAT_POLICIES) == set(ac.POLICIES)


def test_loss_and_grads_bit_identical_across_policies(devices8):
    """Remat changes WHEN activations are (re)computed, never WHAT: loss and
    grads of the tiny model are bit-identical across every selective policy
    (and equal to the no-remat forward)."""
    import dataclasses

    params = llama.init(MCFG, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(7).randint(0, 256, (4, 33)).astype(np.int32))}
    results = {}
    for policy in ("none", "full", "dots_saveable", "save_attn_out",
                   "save_big_matmuls"):
        cfg = dataclasses.replace(MCFG, remat=policy != "none",
                                  remat_policy=policy)
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p, cfg=cfg: llama.loss_fn(
                cfg, p, batch, compute_dtype=jnp.float32)[0]))(params)
        results[policy] = (float(loss), jax.tree.leaves(grads))
    ref_loss, ref_grads = results["full"]
    for policy, (loss, grads) in results.items():
        assert loss == ref_loss, policy
        if policy == "none":
            continue  # no-remat backward may differ in final-ulp fp order
        for a, b in zip(grads, ref_grads):
            assert bool(jnp.all(a == b)), policy
    # the no-remat grads still agree to fp tolerance
    for a, b in zip(results["none"][1], ref_grads):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def _family_policy_parity(mod, cfg0, cfg1):
    batch = {"tokens": jnp.asarray(
        np.random.RandomState(3).randint(0, 256, (2, 17)).astype(np.int32))}
    params = mod.init(cfg0, jax.random.PRNGKey(0))
    l0, g0 = jax.value_and_grad(
        lambda p: mod.loss_fn(cfg0, p, batch,
                              compute_dtype=jnp.float32)[0])(params)
    l1, g1 = jax.value_and_grad(
        lambda p: mod.loss_fn(cfg1, p, batch,
                              compute_dtype=jnp.float32)[0])(params)
    assert float(l0) == float(l1), mod.__name__
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-7)


def test_gpt_policies_bit_identical():
    _family_policy_parity(
        gpt, gpt.GPTConfig.tiny(),
        gpt.GPTConfig.tiny(remat=True, remat_policy="save_big_matmuls"))


def test_mixtral_policies_bit_identical():
    _family_policy_parity(
        mixtral, mixtral.MixtralConfig.tiny(),
        mixtral.MixtralConfig.tiny(remat=True,
                                   remat_policy="save_attn_out"))


def _block_saved_bytes(policy):
    params = llama.init(MCFG, jax.random.PRNGKey(0))
    from deepspeed_tpu.ops.rotary import rope_frequencies

    cos, sin = rope_frequencies(MCFG.head_size, MCFG.max_seq_len,
                                MCFG.rope_theta)
    layer0 = jax.tree.map(lambda a: a[0], params["layers"])
    x = jnp.asarray(np.random.RandomState(0).randn(
        2, 16, MCFG.hidden_size).astype(np.float32))

    def blk(x, layer, cos, sin):
        return jnp.sum(llama._block(MCFG, x, layer, cos, sin, None) ** 2)

    return ac.saved_bytes(blk, x, layer0, cos, sin, policy=policy)


def test_saved_bytes_ordering():
    """The HBM ordering the sweep reports, measured exactly at trace time:
    no remat saves every needed intermediate ≥ save_big_matmuls (every MXU
    dot result) > save_attn_out (one branch output) > full (nothing)."""
    vals = {p: _block_saved_bytes(p)
            for p in ("none", "save_big_matmuls", "save_attn_out", "full")}
    if any(v is None for v in vals.values()):
        pytest.skip("saved_residuals introspection unavailable in this jax")
    assert vals["none"] >= vals["save_big_matmuls"], vals
    assert vals["save_big_matmuls"] > vals["save_attn_out"], vals
    assert vals["save_attn_out"] > vals["full"] == 0, vals


# --------------------------------------------------------------------------- #
# CI lint: policy names must be emitted by the model families
# --------------------------------------------------------------------------- #
def _training_jaxpr(mod, cfg, grad=False):
    params = mod.init(cfg, jax.random.PRNGKey(0))
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}

    def loss(p):
        return mod.loss_fn(cfg, p, batch, compute_dtype=jnp.float32)[0]

    return str(jax.make_jaxpr(jax.grad(loss) if grad else loss)(params))


FAMILIES = ((llama, llama.LlamaConfig.tiny(use_pipeline=False)),
            (gpt, gpt.GPTConfig.tiny()),
            (mixtral, mixtral.MixtralConfig.tiny()))


def test_remat_policy_names_emitted_by_model_families():
    """Tier-1 lint: every checkpoint name a registered remat policy saves is
    emitted by the model families — each family's declared
    CHECKPOINT_NAMES_EMITTED actually appears in its traced training jaxpr
    (``name[name=...]`` primitives), and no policy references a name no
    family emits. Catches silent policy no-ops after model edits."""
    emitted_union = set()
    for mod, cfg in FAMILIES:
        declared = set(mod.CHECKPOINT_NAMES_EMITTED)
        jaxpr = _training_jaxpr(mod, cfg)
        for name in declared:
            assert f"name={name}" in jaxpr, \
                f"{mod.__name__} declares {name!r} but its training jaxpr " \
                f"never emits it"
        emitted_union |= declared
    # the names a KERNEL emits (inside the flash kernel's forward rule) are
    # met where that kernel runs: llama's training jaxpr on the Pallas path
    # (its GRADIENT: the forward rule runs only under differentiation)
    registry.set_backend("attention", "pallas")
    try:
        flash_jaxpr = _training_jaxpr(*FAMILIES[0], grad=True)
    finally:
        registry.set_backend("attention", None)
    for name in ac.KERNEL_CHECKPOINT_NAMES:
        assert f"name={name}" in flash_jaxpr, name
        assert f"name={name}" not in _training_jaxpr(*FAMILIES[0],
                                                     grad=True), name
    emitted_union |= set(ac.KERNEL_CHECKPOINT_NAMES)
    for policy, names in ac.POLICY_SAVED_NAMES.items():
        for name in names:
            if name in ("residual", "block_out"):
                continue  # reserved names for user models (documented)
            assert name in emitted_union, \
                f"policy {policy!r} saves {name!r}, which no model family " \
                f"emits — the policy would be a silent no-op"
    # the flagship selective policies must bite on EVERY family
    for mod, _ in FAMILIES:
        declared = set(mod.CHECKPOINT_NAMES_EMITTED)
        for policy in ("save_attn_out", "save_big_matmuls"):
            assert declared & set(ac.POLICY_SAVED_NAMES[policy]), \
                (mod.__name__, policy)


# --------------------------------------------------------------------------- #
# telemetry: closed registry, hub fan-out, report rendering
# --------------------------------------------------------------------------- #
def test_train_series_schema_validation():
    ok = [("Train/overlap/prefetch_depth", 1.0, 0),
          ("Train/overlap/hidden_comm_frac", 0.5, 0),
          ("Train/remat/saved_bytes_save_big_matmuls", 123.0, 0),
          ("Train/Step/fwd_ms", 1.0, 0),       # open Train families stay open
          ("Train/Samples/train_loss", 2.0, 0)]
    assert schema.validate_events(ok) == []
    bad = schema.validate_events([("Train/overlap/not_a_series", 1.0, 0)])
    assert bad and "TRAIN_SERIES" in bad[0]
    bad = schema.validate_events([("Train/remat/saved_bytes_nopolicy", 1, 0)])
    assert bad and "TRAIN_SERIES" in bad[0]


def test_hub_train_event_and_snapshot():
    from deepspeed_tpu.runtime.config import parse_config
    from deepspeed_tpu.telemetry import TelemetryHub

    hub = TelemetryHub(parse_config({}))
    hub.train_event("overlap/prefetch_depth", 2)
    hub.train_event("Train/remat/step_ms_full", 12.5)
    assert hub.train_values["Train/overlap/prefetch_depth"] == 2.0
    rows = dict((n, (v, k)) for n, v, k in hub.metrics_snapshot())
    assert rows["Train/overlap/prefetch_depth"] == (2.0, "gauge")
    assert rows["Train/remat/step_ms_full"] == (12.5, "gauge")
    events = [(n, v, 0) for n, v in hub.train_values.items()]
    assert schema.validate_events(events) == []


def test_report_renders_overlap_and_remat_sections(tmp_path):
    path = tmp_path / "events.jsonl"
    rows = [("Comm/all_gather_params/bytes", 1024.0),
            ("Comm/all_gather_params/count", 2.0),
            ("Comm/all_gather_params/algo_bytes", 1024.0),
            ("Train/overlap/prefetch_depth", 2.0),
            ("Train/overlap/prefetch_layers", 12.0),
            ("Train/overlap/prefetch_bytes", 4096.0),
            ("Train/overlap/hidden_comm_frac", 0.75),
            ("Train/remat/saved_bytes_full", 0.0),
            ("Train/remat/saved_bytes_save_big_matmuls", 213248.0),
            ("Train/remat/step_ms_full", 52.2),
            ("Train/remat/step_ms_save_big_matmuls", 45.6),
            ("Train/remat/peak_bytes_save_big_matmuls", 19794360.0)]
    with open(path, "w") as f:
        for name, value in rows:
            f.write(json.dumps({"name": name, "value": value, "step": 1,
                                "ts": 0.0}) + "\n")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "telemetry_report.py"),
         str(path), "--comm-efficiency"],
        capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "fine-grained overlap" in out.stdout
    assert "prefetch depth" in out.stdout
    assert "overlap-hidden comm" in out.stdout
    assert "selective remat sweep" in out.stdout
    assert "save_big_matmuls" in out.stdout
    assert "45.60" in out.stdout


def test_config_keys_parse():
    from deepspeed_tpu.runtime.config import parse_config

    cfg = parse_config({})
    assert cfg.comms_overlap.layer_prefetch is False
    assert cfg.comms_overlap.prefetch_depth == 1
    cfg = parse_config({"comms_overlap": {"enabled": True,
                                          "layer_prefetch": True,
                                          "prefetch_depth": 3},
                        "activation_checkpointing": {
                            "policy": "save_big_matmuls"}})
    assert cfg.comms_overlap.layer_prefetch
    assert cfg.comms_overlap.prefetch_depth == 3
    assert ac.get_policy(cfg.activation_checkpointing.policy) is not None
