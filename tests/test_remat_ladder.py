"""``remat: true`` with no policy named keeps what fits: the ladder and its
chooser (``runtime/activation_checkpointing/checkpointing.py``), the engine
that asks once before its step is first lowered, and the fall back to
``full``. No device needed: the memory a device reports is given as numbers.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import deepspeed_tpu as dst
from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models import llama
from deepspeed_tpu.ops import registry
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ac
from deepspeed_tpu.telemetry import schema

V5E_LIMIT = 16_909_336_576          # a v5e chip's allocator: 15.75 GiB


@pytest.fixture(autouse=True)
def _registry():
    """The engine publishes its choice process-wide; never leak it."""
    ac.reset()
    yield
    ac.reset()


# --------------------------------------------------------------------------- #
# the chooser: a pure function of numbers
# --------------------------------------------------------------------------- #
KEPT = {"none": 3000, "save_big_matmuls": 1500}


@pytest.mark.parametrize("headroom, rung", [
    (5000, "none"), (3000, "none"), (2999, "save_big_matmuls"),
    (1500, "save_big_matmuls"), (1499, "full"), (0, "full"), (-10, "full"),
    (None, "full")])
def test_choose_rung_takes_the_richest_that_fits(headroom, rung, monkeypatch):
    # a rung that is not on the ladder is never chosen, whatever fits
    assert ac.choose_rung(KEPT, headroom) == \
        ("full" if rung == "full" else "save_big_matmuls")
    monkeypatch.setattr(ac, "LADDER", ("none", "save_big_matmuls", "full"))
    assert ac.choose_rung(KEPT, headroom) == rung
    # a rung nobody counted (the head-room was spent before its trace)
    assert ac.choose_rung({}, headroom) == "full"


def test_ladder_is_registered_and_ends_in_full():
    assert ac.LADDER[-1] == "full"
    assert set(ac.LADDER) <= set(ac.POLICIES)
    kept = {r: ac.LADDER[::-1].index(r) for r in ac.LADDER}
    # richest first: each rung keeps at least what the next one does
    assert [ac.choose_rung(kept, h) for h in range(len(kept))[::-1]] == \
        list(ac.LADDER)


def test_headroom_is_the_limit_less_every_term():
    choice = ac.RematChoice(limit_bytes=100, held_bytes=40, step_bytes=30,
                            margin_bytes=5)
    assert choice.headroom_bytes == 25
    assert ac.RematChoice(held_bytes=40).headroom_bytes is None  # no limit


# --------------------------------------------------------------------------- #
# the two training cells' shapes (Mistral-7B widths, 4 x 2048 tokens a
# device, ZeRO-3): abstract shapes only, nothing is allocated
# --------------------------------------------------------------------------- #
def _cell(layers: int, chips: int):
    """``(probe, params, param shardings, state bytes a device)`` of the
    benchmark's training role at ``layers`` over ``chips`` devices."""
    cfg = dataclasses.replace(llama.LlamaConfig.mistral_7b(),
                              num_layers=layers, max_seq_len=8192, remat=True)
    mesh = Mesh(np.array(jax.devices()[:chips]), ("data",))
    shapes = jax.eval_shape(lambda: llama.init(cfg, jax.random.PRNGKey(0)))
    # ZeRO-3: every leaf sharded over the data axis on a dimension it divides
    def zero3(x):
        dim = next((i for i, d in enumerate(x.shape) if d % chips == 0), None)
        spec = [None] * x.ndim
        if dim is not None:
            spec[dim] = "data"
        return NamedSharding(mesh, P(*spec))

    shardings = jax.tree.map(zero3, shapes)
    params = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, jnp.float32, sharding=s),
        shapes, shardings)
    batch = {"tokens": jax.ShapeDtypeStruct((4, 2049), jnp.int32)}
    probe = llama.remat_probe(cfg, params, batch)
    state = 3 * ac.device_bytes(params)   # fp32 masters + two Adam moments
    return probe, params, shardings, state


@pytest.fixture
def flash():
    """The chip's attention (the Mosaic flash kernel keeps its output and a
    log-sum-exp, never the S x S probabilities the XLA form would), traced
    here over shapes alone."""
    registry.set_backend("attention", "pallas")
    yield
    registry.set_backend("attention", None)


def _choice(layers: int, chips: int, limit=V5E_LIMIT):
    probe, params, shardings, state = _cell(layers, chips)
    return ac.choose(probe, params, shardings, jnp.bfloat16,
                     limit_bytes=limit, held_bytes=state,
                     gathers_at_use=chips > 1)


def test_train_2k_takes_a_rich_rung(flash):
    choice = _choice(layers=2, chips=1)
    assert choice.rung == "save_big_matmuls", choice
    # a layer keeps 0.70 GB of matmul results - 8192 tokens x (6144 + 4096
    # + 4096 + 14336 + 14336) columns x 2 bytes; the backward never reads
    # mlp_out - and the flash kernel's output and log-sum-exp, 128 x 2048 x
    # 128 in bf16 and in float32
    assert choice.kept_bytes == {"save_big_matmuls": 2 * (
        8192 * 43008 * 2 + 128 * 2048 * 128 * (2 + 4))}
    assert 8.3e9 < choice.held_bytes < 8.5e9
    assert choice.kept_bytes["save_big_matmuls"] <= choice.headroom_bytes
    assert choice.predicted_peak_bytes < V5E_LIMIT


def test_train_zero3_x4_stays_full(flash, monkeypatch):
    traced = []
    saved_bytes = ac.saved_bytes
    monkeypatch.setattr(ac, "saved_bytes", lambda *a, policy=None: (
        traced.append(policy), saved_bytes(*a, policy=policy))[1])
    choice = _choice(layers=10, chips=4)
    assert choice.rung == "full", choice
    assert 7.2e9 < choice.held_bytes < 7.4e9
    # the replay's trace spent the head-room: no rung was traced for it
    assert choice.headroom_bytes < 0 and choice.kept_bytes == {}
    assert traced == [None]


def test_no_limit_reported_means_full_and_traces_nothing(monkeypatch):
    probe, params, shardings, state = _cell(layers=2, chips=1)
    monkeypatch.setattr(ac, "saved_bytes", None)     # a call would raise
    choice = ac.choose(probe, params, shardings, jnp.bfloat16,
                       limit_bytes=0, held_bytes=state)
    assert choice.rung == "full" and choice.headroom_bytes is None
    # nor where what the device holds already passes its limit
    choice = ac.choose(probe, params, shardings, jnp.bfloat16,
                       limit_bytes=state, held_bytes=state)
    assert choice.rung == "full" and choice.headroom_bytes < 0


# --------------------------------------------------------------------------- #
# the engine
# --------------------------------------------------------------------------- #
BATCH = {"tokens": np.zeros((8, 33), np.int32)}   # one row a virtual device


def _engine(cfg, limit=0, extra=None):
    mesh_lib.set_mesh(None)
    engine, *_ = dst.initialize(
        model=llama.model_spec(cfg),
        config={"train_batch_size": 8, "bf16": {"enabled": True},
                "zero_optimization": {"stage": 3}, "steps_per_print": 0,
                "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
                "telemetry": {"compile": {"enabled": True}},
                **(extra or {})},
        rng=jax.random.PRNGKey(0))
    if limit:
        engine.telemetry.memory.snapshot = lambda: {
            "bytes_in_use": 0.0, "peak_bytes": 0.0,
            "bytes_limit": float(limit), "source": "allocator"}
    return engine


def _step_text(engine):
    batch = engine._shard_batch(BATCH, with_gas_dim=True)
    engine._build_train_step()
    engine._remat_for(batch)
    return engine._train_step.lower(
        engine.state, batch, engine._lr_override).as_text()


TINY = llama.LlamaConfig.tiny(remat=True, use_pipeline=False)


def test_cpu_mesh_lowers_the_full_remat_program():
    """No limit reported (the CPU mesh): the rung is ``full`` and the
    lowered step is the one an explicit ``remat_policy="full"`` lowers."""
    chosen = _engine(TINY)
    text = _step_text(chosen)
    assert chosen._remat_choice.rung == "full"
    assert chosen._remat_choice.headroom_bytes is None
    named = _engine(dataclasses.replace(TINY, remat_policy="full"))
    assert text == _step_text(named)
    assert named._remat_choice is None


@pytest.mark.parametrize("limit, rung", [
    (10 ** 9, "save_big_matmuls"), (790_000, "save_big_matmuls"),
    (785_000, "full")])  # the rung's 65 536 bytes fit from 788 506 up
def test_engine_chooses_from_the_memory_it_is_given(limit, rung):
    engine = _engine(TINY, limit=limit)
    engine.train_batch(BATCH)
    choice = engine._remat_choice
    assert choice.rung == rung == ac.default_policy(), choice
    assert ac.last_choice() is choice and choice.fallbacks == 0
    values = engine.telemetry.train_values
    assert values["Train/remat/rung"] == ac.LADDER.index(rung)
    assert values["Train/remat/kept_bytes"] == choice.kept_bytes.get(rung, 0)
    assert values["Train/remat/saved_bytes_save_big_matmuls"] == 65536
    assert values["Train/remat/headroom_bytes"] == choice.headroom_bytes
    assert values["Train/remat/predicted_peak_bytes"] == \
        choice.predicted_peak_bytes > 0
    assert values["Train/remat/compiled_peak_bytes"] == \
        choice.compiled_peak_bytes > 0
    assert values["Train/remat/fallbacks"] == 0
    assert not schema.validate_events(
        [(name, value, 1) for name, value in values.items()])
    # one compile of the step, and the choice is made once
    engine.train_batch(BATCH)
    assert engine.telemetry.compile.summary()["train_step"]["compiles"] == 1
    assert engine._remat_choice is choice


def test_gradient_accumulation_counts_its_accumulator():
    """Under GAS the probe sees ONE micro-batch of one device, and the step
    holds the fp32 accumulator in the gradients' layout besides."""
    plain = _engine(TINY, limit=10 ** 9)
    plain.train_batch(BATCH)
    gas = _engine(TINY, limit=10 ** 9, extra={
        "train_batch_size": 16, "gradient_accumulation_steps": 2})
    out = gas.train_batch({"tokens": np.zeros((16, 33), np.int32)})
    assert np.isfinite(float(out.loss))
    a, b = plain._remat_choice, gas._remat_choice
    assert b.rung == "save_big_matmuls" and b.kept_bytes == a.kept_bytes
    assert b.step_bytes - a.step_bytes == ac.device_bytes(
        gas.state.params, gas._grad_shardings, jnp.float32)


@pytest.mark.parametrize("named", ["family", "engine"])
def test_a_named_policy_is_taken_verbatim(named):
    if named == "family":
        engine = _engine(dataclasses.replace(
            TINY, remat_policy="save_attn_out"), limit=10 ** 9)
    else:
        engine = _engine(TINY, limit=10 ** 9, extra={
            "activation_checkpointing": {"policy": "save_attn_out"}})
        assert ac.default_policy() == "save_attn_out"
    text = _step_text(engine)
    assert engine._remat_choice is None and ac.last_choice() is None
    assert not any(name.startswith("Train/remat/")
                   for name in engine.telemetry.train_values)
    # and it is that policy's program, not a rung's
    ac.reset()
    pinned = _engine(dataclasses.replace(TINY, remat_policy="save_attn_out"))
    assert text == _step_text(pinned)


def test_a_later_engine_inherits_neither_a_choice_nor_a_name():
    first = _engine(TINY, limit=10 ** 9)
    first.train_batch(BATCH)
    assert ac.default_policy() == "save_big_matmuls"
    _engine(llama.LlamaConfig.tiny(use_pipeline=False))
    assert ac.default_policy() == "full" and ac.last_choice() is None
    # nor what an earlier engine was NAMED: a family with no probe would
    # take it through remat_block
    _engine(TINY, extra={"activation_checkpointing": {"policy": "dots"}})
    assert ac.default_policy() == "dots"
    _engine(TINY)
    assert ac.default_policy() == "full"


LONG = {"tokens": np.zeros((8, 65), np.int32)}


def test_each_batch_signature_has_its_own_rung():
    """Kept bytes grow with the sequence: the rung a short curriculum
    bucket holds is not the long bucket's, and each bucket's lowering is
    gated and lowered under its OWN rung, whatever ran before."""
    engine = _engine(TINY, limit=850_000)  # short 125 109 spare, long < 0
    engine.train_batch(BATCH)
    short = engine._remat_choice
    assert short.rung == "save_big_matmuls" == ac.default_policy()
    gated = []
    first_lowering = engine._first_lowering
    engine._first_lowering = lambda batch, run: (
        gated.append(ac.default_policy()), first_lowering(batch, run))[1]
    engine.train_batch(LONG)
    long = engine._remat_choice
    assert long is not short and long.rung == "full" == ac.default_policy()
    assert long.step_bytes > short.step_bytes and gated == ["full"]
    assert engine.telemetry.train_values["Train/remat/rung"] == \
        ac.LADDER.index("full")
    # back in the short bucket: its rung again, its program, no compile
    engine.train_batch(BATCH)
    assert engine._remat_choice is short
    assert ac.default_policy() == "save_big_matmuls" and len(gated) == 1
    assert engine.telemetry.compile.summary()["train_step"]["compiles"] == 2
    # and each bucket's program is its rung's
    for data, policy in ((BATCH, "save_big_matmuls"), (LONG, "full")):
        batch = engine._shard_batch(data, with_gas_dim=True)
        engine._remat_for(batch)
        text = engine._train_step.lower(
            engine.state, batch, engine._lr_override).as_text()
        ac.reset()
        pinned = _engine(dataclasses.replace(TINY, remat_policy=policy))
        pinned._build_train_step()
        assert text == pinned._train_step.lower(
            pinned.state, batch, pinned._lr_override).as_text(), policy


def test_the_rung_is_chosen_before_anything_lowers_the_step():
    """The flops profiler lowers the step ahead of the first dispatch (and
    JAX caches that trace): the rung must be in place by then."""
    engine = _engine(TINY, limit=10 ** 9, extra={
        "flops_profiler": {"enabled": True}})
    lowered = []
    estimate = engine._estimate_step_flops
    engine._estimate_step_flops = lambda batch: (
        lowered.append(ac.default_policy()), estimate(batch))[1]
    engine.train_batch(BATCH)
    assert lowered == ["save_big_matmuls"]
    batch = engine._shard_batch(BATCH, with_gas_dim=True)
    text = engine._train_step.lower(
        engine.state, batch, engine._lr_override).as_text()
    ac.reset()
    assert text == _step_text(_engine(dataclasses.replace(
        TINY, remat_policy="save_big_matmuls")))
    assert text != _step_text(_engine(dataclasses.replace(
        TINY, remat_policy="full")))


def test_processes_of_one_job_agree(monkeypatch):
    """Every host lowers the same program: the choice is made from the
    LARGEST count of held bytes and the smallest limit any process reports,
    and a compile one process loses is a fall back for all of them."""
    from jax.experimental import multihost_utils

    peers = {"held": 0, "limit": 10 ** 9, "failed": 0}
    gathered = []

    def allgather(x):
        gathered.append(np.asarray(x).tolist())
        other = [peers["failed"]] if len(x) == 1 \
            else [peers["held"], -peers["limit"]]
        return np.stack([np.asarray(x), np.asarray(other, np.int64)])

    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather", allgather)
    # a peer that holds more: its count decides, here against the rung
    peers["held"] = 10 ** 9
    engine = _engine(TINY, limit=10 ** 9)
    engine._build_train_step()
    batch = engine._shard_batch(BATCH, with_gas_dim=True)
    assert engine._remat_for(batch)
    assert engine._remat_choice.rung == "full"
    assert engine._remat_choice.held_bytes == 10 ** 9
    # a peer with a smaller limit
    peers.update(held=0, limit=785_000)
    engine = _engine(TINY, limit=10 ** 9)
    engine._build_train_step()
    assert engine._remat_for(batch)
    assert engine._remat_choice.rung == "full"
    assert engine._remat_choice.limit_bytes == 785_000
    # peers that agree with us; then one of them loses its compile
    peers.update(limit=10 ** 9, failed=1)
    engine = _engine(TINY, limit=10 ** 9)
    engine._build_train_step()
    assert engine._remat_for(batch)
    choice = engine._remat_choice
    assert choice.rung == "save_big_matmuls"
    ran = []
    engine._first_lowering(batch, lambda: ran.append(ac.default_policy()))
    assert ran == ["full"] and choice.rung == "full" == ac.default_policy()
    assert choice.fallbacks == 1 and gathered[-1] == [0]


def test_resource_exhausted_compile_falls_back_to_full():
    engine = _engine(TINY, limit=10 ** 9)
    calls = []

    def out_of_memory(*args, **kwargs):
        calls.append(ac.default_policy())
        raise jax.errors.JaxRuntimeError(
            "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
            "of memory in memory space hbm.")

    engine._train_step = out_of_memory
    out = engine.train_batch(BATCH)
    assert calls == ["save_big_matmuls"]
    assert np.isfinite(float(out.loss))
    choice = engine._remat_choice
    assert choice.rung == "full" == ac.default_policy()
    assert choice.fallbacks == 1
    assert engine.telemetry.train_values["Train/remat/fallbacks"] == 1
    assert engine.telemetry.train_values["Train/remat/rung"] == \
        ac.LADDER.index("full")
    # the step that ran is the full-remat program
    full = _engine(dataclasses.replace(TINY, remat_policy="full"))
    batch = engine._shard_batch(BATCH, with_gas_dim=True)
    assert engine._train_step.lower(
        engine.state, batch, engine._lr_override).as_text() == \
        _step_text(full)


def test_other_errors_and_a_full_rung_are_not_retried():
    engine = _engine(TINY, limit=10 ** 9)

    def broken(*args, **kwargs):
        raise jax.errors.JaxRuntimeError("INTERNAL: something else")

    engine._train_step = broken
    with pytest.raises(jax.errors.JaxRuntimeError, match="INTERNAL"):
        engine.train_batch(BATCH)
    assert engine._remat_choice.fallbacks == 0
