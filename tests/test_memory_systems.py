"""Memory systems: remat policies, tiled compute (ALST), FPDT chunked
attention, engine state offload.

Mirrors the reference's memory-feature tests (activation checkpointing tests
under ``tests/unit/runtime/``, offload_states tests in
``tests/unit/runtime/zero/test_offload_states.py``): correctness is asserted
against the untiled/unchunked computation, not golden files.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.attention import attention
from deepspeed_tpu.runtime.activation_checkpointing import (
    checkpoint, checkpointing, configure, get_policy, reset)
from deepspeed_tpu.sequence.fpdt import fpdt_attention
from deepspeed_tpu.sequence.tiled import (sequence_tiled_compute,
                                          tiled_fused_logits_loss, tiled_mlp)


class TestRematPolicies:
    def test_policies_registered(self):
        for name in ["full", "none", "dots_saveable", "save_names", "offload"]:
            get_policy(name)  # must not raise

    def test_checkpoint_matches_plain(self):
        W = jax.random.normal(jax.random.PRNGKey(0), (16, 16))

        def f(x):
            return jnp.tanh(x @ W).sum()

        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16))
        g_plain = jax.grad(lambda x: f(x))(x)
        g_remat = jax.grad(lambda x: checkpoint(f, x, policy="full"))(x)
        # remat re-associates the fp32 recompute: a few ulps, not equality
        np.testing.assert_allclose(g_plain, g_remat, rtol=1e-5)

    def test_configure_cpu_checkpointing_selects_offload(self):
        cfg = configure(checkpoint_in_cpu=True)
        assert cfg.policy == "offload"
        assert checkpointing.is_configured()
        reset()
        assert not checkpointing.is_configured()

    def test_offload_policy_grads_match(self):
        from jax.ad_checkpoint import checkpoint_name
        W = jax.random.normal(jax.random.PRNGKey(0), (8, 8))

        def f(x):
            h = checkpoint_name(jnp.tanh(x @ W), "residual")
            return (h @ W).sum()

        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8))
        g_plain = jax.grad(f)(x)
        g_off = jax.jit(jax.grad(
            lambda x: checkpoint(f, x, policy="offload")))(x)
        np.testing.assert_allclose(g_plain, g_off, rtol=1e-5, atol=1e-6)


class TestTiledCompute:
    def test_sequence_tiled_matches(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 8))
        fn = lambda t: jax.nn.gelu(t) * 2.0
        out = sequence_tiled_compute(fn, x, shards=4)
        np.testing.assert_allclose(out, fn(x), rtol=1e-6)

    def test_tiled_mlp_matches_and_grads(self):
        key = jax.random.PRNGKey(0)
        W1 = jax.random.normal(key, (8, 32)) * 0.1
        W2 = jax.random.normal(key, (32, 8)) * 0.1
        params = (W1, W2)

        def mlp(p, x):
            return jax.nn.gelu(x @ p[0]) @ p[1]

        x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 8))
        out = tiled_mlp(mlp, params, x, shards=4)
        np.testing.assert_allclose(out, mlp(params, x), rtol=1e-5, atol=1e-6)

        g_t = jax.grad(lambda p: tiled_mlp(mlp, p, x, shards=4).sum())(params)
        g_p = jax.grad(lambda p: mlp(p, x).sum())(params)
        for a, b in zip(jax.tree.leaves(g_t), jax.tree.leaves(g_p)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_tiled_logits_loss_matches_full(self):
        B, S, H, V = 2, 16, 8, 64
        hidden = jax.random.normal(jax.random.PRNGKey(0), (B, S, H))
        W = jax.random.normal(jax.random.PRNGKey(1), (H, V)) * 0.2
        labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)
        labels = labels.at[0, :3].set(-100)  # test ignore_index

        loss_tiled = tiled_fused_logits_loss(hidden, W, labels, shards=4)

        logits = hidden @ W
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.where(labels == -100, 0, labels)[..., None], -1)[..., 0]
        valid = labels != -100
        loss_full = jnp.where(valid, lse - picked, 0.0).sum() / valid.sum()
        np.testing.assert_allclose(loss_tiled, loss_full, rtol=1e-5)

    def test_tiled_logits_loss_grad(self):
        B, S, H, V = 1, 8, 4, 16
        hidden = jax.random.normal(jax.random.PRNGKey(0), (B, S, H))
        W = jax.random.normal(jax.random.PRNGKey(1), (H, V)) * 0.2
        labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, V)

        g_t = jax.grad(lambda h: tiled_fused_logits_loss(h, W, labels,
                                                         shards=2))(hidden)

        def full(h):
            logits = h @ W
            lse = jax.nn.logsumexp(logits, -1)
            picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
            return (lse - picked).mean()

        np.testing.assert_allclose(g_t, jax.grad(full)(hidden),
                                   rtol=1e-4, atol=1e-6)


class TestFPDT:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full_attention(self, causal):
        B, S, H, D = 2, 32, 4, 8
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, H, D))
                   for i in range(3))
        out = fpdt_attention(q, k, v, chunks=4, causal=causal)
        ref = attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    def test_gqa(self):
        B, S, H, D, KV = 1, 16, 8, 4, 2
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, KV, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, KV, D))
        out = fpdt_attention(q, k, v, chunks=2, causal=True)
        ref = attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)

    def test_grads_flow(self):
        B, S, H, D = 1, 16, 2, 4
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, H, D))
                   for i in range(3))
        g = jax.grad(lambda q: fpdt_attention(q, k, v, chunks=4).sum())(q)
        g_ref = jax.grad(lambda q: attention(q, k, v, causal=True).sum())(q)
        np.testing.assert_allclose(g, g_ref, rtol=2e-3, atol=2e-3)

    def test_offload_variant_jits(self):
        B, S, H, D = 1, 16, 2, 4
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, H, D))
                   for i in range(3))
        out = jax.jit(lambda q, k, v: fpdt_attention(
            q, k, v, chunks=2, offload=True))(q, k, v)
        ref = attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, rtol=2e-3, atol=2e-3)


class TestOffloadStates:
    def test_offload_and_reload_roundtrip(self):
        import deepspeed_tpu as dst
        from deepspeed_tpu.runtime.engine import ModelSpec
        from deepspeed_tpu.runtime.offload_states import (
            OffloadStateTypeEnum, offloaded_memory_kinds)

        def loss_fn(params, batch):
            pred = batch["x"] @ params["w"]
            return jnp.mean((pred - batch["y"]) ** 2), {}

        spec = ModelSpec(
            loss_fn=loss_fn,
            init_fn=lambda k: {"w": jax.random.normal(k, (8, 8)) * 0.1},
            pipeline_capable=False)
        config = {
            "train_batch_size": 8,
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2},
        }
        engine, *_ = dst.initialize(model=spec, config=config)
        batch = {"x": np.ones((8, 8), np.float32),
                 "y": np.zeros((8, 8), np.float32)}
        engine.train_batch(batch)

        engine.offload_states()
        kinds = offloaded_memory_kinds(engine.state.opt_state)
        assert kinds <= {"pinned_host"}, kinds
        kinds_p = offloaded_memory_kinds(engine.state.params)
        assert kinds_p <= {"pinned_host"}, kinds_p

        engine.reload_states()
        assert offloaded_memory_kinds(engine.state.params) == {"device"}
        out = engine.train_batch(batch)  # still trains after round trip
        assert np.isfinite(float(out.loss))

    def test_partial_include(self):
        import deepspeed_tpu as dst
        from deepspeed_tpu.runtime.engine import ModelSpec
        from deepspeed_tpu.runtime.offload_states import (
            OffloadStateTypeEnum, offloaded_memory_kinds)

        def loss_fn(params, batch):
            return jnp.mean((batch["x"] @ params["w"]) ** 2), {}

        spec = ModelSpec(loss_fn=loss_fn,
                         init_fn=lambda k: {"w": jnp.ones((4, 4))},
                         pipeline_capable=False)
        config = {"train_batch_size": 8,
                  "optimizer": {"type": "sgd", "params": {"lr": 0.1}}}
        engine, *_ = dst.initialize(model=spec, config=config)

        engine.offload_states(include=[OffloadStateTypeEnum.optim_states])
        assert offloaded_memory_kinds(engine.state.params) == {"device"}
        engine.reload_states()

        # plain strings normalize to the enum
        engine.offload_states(include=["optim_states"])
        assert offloaded_memory_kinds(engine.state.opt_state) <= {"pinned_host"}
        assert offloaded_memory_kinds(engine.state.params) == {"device"}
        engine.reload_states()


def test_offload_states_nvme_tier(tmp_path, devices8):
    """device='nvme' spills through the swap_tensor disk tier and reload
    restores the exact sharded state (reference routes offload_states nvme
    to the partitioned swappers)."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.runtime.engine import ModelSpec

    mesh_lib.set_mesh(None)

    def loss_fn(params, batch):
        pred = batch["x"] @ params["w"]
        return jnp.mean((pred - batch["y"]) ** 2), {}

    spec = ModelSpec(
        loss_fn=loss_fn,
        init_fn=lambda k: {"w": jax.random.normal(k, (8, 8)) * 0.1},
        pipeline_capable=False)
    config = {
        "train_batch_size": 8,
        "optimizer": {"type": "adam", "params": {"lr": 1e-3}},
        "zero_optimization": {
            "stage": 2,
            "offload_optimizer": {"device": "none",
                                  "nvme_path": str(tmp_path)}},
    }
    engine, *_ = dst.initialize(model=spec, config=config)
    batch = {"x": np.ones((8, 8), np.float32),
             "y": np.zeros((8, 8), np.float32)}
    engine.train_batch(batch)
    before = np.asarray(jax.tree.leaves(engine.state.opt_state)[0])
    w_before = np.asarray(engine.state.params["w"])

    engine.offload_states(device="nvme")
    assert list(tmp_path.rglob("*.swp")), "no swap files written"
    # live arrays replaced by metas — nothing array-like left on device
    assert not any(isinstance(l, jax.Array)
                   for l in jax.tree.leaves(engine.state.opt_state))

    engine.reload_states()
    after = np.asarray(jax.tree.leaves(engine.state.opt_state)[0])
    np.testing.assert_array_equal(after, before)
    np.testing.assert_array_equal(np.asarray(engine.state.params["w"]),
                                  w_before)
    out = engine.train_batch(batch)  # still trains after the disk roundtrip
    assert np.isfinite(float(out.loss))


# --------------------------------------------------------------------------- #
# NVMe-STREAMED optimizer step (reference stage3.py:2412 sub-group swap cycle)
# --------------------------------------------------------------------------- #
def test_nvme_streaming_optimizer_parity_and_bounded_memory(tmp_path):
    """Streaming the state through NVMe per sub-group must (a) match the
    non-streamed CPU Adam bit-for-bit-ish, (b) keep peak resident fp32 state
    bounded by ~3 sub-groups — NOT the full state size."""
    from deepspeed_tpu.ops.cpu_optimizer import DeepSpeedCPUAdam
    from deepspeed_tpu.runtime.swap_tensor.streaming_optimizer import (
        NVMeStreamingOptimizer)

    rng = np.random.default_rng(0)
    params = [rng.standard_normal((4096, 16)).astype(np.float32)
              for _ in range(8)]
    ref_params = [p.copy() for p in params]
    opt = NVMeStreamingOptimizer(params, str(tmp_path / "swp"), lr=1e-2,
                                 weight_decay=0.01,
                                 sub_group_size=70_000)  # ~2 leaves/group
    assert len(opt.groups) >= 4
    ref = DeepSpeedCPUAdam(ref_params, lr=1e-2, weight_decay=0.01)
    for _ in range(3):
        grads = [rng.standard_normal(p.shape).astype(np.float32)
                 for p in params]
        out = opt.step([g.copy() for g in grads])
        ref.step([g.copy() for g in grads])
    ps, ms, vs = opt.state_leaves()
    for a, b in zip(ps, ref_params):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    for a, b in zip(ms, ref.exp_avg):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    # bf16 outputs carry the updated values
    from deepspeed_tpu.ops.cpu_optimizer import bf16_to_fp32
    np.testing.assert_allclose(bf16_to_fp32(out[0]), ref_params[0],
                               rtol=1e-2, atol=1e-2)
    # bounded residency: ≤ 3 sub-groups of fp32 state, << total
    total = sum(g.nbytes for g in opt.groups)
    biggest = max(g.nbytes for g in opt.groups)
    assert opt.peak_resident_bytes <= 3 * biggest, (
        opt.peak_resident_bytes, biggest)
    assert opt.peak_resident_bytes < total
    opt.purge()


def test_nvme_streaming_optimizer_resume(tmp_path):
    """state_leaves → load_state_leaves round-trips the NVMe state."""
    from deepspeed_tpu.runtime.swap_tensor.streaming_optimizer import (
        NVMeStreamingOptimizer)

    rng = np.random.default_rng(1)
    params = [rng.standard_normal((64,)).astype(np.float32)
              for _ in range(3)]
    opt = NVMeStreamingOptimizer(params, str(tmp_path / "a"), lr=1e-2,
                                 sub_group_size=64)
    grads = [rng.standard_normal(p.shape).astype(np.float32) for p in params]
    opt.step(grads)
    ps, ms, vs = opt.state_leaves()

    opt2 = NVMeStreamingOptimizer(params, str(tmp_path / "b"), lr=1e-2,
                                  sub_group_size=64)
    opt2.load_state_leaves(ps, ms, vs, step=opt.step_count)
    out1 = opt.step([g.copy() for g in grads])
    out2 = opt2.step([g.copy() for g in grads])
    for a, b in zip(out1, out2):
        np.testing.assert_array_equal(a, b)


def test_engine_nvme_streamed_optimizer_step(tmp_path, devices8):
    """offload_optimizer device=nvme: the engine trains with fp32 masters +
    moments resident on NVMe (streamed per sub-group through the step), loss
    tracking the all-device engine within bf16 tolerance, and peak host
    residency bounded by sub-groups, not total state."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama

    mcfg = llama.LlamaConfig.tiny()
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(5), (8, 33),
                                           0, mcfg.vocab_size))

    def run(extra_zero):
        mesh_lib.set_mesh(None)
        spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
        zero = {"stage": 0}
        zero.update(extra_zero)
        engine, *_ = dst.initialize(
            model=spec,
            config={"train_batch_size": 8,
                    "bf16": {"enabled": True},
                    "gradient_clipping": 1.0,
                    "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
                    "zero_optimization": zero,
                    "steps_per_print": 0},
            rng=jax.random.PRNGKey(3))
        losses = [float(engine.train_batch({"tokens": tokens}).loss)
                  for _ in range(6)]
        return engine, losses

    _, base_losses = run({})
    engine, nvme_losses = run({
        "offload_optimizer": {"device": "nvme",
                              "nvme_path": str(tmp_path)},
        "sub_group_size": 30_000})  # force many sub-groups on the tiny model
    assert nvme_losses[-1] < nvme_losses[0]
    np.testing.assert_allclose(base_losses, nvme_losses, rtol=0.05, atol=0.05)
    opt = engine._nvme_opt
    assert len(opt.groups) >= 3
    total = sum(g.nbytes for g in opt.groups)
    assert opt.peak_resident_bytes <= 3 * max(g.nbytes for g in opt.groups)
    assert opt.peak_resident_bytes < total
    # the state really lives on disk
    files = list((tmp_path / "opt_state").glob("*.swp"))
    assert len(files) == 3 * len(jax.tree.leaves(engine.state.params))


def test_engine_nvme_checkpoint_roundtrip(tmp_path, devices8):
    """save_checkpoint / load_checkpoint must carry the NVMe-resident
    masters + moments: resumed training continues the original trajectory
    instead of resetting to init."""
    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama

    mcfg = llama.LlamaConfig.tiny()
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(6), (8, 33),
                                           0, mcfg.vocab_size))

    def make(swap_sub):
        mesh_lib.set_mesh(None)
        spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
        engine, *_ = dst.initialize(
            model=spec,
            config={"train_batch_size": 8,
                    "bf16": {"enabled": True},
                    "optimizer": {"type": "adamw", "params": {"lr": 5e-3}},
                    "zero_optimization": {
                        "stage": 0,
                        "offload_optimizer": {"device": "nvme",
                                              "nvme_path": str(swap_sub)},
                        "sub_group_size": 30_000},
                    "steps_per_print": 0},
            rng=jax.random.PRNGKey(3))
        return engine

    e1 = make(tmp_path / "swap1")
    for _ in range(3):
        e1.train_batch({"tokens": tokens})
    e1.save_checkpoint(str(tmp_path / "ckpt"))
    cont = [float(e1.train_batch({"tokens": tokens}).loss)
            for _ in range(3)]

    e2 = make(tmp_path / "swap2")  # fresh init — must be overwritten by load
    e2.load_checkpoint(str(tmp_path / "ckpt"))
    assert e2._nvme_opt.step_count == 3
    resumed = [float(e2.train_batch({"tokens": tokens}).loss)
               for _ in range(3)]
    np.testing.assert_allclose(cont, resumed, rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("flag", ["offload_kv", "offload"])
def test_fpdt_offload_numerics_match(devices8, flag):
    """Host-parking (offload_kv: the K/V stream; offload: the forward
    residuals) is a placement change, not a math change: fwd outputs and
    input grads must match the on-device path exactly."""
    from deepspeed_tpu.sequence.fpdt import fpdt_attention

    B, S, H, Hkv, D = 1, 256, 4, 2, 16  # GQA-narrow KV parks narrow
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)

    def loss(q, k, v, **kw):
        out = fpdt_attention(q, k, v, chunks=4, **kw)
        return jnp.sum(out ** 2)

    base = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    host = jax.jit(jax.value_and_grad(
        lambda *a: loss(*a, **{flag: True}), argnums=(0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(float(base[0]), float(host[0]), rtol=1e-6)
    for g0, g1 in zip(base[1], host[1]):
        np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                                   rtol=1e-5, atol=1e-5)


def test_fpdt_peak_memory_scales_linearly_not_quadratically():
    """The chunk pipeline's compiled peak temp must grow ~linearly in S
    (fixed chunk size): dense attention's scores alone would grow 64× for
    8× seq. On CPU the host space is not separate, so this pins the
    chunking bound; the host-tier bound (device KV = O(S/chunks)) shows up
    as S(5)-space buffers on TPU (see test below)."""
    from deepspeed_tpu.sequence.fpdt import fpdt_attention

    B, H, D, c = 1, 4, 64, 512

    def temp_bytes(S):
        chunks = S // c

        def loss(q, k, v):
            out = fpdt_attention(q, k, v, chunks=chunks, offload=True)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        sh = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)
        comp = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            sh, sh, sh).compile()
        return comp.memory_analysis().temp_size_in_bytes

    t1, t8 = temp_bytes(4096), temp_bytes(32768)
    ratio = t8 / t1
    assert ratio < 12, (t1, t8, ratio)  # ~8 = linear; 64 = quadratic


@pytest.mark.skipif(jax.default_backend() != "tpu",
                    reason="memory spaces are only separate on TPU")
def test_fpdt_offload_kv_parks_kv_in_host_space():
    """On TPU, offload_kv must place the full K/V buffers in host space —
    the compiled HLO carries S(5) (host) layout annotations.

    This is the ONE intentionally-skipped test of the CPU tier-1 lane
    (investigated 2026-08: not a rot casualty). The CPU backend compiles
    the same program but XLA:CPU has a single flat memory space — no
    ``S(5)`` annotation ever appears in its HLO, so the assertion is only
    meaningful for the TPU (``tests/test_chip_compile.py`` checks the
    annotation itself against a described chip). The CPU-checkable halves of fpdt offload (numerics,
    saved-residual bytes) are covered by the tests above."""
    from deepspeed_tpu.sequence.fpdt import fpdt_attention

    B, S, H, D = 1, 2048, 4, 64

    def loss(q, k, v):
        return jnp.sum(fpdt_attention(q, k, v, chunks=8,
                                      offload_kv=True) ** 2)

    sh = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)
    comp = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sh, sh, sh).compile()
    assert "S(5)" in comp.as_text()


def test_nvme_h2d_dispatch_interleaves_with_group_stream(tmp_path, monkeypatch):
    """Overlap structure of the streamed step (reference
    pipelined_optimizer_swapper.py:52): the caller's ``on_group`` H2D hook
    for sub-group g fires BEFORE later groups' Adam updates run, so device
    transfers are in flight while the tail of the stream still computes —
    not one bulk transfer after a fully synchronous host step."""
    from deepspeed_tpu.runtime.swap_tensor import streaming_optimizer as so

    leaves = [np.random.default_rng(i).normal(size=(512,)).astype(np.float32)
              for i in range(6)]
    opt = so.NVMeStreamingOptimizer(
        leaves, str(tmp_path / "s"), lr=1e-3, sub_group_size=1024)
    assert len(opt.groups) >= 3
    events = []
    real_adam = so.adam_step_buffers

    def spy_adam(*a, **k):
        events.append("adam")
        return real_adam(*a, **k)

    monkeypatch.setattr(so, "adam_step_buffers", spy_adam)
    grads = [np.ones_like(l) for l in leaves]
    opt.step(grads, out_dtype="float32",
             on_group=lambda ids, outs: events.append(("h2d", tuple(ids))))
    h2d_first = events.index(next(e for e in events if e != "adam"))
    assert h2d_first < len(events) - 1 and "adam" in events[h2d_first + 1:], \
        (events, "no Adam work after the first H2D hook — nothing overlaps")
