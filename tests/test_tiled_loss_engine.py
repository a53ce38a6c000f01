"""Engine wiring for ``sequence.tiled_loss`` (docs/performance.md
"Million-token context"): the fused unembed+CE head must (a) leave the
default train step BYTE-identical when off, (b) match the dense loss_fn's
value and grads exactly when on — per model family, including the
bias-carrying GPT-J-style head — and (c) cut the compiled peak from the
dense [B, S, V] logits cliff to a per-tile slice."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu as dst
from deepspeed_tpu.comm import mesh as mesh_mod
from deepspeed_tpu.models import gptneox, llama, mixtral
from deepspeed_tpu.sequence.tiled import tiled_fused_logits_loss

V = 64


def _llama_cfg():
    return llama.LlamaConfig(vocab_size=V, hidden_size=32,
                             intermediate_size=64, num_layers=2, num_heads=4,
                             num_kv_heads=2, max_seq_len=64)


def _mk_engine(seq=None):
    mesh_mod.set_mesh(None)
    cfg = {"train_batch_size": 8,
           "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
           "zero_optimization": {"stage": 2},
           "steps_per_print": 0, "seed": 7}
    if seq is not None:
        cfg["sequence"] = seq
    spec = llama.model_spec(_llama_cfg(), compute_dtype=jnp.float32)
    engine, *_ = dst.initialize(model=spec, config=cfg)
    return engine


def _batch(seed=0, b=8, s=33):
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(0, V, (b, s)).astype(np.int32)}


def _lowered(e):
    if e._train_step is None:
        e._build_train_step()
    sb = e._shard_batch(_batch(seed=1), with_gas_dim=True)
    with e.mesh_mgr.activate():
        return e._train_step.lower(e.state, sb, e._lr_override).as_text()


# --------------------------------------------------------------------------- #
# default-OFF pin: the knob must be invisible until asked for
# --------------------------------------------------------------------------- #
def test_tiled_loss_default_off_byte_identical(devices8):
    e_def = _mk_engine()                                   # no block at all
    e_off = _mk_engine({"tiled_loss": False})              # explicit off
    e_on = _mk_engine({"tiled_loss": True, "tiled_loss_shards": 4})
    t_def, t_off, t_on = _lowered(e_def), _lowered(e_off), _lowered(e_on)
    assert t_def == t_off          # absent block == disabled block, exactly
    assert t_on != t_def           # the enabled program really is different
    # same data, same seed → the tiled step optimizes the same loss
    b = _batch(seed=2)
    l_def = float(e_def.train_batch(b).loss)
    l_on = float(e_on.train_batch(b).loss)
    assert abs(l_def - l_on) < 1e-5, (l_def, l_on)


# --------------------------------------------------------------------------- #
# per-family value+grad parity of the model-spec tiled_loss_fn
# --------------------------------------------------------------------------- #
def _family_spec(name):
    if name == "llama":
        return llama.model_spec(_llama_cfg(), compute_dtype=jnp.float32)
    if name == "gptneox":  # GPT-J-style head WITH the lm_head bias leg
        cfg = gptneox.GPTNeoXConfig(vocab_size=V, hidden_size=32,
                                    intermediate_size=64, num_layers=2,
                                    num_heads=4, max_seq_len=64,
                                    lm_head_bias=True)
        return gptneox.model_spec(cfg, compute_dtype=jnp.float32)
    cfg = mixtral.MixtralConfig(vocab_size=V, hidden_size=32,
                                intermediate_size=64, num_layers=2,
                                num_heads=4, num_kv_heads=2, num_experts=4,
                                top_k=2, max_seq_len=64)
    return mixtral.model_spec(cfg, compute_dtype=jnp.float32)


@pytest.mark.parametrize("family", ["llama", "gptneox", "mixtral"])
def test_model_tiled_loss_fn_matches_dense(devices8, family):
    spec = _family_spec(family)
    params = spec.init_fn(jax.random.PRNGKey(0))
    batch = _batch(seed=3, b=2, s=17)
    l0, _ = spec.loss_fn(params, batch)
    l1, _ = spec.tiled_loss_fn(params, batch, shards=4)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    g0 = jax.grad(lambda p: spec.loss_fn(p, batch)[0])(params)
    g1 = jax.grad(lambda p: spec.tiled_loss_fn(p, batch, shards=4)[0])(params)
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


def test_tiled_loss_bias_head_parity():
    """The standalone head with a vocab bias (GPT-J lineage): value+grad
    must match the dense biased CE, including ignore_index masking."""
    B, S, H, Vb = 2, 16, 8, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    hidden = jax.random.normal(ks[0], (B, S, H))
    W = jax.random.normal(ks[1], (H, Vb)) * 0.2
    bias = jax.random.normal(ks[2], (Vb,)) * 0.1
    labels = jax.random.randint(ks[3], (B, S), 0, Vb)
    labels = labels.at[0, :3].set(-100)

    def dense(h, w, b):
        logits = h @ w + b
        lse = jax.nn.logsumexp(logits, -1)
        picked = jnp.take_along_axis(
            logits, jnp.where(labels == -100, 0, labels)[..., None],
            -1)[..., 0]
        valid = labels != -100
        return jnp.where(valid, lse - picked, 0.0).sum() / valid.sum()

    def tiled(h, w, b):
        return tiled_fused_logits_loss(h, w, labels, shards=4, bias=b)

    np.testing.assert_allclose(float(tiled(hidden, W, bias)),
                               float(dense(hidden, W, bias)), rtol=1e-5)
    g_t = jax.grad(tiled, argnums=(0, 1, 2))(hidden, W, bias)
    g_d = jax.grad(dense, argnums=(0, 1, 2))(hidden, W, bias)
    for a, b in zip(g_t, g_d):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-6)


# --------------------------------------------------------------------------- #
# memory pin: the tiled head never pays the [B, S, V] fp32 logits cliff
# --------------------------------------------------------------------------- #
def test_tiled_loss_compiled_peak_beats_dense(devices8):
    """The FPDT-pin convention on the loss head: compiled peak temp of
    grad(dense CE) carries the S×V fp32 logits (plus its cotangent) while
    grad(tiled CE) carries S/shards×V — the ratio must show it, and the
    tiled peak must scale ~linearly in S."""
    B, H, Vb, shards = 1, 64, 8192, 8

    def temp_bytes(S, tiled):
        labels = jnp.zeros((B, S), jnp.int32)

        def dense_loss(h, w):
            logits = (h @ w).astype(jnp.float32)
            lse = jax.nn.logsumexp(logits, -1)
            picked = jnp.take_along_axis(logits, labels[..., None],
                                         -1)[..., 0]
            return (lse - picked).mean()

        def tiled_loss(h, w):
            return tiled_fused_logits_loss(h, w, labels, shards=shards)

        fn = tiled_loss if tiled else dense_loss
        sh = jax.ShapeDtypeStruct((B, S, H), jnp.bfloat16)
        sw = jax.ShapeDtypeStruct((H, Vb), jnp.bfloat16)
        comp = jax.jit(jax.grad(fn, argnums=(0, 1))).lower(sh, sw).compile()
        return comp.memory_analysis().temp_size_in_bytes

    S = 2048
    dense_b, tiled_b = temp_bytes(S, False), temp_bytes(S, True)
    assert tiled_b * 3 < dense_b, (dense_b, tiled_b)
    # ~linear in S: 4× the context must not cost ~4×(V/shards) extra
    t4 = temp_bytes(4 * S, True)
    assert t4 / tiled_b < 8, (tiled_b, t4)  # linear ≈ 4, logits cliff ≈ 32
