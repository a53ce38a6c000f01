"""Llama model correctness on CPU (reference model idea: ``tests/unit/simple_model.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import llama
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing as ac


@pytest.fixture(scope="module")
def tiny():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return cfg, params


def test_forward_shapes(tiny):
    cfg, params = tiny
    tokens = jnp.zeros((2, 16), jnp.int32)
    logits = llama.apply(cfg, params, tokens, compute_dtype=jnp.float32)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    assert bool(jnp.isfinite(logits).all())


def test_causality(tiny):
    """Changing a future token must not change past logits."""
    cfg, params = tiny
    rng = jax.random.PRNGKey(1)
    t1 = jax.random.randint(rng, (1, 16), 0, cfg.vocab_size)
    t2 = t1.at[0, 10].set((t1[0, 10] + 1) % cfg.vocab_size)
    l1 = llama.apply(cfg, params, t1, compute_dtype=jnp.float32)
    l2 = llama.apply(cfg, params, t2, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(l1[0, :10]), np.asarray(l2[0, :10]),
                               rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(l1[0, 10:]), np.asarray(l2[0, 10:]))


def test_loss_decreases_under_sgd(tiny):
    """Walking-skeleton convergence check (reference compares loss trends, not
    golden files — tests/unit/simple_model.py style)."""
    cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 33), 0, cfg.vocab_size)
    batch = {"tokens": tokens}

    @jax.jit
    def step(params):
        (loss, _), grads = jax.value_and_grad(
            lambda p: llama.loss_fn(cfg, p, batch, compute_dtype=jnp.float32),
            has_aux=True)(params)
        params = jax.tree.map(lambda p, g: p - 0.5 * g, params, grads)
        return params, loss

    losses = []
    for _ in range(10):
        params, loss = step(params)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.9, losses


def test_label_masking(tiny):
    cfg, params = tiny
    tokens = jnp.ones((1, 8), jnp.int32)
    labels = jnp.full((1, 8), -100, jnp.int32)
    labels = labels.at[0, 3].set(5)
    loss, aux = llama.loss_fn(cfg, params, {"tokens": tokens, "labels": labels},
                              compute_dtype=jnp.float32)
    assert int(aux["ntokens"]) == 1
    assert bool(jnp.isfinite(loss))


def test_tied_embeddings():
    cfg = llama.LlamaConfig.tiny(tie_embeddings=True)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    assert "lm_head" not in params
    logits = llama.apply(cfg, params, jnp.zeros((1, 4), jnp.int32),
                         compute_dtype=jnp.float32)
    assert logits.shape == (1, 4, cfg.vocab_size)


@pytest.mark.parametrize("rung", [*ac.LADDER, "none", "save_attn_out"])
def test_remat_matches_no_remat(rung):
    """Every rung of the ladder the engine chooses from (``remat: true``, no
    policy named: the registry's default decides), and each candidate the
    A/B turned down, gives the loss and the gradients of the
    un-rematerialized model."""
    cfg = llama.LlamaConfig.tiny()
    cfg_remat = llama.LlamaConfig.tiny(remat=True)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 17), 0, cfg.vocab_size)

    def loss(c, p):
        return llama.loss_fn(c, p, {"tokens": tokens}, compute_dtype=jnp.float32)[0]

    l1, g1 = jax.value_and_grad(lambda p: loss(cfg, p))(params)
    ac.configure(choice=ac.RematChoice(rung=rung))
    try:
        l2, g2 = jax.value_and_grad(lambda p: loss(cfg_remat, p))(params)
    finally:
        ac.reset()
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6), g1, g2)


def test_param_count_accounting():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    actual = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    assert actual == cfg.num_params
