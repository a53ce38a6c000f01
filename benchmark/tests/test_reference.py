"""Both plain references against the program's models at a tiny size."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=128,
            rope_theta=10000.0, rms_norm_eps=1e-5, tie_word_embeddings=False,
            num_local_experts=4, num_experts_per_tok=2)


@pytest.mark.parametrize("family,options", [("mistral", {}),
                                            ("mixtral", {"drop_tokens": False})])
def test_reference_agrees_with_the_program_in_float32(family, options):
    fam = importlib.import_module(f"benchmark.families.{family}")
    ref = importlib.import_module(f"benchmark.reference.{fam.REFERENCE}")
    cfg = fam.build_cfg(TINY, **options)
    module = fam.module()
    params = module.init(cfg, jax.random.PRNGKey(0))
    rows = np.random.default_rng(0).integers(0, 256, (2, 33))
    with jax.default_matmul_precision("highest"):
        out = module.apply(cfg, params, jnp.asarray(rows[:1, :-1]),
                           compute_dtype=jnp.float32)
    want = (out[0] if isinstance(out, tuple) else out)[0]
    got = ref.logits(TINY, fam.Weights(params), rows[0, :-1])
    # float32 both sides, another order of operations: 1e-5 of unit logits
    assert float(jnp.abs(got - want).max()) < 1e-4
    _, aux = module.loss_fn(cfg, params, {"tokens": jnp.asarray(rows)},
                            compute_dtype=jnp.float32)
    lm = float(aux.get("lm_loss", aux["loss"]))
    assert float(ref.loss(TINY, fam.Weights(params), rows)) == \
        pytest.approx(lm, abs=1e-4)


def test_mixtral_reference_routes_two_experts_with_renormalised_weights():
    from benchmark.reference import mixtral

    x = jax.random.normal(jax.random.PRNGKey(1), (5, 64))
    w = {"attn_norm": jnp.ones(64), "ffn_norm": jnp.ones(64),
         "q": jnp.zeros((64, 64)), "k": jnp.zeros((64, 32)),
         "v": jnp.zeros((64, 32)), "o": jnp.zeros((64, 64)),
         "router": jax.random.normal(jax.random.PRNGKey(2), (64, 4))}
    frozen = tuple(sorted((k, v) for k, v in TINY.items()
                          if isinstance(v, (int, float, bool, str))))
    _, y, dense, margin = mixtral._attention_and_route(x, w, frozen)
    assert np.allclose(np.asarray(dense.sum(-1)), 1.0, atol=1e-6)
    assert (np.asarray(dense) > 0).sum(-1).tolist() == [2] * 5
    # the margin is the second router logit's lead over the third
    ranked = np.sort(np.asarray(y @ w["router"]), axis=-1)
    assert np.allclose(np.asarray(margin), ranked[:, -2] - ranked[:, -3],
                       atol=1e-5)


@pytest.mark.parametrize("family,finite", [("mistral", False),
                                           ("mixtral", True)])
def test_logits_and_margin_gives_the_same_logits_and_one_margin_a_position(
        family, finite):
    fam = importlib.import_module(f"benchmark.families.{family}")
    ref = importlib.import_module(f"benchmark.reference.{fam.REFERENCE}")
    options = {"drop_tokens": False} if family == "mixtral" else {}
    params = fam.module().init(fam.build_cfg(TINY, **options),
                               jax.random.PRNGKey(0))
    row = np.random.default_rng(1).integers(0, 256, 24)
    got, margin = ref.logits_and_margin(TINY, fam.Weights(params), row)
    assert np.array_equal(np.asarray(got), np.asarray(
        ref.logits(TINY, fam.Weights(params), row)))
    assert margin.shape == (24,) and bool((margin > 0).all())
    assert bool(np.isfinite(np.asarray(margin)).all()) == finite
