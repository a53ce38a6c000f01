"""OLMoE-1B-7B through ``models/mixtral.py`` (ISSUE 26) against the benchmark's
plain reference (``benchmark/reference/olmoe.py`` - the one reference, not a
copy) at a small size on the CPU: the full forward, the loss, and prefill
then decode through the dense and the paged cache, at every served position.
Also: with the whole-projection QK-norm off, every program of the family
traces to the parent's jaxpr.
"""

import dataclasses
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import olmoe as family
from benchmark.reference import olmoe as reference
from benchmark.reference import olmoe_variants
from deepspeed_tpu.models import llama, mixtral

# OLMoE's published keys at a toy size (8 experts, 4 a token, MHA)
TINY = dict(attention_bias=False, clip_qkv=None, hidden_act="silu",
            hidden_size=64, intermediate_size=32,
            max_position_embeddings=128, model_type="olmoe",
            norm_topk_prob=False, num_attention_heads=4, num_experts=8,
            num_experts_per_tok=4, num_hidden_layers=2,
            num_key_value_heads=4, rms_norm_eps=1e-5, rope_scaling=None,
            rope_theta=10000, tie_word_embeddings=False, vocab_size=256)
OPTIONS = dict(drop_tokens=False, norm_topk_prob=False, qk_proj_norm=True)
PROMPT, CHUNK, STEPS, BLOCK = 21, 8, 6, 4     # three chunks, the last short
PATHS = ("apply", "apply_cached", "apply_paged")


def build(dtype=jnp.float32, **options):
    """The configuration, seeded random weights in ``dtype`` - the norms'
    weights too, which ``init`` leaves at one: a norm whose weight went
    unused, or met the wrong width, would otherwise pass - and a row of
    tokens, prompt and answer."""
    cfg = family.build_cfg(TINY, **{**OPTIONS, **options})
    params = mixtral.init(cfg, jax.random.PRNGKey(0))
    layers = params["layers"]
    for i, name in enumerate(("attn_norm", "mlp_norm", "q_norm", "k_norm")):
        if name in layers:
            layers[name] = 1.0 + 0.2 * jax.random.normal(
                jax.random.PRNGKey(10 + i), layers[name].shape)
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    row = np.random.default_rng(0).integers(0, 256, PROMPT + STEPS)
    return cfg, params, row


def pieces(row):
    """``(start, tokens)`` of each call: the prompt by chunks, then single
    tokens."""
    cuts = list(range(0, PROMPT, CHUNK)) + list(range(PROMPT, len(row)))
    return [(a, row[a:b]) for a, b in zip(cuts, cuts[1:] + [len(row)])]


def program_logits(path, cfg, params, row, dtype=jnp.float32):
    """Logits ``[len(row), vocab]`` of the program along ``path``: one full
    forward, or the row fed through a cache piece by piece (the paged path
    pads each piece to the chunk and masks the padding, as the engine
    does)."""
    if path == "apply":
        return mixtral.apply(cfg, params, jnp.asarray(row[None]),
                             compute_dtype=dtype)[0][0]
    out = []
    # a jit of this call's own: one compile a shape, not one a piece
    if path == "apply_cached":
        cached = jax.jit(functools.partial(mixtral.apply_cached, cfg,
                                           compute_dtype=dtype))
        cache = mixtral.init_cache(cfg, 1, 32, dtype=dtype)
        for start, piece in pieces(row):
            logits, cache = cached(params, jnp.asarray(piece[None]), cache,
                                   jnp.asarray([start], jnp.int32))
            out.append(logits[0])
        return jnp.concatenate(out)
    paged = jax.jit(functools.partial(mixtral.apply_paged, cfg,
                                      compute_dtype=dtype))
    cache = mixtral.init_paged_cache(cfg, 16, BLOCK, dtype=dtype)
    table = jnp.asarray([[3, 1, 7, 2, 9, 4, 5, 0]], jnp.int32)  # 0: trash
    for start, piece in pieces(row):
        width = CHUNK if start < PROMPT else 1
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(piece)] = piece
        logits, cache = paged(params, jnp.asarray(padded), cache, table,
                              jnp.asarray([start], jnp.int32),
                              valid=jnp.arange(width)[None] < len(piece))
        out.append(logits[0, :len(piece)])
    return jnp.concatenate(out)


def gap(a, b):
    return float(jnp.abs(a - b).max())


@pytest.fixture(scope="module")
def f32():
    cfg, params, row = build()
    with jax.default_matmul_precision("highest"):
        want = reference.logits(TINY, family.Weights(params), row)
    return cfg, params, row, want


@pytest.mark.parametrize("path", PATHS)
def test_program_agrees_with_the_plain_reference_in_float32(f32, path,
                                                            one_device):
    cfg, params, row, want = f32
    with jax.default_matmul_precision("highest"):
        got = program_logits(path, cfg, params, row)
    # float32 on both sides in another order of operations: 1e-5 of
    # unit-variance logits; a wrong norm or gate moves them by 1e-2 and more
    assert got.shape == want.shape and gap(got, want) < 1e-4


def test_loss_agrees_with_the_plain_reference(f32):
    cfg, params, _, _ = f32
    rows = np.random.default_rng(1).integers(0, 256, (2, 33))
    _, aux = mixtral.loss_fn(cfg, params, {"tokens": jnp.asarray(rows)},
                             compute_dtype=jnp.float32)
    want = reference.loss(TINY, family.Weights(params), rows)
    assert float(aux["lm_loss"]) == pytest.approx(float(want), abs=1e-4)


@pytest.mark.parametrize("variant", olmoe_variants.NAMES)
def test_a_wrong_norm_or_gate_fails_the_tolerance(f32, variant):
    """The per-head norm (``llama.py``'s kind) in place of the whole-
    projection norm, renormalised gates in place of raw ones, and experts
    that meet another expert's weights each lie a hundred times beyond what
    the program, along every path, is held to above."""
    _, params, row, want = f32
    with jax.default_matmul_precision("highest"):
        wrong = olmoe_variants.logits(variant, TINY, family.Weights(params),
                                      row)
    assert gap(wrong, want) > 1e-2


def test_the_program_itself_with_renormalised_gates_or_no_norm_fails(f32):
    cfg, params, row, want = f32
    unnormed = {**params, "layers": {
        k: v for k, v in params["layers"].items()
        if k not in ("q_norm", "k_norm")}}    # the leaves switch the norm
    for wrong_cfg, wrong_params in (
            (dataclasses.replace(cfg, norm_topk_prob=True), params),
            (dataclasses.replace(cfg, qk_proj_norm=False), unnormed)):
        with jax.default_matmul_precision("highest"):
            got = program_logits("apply", wrong_cfg, wrong_params, row)
        assert gap(got, want) > 1e-2


def test_bfloat16_through_the_paged_cache_stays_near_the_reference(one_device):
    """The served precision. bf16 keeps 8 bits of mantissa: every matmul
    input and every stored activation is rounded to 0.4 %, and unit-variance
    logits that pass 2 layers of such roundings differ from the float32
    reference's by 0.0068 on average and by 0.052 at most (measured here,
    the same along all three paths). Held: a mean under 0.02 and no logit
    over 0.15, three times what bf16 gives. The nearest wrong variant,
    renormalised gates, lies at 0.086 / 0.61 (per-head norm 0.196 / 1.52,
    rolled experts 0.51 / 3.2), four times beyond either limit; the same
    program in float32 lies 1e-5 off (the test above), so arithmetic
    coarser than bf16 - an 8-bit matmul rounds 16 times as hard - fails
    too. A router flip (bf16 ordering the 4th and 5th probability the other
    way) would show in the largest difference first; none happens on this
    row."""
    cfg, params, row = build(jnp.bfloat16)
    weights = family.Weights(params)      # the reference widens the same
    got = program_logits("apply_paged", cfg, params, row, jnp.bfloat16)
    with jax.default_matmul_precision("highest"):
        want = reference.logits(TINY, weights, row)
        wrong = {v: olmoe_variants.logits(v, TINY, weights, row)
                 for v in olmoe_variants.NAMES}
    mean = lambda a, b: float(jnp.abs(a - b).mean())
    assert mean(got, want) < 0.02 and gap(got, want) < 0.15
    for variant, logits in wrong.items():
        assert mean(got, logits) > 0.06 and gap(got, logits) > 0.45, variant


def test_moe_rows_are_the_layers_static_shapes(one_device):
    cfg = family.build_cfg(TINY, **OPTIONS)
    # 16 rows: 16 x 4 routed; the grouped bank computes a 16-row tile of
    # each of the 8 experts (every one is reached: 1 - 2^-16)
    assert mixtral.moe_rows(cfg, 16) == {"moe_rows_routed": 64,
                                         "moe_rows_computed": 128,
                                         "moe_row_tile": 16}
    # 256 rows, 64 an expert: a 256-row tile of each, of which the kernel
    # computes the 128-row sub-tile in use (the slabs: 8 x 256)
    wide = mixtral.MixtralConfig(num_experts=8, top_k=2)
    assert mixtral.moe_rows(wide, 256) == {"moe_rows_routed": 512,
                                           "moe_rows_computed": 1024,
                                           "moe_row_tile": 256}
    assert not hasattr(llama, "moe_rows")        # a dense family has none


def test_the_qk_norm_leaves_are_as_wide_as_the_projections():
    cfg = family.build_cfg({**TINY, "num_key_value_heads": 2}, **OPTIONS)
    shapes = jax.eval_shape(lambda k: mixtral.init(cfg, k),
                            jax.random.PRNGKey(0))["layers"]
    assert shapes["q_norm"].shape == (2, 64) and \
        shapes["k_norm"].shape == (2, 32)
    axes = mixtral.param_logical_axes(cfg)["layers"]
    assert axes["q_norm"] == ("layers", "heads") and \
        axes["k_norm"] == ("layers", "kv_heads")
    off = mixtral.MixtralConfig.tiny()
    assert "q_norm" not in mixtral.param_logical_axes(off)["layers"]
    assert "q_norm" not in jax.eval_shape(
        lambda k: mixtral.init(off, k), jax.random.PRNGKey(0))["layers"]


# --- with the norm off, the family's programs are the parent's ------------- #
def _text(fn, *args) -> str:
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))


def program_texts(module, cfg):
    """The jaxpr of each program of a family at ``cfg``, on shapes alone."""
    shape = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda k: module.init(cfg, k),
                            jax.random.PRNGKey(0))
    tokens, one = shape((2, 8), jnp.int32), shape((2, 1), jnp.int32)
    lens, table = shape((2,), jnp.int32), shape((2, 4), jnp.int32)
    dense = jax.eval_shape(lambda: module.init_cache(cfg, 2, 16))
    paged = jax.eval_shape(lambda: module.init_paged_cache(cfg, 8, 4))
    grad = jax.grad(lambda p, t: module.loss_fn(cfg, p, {"tokens": t})[0])
    return {
        "apply": _text(lambda p, t: module.apply(cfg, p, t), params, tokens),
        "loss_grad": _text(grad, params, tokens),
        "apply_cached": _text(
            lambda p, t, c, n: module.apply_cached(cfg, p, t, c, n),
            params, tokens, dense, lens),
        "apply_paged": _text(
            lambda p, t, c, b, n: module.apply_paged(cfg, p, t, c, b, n),
            params, tokens, paged, table, lens),
        "apply_paged_decode": _text(
            lambda p, t, c, b, n: module.apply_paged(cfg, p, t, c, b, n),
            params, one, paged, table, lens),
    }


FAMILIES = {
    "mixtral": lambda: (mixtral, mixtral.MixtralConfig.tiny(
        drop_tokens=False)),
    "qwen2_moe": lambda: (mixtral, mixtral.MixtralConfig.tiny(
        attention_bias=True, norm_topk_prob=False,
        shared_expert_intermediate_size=32, remat=True)),
    "mistral": lambda: (llama, llama.LlamaConfig.tiny()),
}


def program_hashes(one_device=True):
    """Under a one-device process mesh, as on a one-chip host: where the
    serving forwards of a MoE family take the grouped form. Else over this
    directory's eight virtual devices, where they keep the slabs."""
    from deepspeed_tpu.comm import mesh as mesh_lib

    before = mesh_lib._global_mesh
    mesh_lib.set_mesh(None)
    if one_device:
        mesh_lib.init_mesh({"data": 1}, devices=jax.devices()[:1])
    try:
        return {f"{name}.{program}":
                hashlib.sha256(text.encode()).hexdigest()[:16]
                for name, make in FAMILIES.items()
                for program, text in program_texts(*make()).items()}
    finally:
        mesh_lib.set_mesh(before)


# ``program_hashes()`` under this directory's conftest: the dense and the
# training programs are those of the commit before ISSUE 26 (45ecee2); the six
# ``apply_paged`` lines are ISSUE 29's, re-taken on its finished tree (the
# pools became the layer scan's carry). ISSUE 41 re-took the six lines of
# the MoE families' SERVING forwards on its finished tree (the grouped form,
# over the stacked banks: ``apply_cached``, ``apply_paged`` and its decode, of
# ``mixtral`` and ``qwen2_moe``); the four training lines (``apply`` and
# ``loss_grad``: one layer's bank, the slabs, with ``drop_tokens`` False as
# with True) and the five ``mistral.*`` are as they were. A PR that means to change one of these programs replaces its line; one
# that does not has changed it by accident.
PARENT_HASHES = {
    "mistral.apply": "18726a070296e819",
    "mistral.apply_cached": "26908a8864698d2d",
    "mistral.apply_paged": "75f76ae051c1e560",
    "mistral.apply_paged_decode": "ffe8c14146072ee0",
    "mistral.loss_grad": "6d328cc674130e50",
    "mixtral.apply": "9dda8853ea9faa33",
    "mixtral.apply_cached": "5156a5e9fc412514",
    "mixtral.apply_paged": "7ff9f441c4f232a2",
    "mixtral.apply_paged_decode": "a4a88501885ab542",
    "mixtral.loss_grad": "0bf2200b1c25dbf1",
    "qwen2_moe.apply": "85641834200bb56f",
    "qwen2_moe.apply_cached": "e69ce3f99443956b",
    "qwen2_moe.apply_paged": "707a9cf1470fc306",
    "qwen2_moe.apply_paged_decode": "7ae307f800f9e4aa",
    "qwen2_moe.loss_grad": "e4872d8dfc0a6a34",
}

# The six serving lines as ISSUE 41's parent (166de2b) had them: what a
# process whose mesh spans devices still traces (the slabs, the bank a
# scanned input as before - the grouped matmul is one device's kernel).
OVER_A_MESH = {
    "mixtral.apply_cached": "8a284dcc95d1bdc8",
    "mixtral.apply_paged": "c7cd0c20ba4f3634",
    "mixtral.apply_paged_decode": "752dc3cfbdd603e9",
    "qwen2_moe.apply_cached": "d160ec6149557341",
    "qwen2_moe.apply_paged": "8550fe9e4ef9b557",
    "qwen2_moe.apply_paged_decode": "d8bce010d024ebe0",
}


@pytest.fixture(scope="module")
def hashes():
    return program_hashes()


@pytest.fixture(scope="module")
def mesh_hashes():
    return program_hashes(one_device=False)


@pytest.mark.parametrize("program", sorted(OVER_A_MESH))
def test_over_a_mesh_a_serving_forward_is_the_parents(mesh_hashes, program):
    assert mesh_hashes[program] == OVER_A_MESH[program]
    assert OVER_A_MESH[program] != PARENT_HASHES[program]


def test_off_the_serving_forwards_the_mesh_changes_no_program(mesh_hashes):
    assert {p: h for p, h in mesh_hashes.items() if p not in OVER_A_MESH} \
        == {p: h for p, h in PARENT_HASHES.items() if p not in OVER_A_MESH}


@pytest.mark.parametrize("program", sorted(PARENT_HASHES))
def test_with_the_qk_norm_off_the_program_is_the_parents(hashes, program):
    assert hashes[program] == PARENT_HASHES[program]
