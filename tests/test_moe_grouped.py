"""The grouped form of a no-drop MoE call (ISSUE 41): the rows the router
sent, in an expert-major tile-aligned order, through ``moe_grouped_matmul``,
against the capacity slabs it takes the place of."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.moe.layer import BANK, MoELayer, init_moe_ffn
from deepspeed_tpu.moe.sharded_moe import (row_groups, row_tile,
                                           top_k_gating_compact)
from deepspeed_tpu.ops import registry
from deepspeed_tpu.ops.pallas import grouped_matmul

HIDDEN, INTER = 32, 48
LAYER = 1       # the layer of the stacked banks that holds a case's weights


# the grouped form runs where the program is one device's
pytestmark = pytest.mark.usefixtures("one_device")


def _stacked(params):
    """``params`` as a serving forward hands them to the layer: the bank's
    leaves the STACKED ``[L, E, ...]`` weights of three layers, the case's
    at ``LAYER`` between two of other values."""
    return {**params, **{
        n: jnp.stack([params[n] * 0.5, params[n], -params[n]])
        for n in BANK}}


def _bank(n_experts, routed=None, shared=False, dtype=jnp.float32):
    params = init_moe_ffn(jax.random.PRNGKey(0), n_experts, HIDDEN, INTER,
                          dtype, routed=routed)
    if shared:
        ks = jax.random.split(jax.random.PRNGKey(7), 4)
        params.update(
            shared_w_gate=jax.random.normal(ks[0], (HIDDEN, 24)) * 0.2,
            shared_w_up=jax.random.normal(ks[1], (HIDDEN, 24)) * 0.2,
            shared_w_down=jax.random.normal(ks[2], (24, HIDDEN)) * 0.2,
            shared_gate=jax.random.normal(ks[3], (HIDDEN, 1)) * 0.2)
    return params


def _steer(params, expert, sign):
    """Every (positive) token's logit for ``expert`` far above (below) the
    others': the expert gets every row (no row)."""
    router = params["router"].at[:, expert].set(sign * 4.0)
    return {**params, "router": router}


# name -> (layer kwargs, rows, the bank's changes)
CASES = {
    "mixtral_8x2_normalised": (dict(n_experts=8, top_k=2), 37, {}),
    "olmoe_64x8_unnormalised": (dict(n_experts=64, top_k=8,
                                     norm_topk=False), 24, {}),
    "held_16_of_128": (dict(n_experts=128, top_k=8, held=(16, 16)), 40, {}),
    "shared_expert": (dict(n_experts=4, top_k=2, norm_topk=False), 19,
                      dict(shared=True)),
    "an_expert_with_no_row": (dict(n_experts=4, top_k=2), 21,
                              dict(steer=(2, -1.0))),
    "an_expert_with_every_row": (dict(n_experts=4, top_k=2), 300,
                                 dict(steer=(1, 1.0))),
    "rows_no_multiple_of_the_tile": (dict(n_experts=2, top_k=1), 45, {}),
    "one_row": (dict(n_experts=8, top_k=2), 1, {}),
}


def _case(name):
    kw, rows, change = CASES[name]
    held = kw.get("held")
    params = _bank(held[1] if held else kw["n_experts"],
                   routed=kw["n_experts"], shared=change.get("shared", False))
    x = jax.random.normal(jax.random.PRNGKey(3), (1, rows, HIDDEN))
    if "steer" in change:
        params, x = _steer(params, *change["steer"]), jnp.abs(x)
    layer = MoELayer(drop_tokens=False, **kw)
    assert layer.grouped()
    # the grouped form is the stacked banks'; one layer's bank builds slabs
    grouped = lambda p, x: layer(_stacked(p), x, layer=LAYER)
    return params, x, grouped, layer


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_grouped_form_equals_the_capacity_form(name):
    """Same gates, same products, summed over the same rows less the rows
    that were zeros: only the order of a token's k-term sum may differ."""
    params, x, grouped, slabs = _case(name)
    with jax.default_matmul_precision("highest"):
        got, aux = jax.jit(grouped)(params, x)
        want, aux_want = jax.jit(slabs)(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-6)
    assert float(aux) == float(aux_want)
    assert float(jnp.abs(want).max()) > 0.01


def test_steered_routers_give_the_groups_their_names_say():
    for name, expert, rows in (("an_expert_with_no_row", 2, 0),
                               ("an_expert_with_every_row", 1, 300)):
        params, x, grouped, _ = _case(name)
        logits = x[0] @ params["router"]
        cg = top_k_gating_compact(logits, 2, drop_tokens=False)
        assert int(cg.counts[expert]) == rows
        assert int(cg.counts.sum()) == 2 * x.shape[1]
    # 300 rows of one expert at a tile of 128: three tiles of its own
    # 300 rows of one expert at a tile of 256: two tiles of its own, the
    # second 44 rows full
    groups = row_groups(cg, row_tile(300, 4, 2, 4, INTER))
    assert groups.tile == 256
    own = np.asarray(groups.tile_expert == 1)[:int(groups.num_tiles)]
    assert own.sum() == 2
    assert sorted(np.asarray(groups.tile_rows)[:int(groups.num_tiles)][own]) \
        == [44, 256]


def test_rows_to_absent_experts_have_no_place_and_add_nothing():
    params, x, grouped, _ = _case("held_16_of_128")
    cg = top_k_gating_compact(x[0] @ params["router"], 8, drop_tokens=False)
    groups = row_groups(cg, 16, held=(16, 16))
    places = groups.source.shape[0]
    absent = np.asarray((cg.topk_idx < 16) | (cg.topk_idx >= 32))
    assert absent.any() and not absent.all()
    assert (np.asarray(groups.place)[absent] == places).all()
    assert (np.asarray(groups.place)[~absent] < places).all()
    # a bank of zeros for the held experts: nothing else adds anything
    zeros = {**params, **{n: jnp.zeros_like(params[n]) for n in BANK}}
    out, _ = grouped(zeros, x)
    assert float(jnp.abs(out).max()) == 0.0
    out, _ = grouped(params, x)
    assert float(jnp.abs(out).max()) > 0.01


@pytest.mark.parametrize("tile", [16, 64])
@pytest.mark.parametrize("held", [None, (2, 3)])
def test_row_groups_is_a_place_for_every_routed_row(tile, held):
    logits = jax.random.normal(jax.random.PRNGKey(5), (50, 8))
    cg = top_k_gating_compact(logits, 3, drop_tokens=False)
    groups = row_groups(cg, tile, held)
    place, source = np.asarray(groups.place), np.asarray(groups.source)
    first, count = held or (0, 8)
    chosen = np.asarray(cg.topk_idx)
    present = (chosen >= first) & (chosen < first + count)
    places = source.shape[0]
    assert places % tile == 0 and groups.tile_expert.shape == (places // tile,)
    # distinct places, and ``source`` is the way back
    assert len(set(place[present])) == present.sum()
    token = np.broadcast_to(np.arange(50)[:, None], place.shape)
    assert (source[place[present]] == token[present]).all()
    assert (source == 50).sum() == places - present.sum()
    # a tile holds one expert's rows, and the tiles in use are a prefix
    tile_expert = np.asarray(groups.tile_expert)
    assert (tile_expert[place[present] // tile] + first
            == chosen[present]).all()
    counts = np.asarray(cg.counts)[first:first + count]
    used = int(groups.num_tiles)
    assert used == int(np.ceil(counts / tile).sum())
    assert place[present].max() < used * tile
    # each tile's rows in use are a prefix of it, and add up to its expert's
    tile_rows = np.asarray(groups.tile_rows)
    assert (tile_rows[:used] > 0).all() and (tile_rows[used:] == 0).all()
    assert (source.reshape(-1, tile)[:used] < 50).sum(1).tolist() \
        == tile_rows[:used].tolist()
    assert ((source.reshape(-1, tile) < 50).cumsum(1)
            == np.minimum(np.arange(1, tile + 1), tile_rows[:, None])).all()
    assert np.bincount(tile_expert[:used], tile_rows[:used],
                       minlength=count).tolist() == counts.tolist()


@pytest.mark.parametrize("rows,n_experts,k,held,inter,tile", [
    (272, 8, 2, 8, 14336, 256),     # Mixtral's mixed call, 68 rows an expert:
                                    # an expert read twice costs an eighth of
                                    # the bank, padding next to nothing
    (272, 64, 8, 64, 1024, 64),     # OLMoE's, 34 rows: the head-room alone
    (520, 128, 8, 16, 768, 64),     # Keye's, 32.5 rows, 16 experts held
    (16, 64, 8, 64, 1024, 16),      # the decode-only calls: fewer rows than
    (16, 8, 2, 8, 14336, 16),       # a tile could hold
    (8, 128, 8, 16, 768, 16),
    (1, 8, 2, 8, 14336, 16),
    (4096, 8, 2, 8, 14336, 256),    # a whole prompt: the largest tile
    (4096, 64, 8, 64, 1024, 256),
])
def test_the_row_tile_follows_the_calls_shapes(rows, n_experts, k, held,
                                               inter, tile):
    assert row_tile(rows, n_experts, k, held, inter) == tile


def test_the_weight_blocks_follow_the_banks_widths():
    """An expert's matrices whole where they fit (OLMoE, Keye), blocks of
    columns where they do not (Mixtral: 128 of 14336 a step)."""
    assert grouped_matmul.f_block(2048, 1024) == 1024
    assert grouped_matmul.f_block(2048, 768) == 768
    assert grouped_matmul.f_block(4096, 14336) == 128
    assert grouped_matmul.f_block(2048, 8192) == 256
    assert grouped_matmul.f_block(64, 48) == 48


# --- the kernel against its jax.numpy reference ---------------------------- #
def _kernel_case(tile, stacked, dtype, n_experts=5, layers=3):
    logits = jax.random.normal(jax.random.PRNGKey(2), (70, n_experts))
    cg = top_k_gating_compact(logits, 2, drop_tokens=False)
    groups = row_groups(cg, tile)
    lead = (layers if stacked else 1,)
    ks = jax.random.split(jax.random.PRNGKey(9), 4)
    hidden, inter = 128, 256
    bank = [jax.random.normal(k, lead + shape, dtype) * shape[1] ** -0.5
            for k, shape in zip(ks, [(n_experts, hidden, inter)] * 2
                                + [(n_experts, inter, hidden)])]
    x = jax.random.normal(ks[3], (70, hidden), dtype)
    rows = jnp.concatenate([x, jnp.zeros((1, hidden), dtype)])[groups.source]
    return rows, bank, groups


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("stacked", [False, True])
@pytest.mark.parametrize("tile,f_blocks", [(16, 1), (64, 2), (256, 2)])
def test_the_kernel_equals_its_reference(monkeypatch, tile, f_blocks, stacked,
                                         dtype):
    """Interpret mode: tiles past ``num_tiles`` are skipped (the reference
    zeroes them), the stacked bank is read at the layer, and the down matmul
    accumulates over F blocks."""
    if f_blocks > 1:    # 128 columns a step: two steps a tile
        monkeypatch.setattr(grouped_matmul, "_WHOLE_VMEM", 0)
        monkeypatch.setattr(grouped_matmul, "_BLOCK_VMEM", 0)
    assert grouped_matmul.f_block(128, 256, dtype(0).itemsize) \
        == 256 // f_blocks
    rows, bank, groups = _kernel_case(tile, stacked, dtype)
    args = (rows, *bank, groups.tile_expert, groups.tile_rows,
            groups.num_tiles)
    layer = jnp.int32(1 if stacked else 0)
    with jax.default_matmul_precision("highest"):
        got = grouped_matmul.moe_grouped_matmul(*args, layer, tile=tile)
        want = grouped_matmul.moe_grouped_matmul_xla(*args, layer, tile=tile)
    used = int(groups.num_tiles) * tile
    assert 0 < used <= rows.shape[0] and (used < rows.shape[0]) == (tile < 256)
    # the rows in use; a tile of 256 is worked in two sub-tiles of 128 and
    # the second skipped where the first holds the expert's rows
    live = np.asarray(groups.source) < 70
    assert live[:used].sum() == 140 and not live[used:].any()
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               rtol=tol, atol=tol)
    assert float(jnp.abs(want[~live]).max()) == 0.0
    if tile == 256:
        assert (np.asarray(groups.tile_rows)[:int(groups.num_tiles)]
                <= 128).any()
    if stacked:     # another layer's weights give another result
        other = grouped_matmul.moe_grouped_matmul_xla(
            *args, jnp.int32(2), tile=tile)
        assert float(jnp.abs(other - want).max()) > 0.1


def test_a_call_that_routes_no_row_to_the_bank_computes_nothing():
    rows, bank, groups = _kernel_case(16, False, jnp.float32)
    out = grouped_matmul.moe_grouped_matmul(
        rows, *bank, groups.tile_expert, jnp.zeros_like(groups.tile_rows),
        jnp.int32(0), 0, tile=16)
    assert out.shape == rows.shape      # unspecified rows, no fault


def test_the_layer_runs_the_registered_kernel():
    params, x, grouped, _ = _case("mixtral_8x2_normalised")
    want, _ = grouped(params, x)
    registry.set_backend("moe_grouped_matmul", "pallas")
    try:
        got, _ = grouped(params, x)
    finally:
        registry.set_backend("moe_grouped_matmul", None)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    assert registry.resolved()["moe_grouped_matmul"] == "xla"   # off a TPU


# --- which calls take which form ------------------------------------------- #
def _jaxpr(moe, params, x, **kw):
    return str(jax.make_jaxpr(lambda p, x: moe(p, x, **kw))(params, x))


@pytest.fixture
def the_kernel():
    """Off a TPU the registry takes the reference; with the kernel chosen a
    grouped call shows in a jaxpr by its name."""
    registry.set_backend("moe_grouped_matmul", "pallas")
    yield "moe_grouped_matmul"
    registry.set_backend("moe_grouped_matmul", None)


def test_a_call_that_may_drop_tokens_keeps_the_slabs(the_kernel):
    """Dropping IS the capacity slab's meaning: ``drop_tokens=True`` (the
    training default) still builds ``[E, C, H]`` slabs through the one-hot
    einsums, and no grouped call; a no-drop call over the stacked banks has
    neither slab nor ``[T, E, C]`` mask."""
    params = _bank(4)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, HIDDEN))
    dropping = MoELayer(4, 2, capacity_factor=1.25)
    assert not dropping.grouped()
    capacity = 25                               # ceil(2 * 40 * 1.25 / 4)
    for kw in ({}, {"layer": LAYER}):
        text = _jaxpr(dropping, _stacked(params) if kw else params, x, **kw)
        assert f"f32[4,{capacity},{HIDDEN}]" in text
        assert f"[40,4,{capacity}]" in text and the_kernel not in text
    kept = _jaxpr(MoELayer(4, 2, drop_tokens=False), _stacked(params), x,
                  layer=LAYER)
    assert the_kernel in kept
    assert "[40,4,40]" not in kept and f"f32[4,40,{HIDDEN}]" not in kept
    # 'compact' keeps its own table form either way (Queue C2b)
    assert not MoELayer(4, 2, drop_tokens=False, dispatch="compact").grouped()


def test_one_layers_bank_keeps_the_slabs(the_kernel):
    """A no-drop call WITHOUT the stacked banks (the training forward,
    ``mixtral.apply``; an imported MoE fine-tuned on one chip) builds the
    slabs it always did: its backward needs a transpose the kernel has not,
    and a layer's bank handed to a Mosaic call is a copy of it."""
    params = _bank(4)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 20, HIDDEN))
    layer = MoELayer(4, 2, drop_tokens=False)
    assert layer.grouped()
    text = _jaxpr(layer, params, x)
    assert the_kernel not in text and f"f32[4,40,{HIDDEN}]" in text
    grads = jax.grad(lambda p: jnp.sum(layer(p, x)[0] ** 2))(params)
    assert all(float(jnp.abs(grads[n]).max()) > 0 for n in BANK)


def test_a_program_over_a_mesh_keeps_the_slabs(devices8):
    """The grouped matmul is one device's kernel: a trace whose mesh has an
    axis larger than one takes the slabs, and so does every trace of a
    process whose mesh spans devices - the inference engines install theirs
    (``set_mesh``) and trace with NO mesh context."""
    from jax.sharding import Mesh

    layer = MoELayer(4, 2, drop_tokens=False)
    assert layer.grouped()
    with jax.set_mesh(Mesh(np.array(devices8).reshape(2, 4),
                           ("data", "expert"))):
        assert not layer.grouped()
    assert layer.grouped()
    mesh_lib.init_mesh({"tensor": 2, "data": 4})
    assert not layer.grouped()
    mesh_lib.set_mesh(None)         # the default: every device of the host
    assert not layer.grouped()


@pytest.mark.parametrize("tp", [2, 1])
def test_an_engine_over_devices_serves_the_slabs(devices8, the_kernel, tp):
    """``init_inference`` of a no-drop Mixtral over ``tensor: tp, data:
    8 / tp``: its cached forward holds no grouped call (Mosaic would refuse
    one in a program XLA partitions) and generates what a one-device
    engine, which takes the grouped form, does."""
    from deepspeed_tpu.inference import init_inference

    cfg = mixtral.MixtralConfig.tiny(drop_tokens=False)
    params = mixtral.init(cfg, jax.random.PRNGKey(0))
    config = {"dtype": "float32", "tensor_parallel": {"tp_size": tp}}
    prompt = np.array([[5, 9, 2, 7]], np.int32)

    def cached_forward():
        cache = jax.eval_shape(lambda: mixtral.init_cache(cfg, 1, 16))
        return str(jax.make_jaxpr(
            lambda p, t, c, n: mixtral.apply_cached(cfg, p, t, c, n))(
                params, prompt, cache, jnp.zeros((1,), jnp.int32)))

    single = init_inference(mixtral, model_cfg=cfg, params=params,
                            config={"dtype": "float32"})
    assert single.mesh_mgr.world_size == 1
    assert the_kernel in cached_forward()
    assert mixtral.moe_rows(cfg, 4)["moe_row_tile"] == 16
    want = single.generate(prompt, max_new_tokens=4)

    mesh_lib.set_mesh(None)
    engine = init_inference(mixtral, model_cfg=cfg, params=params,
                            config=config)
    assert engine.mesh_mgr.world_size == 8
    assert engine.mesh_mgr.tp_world_size == tp
    assert the_kernel not in cached_forward()
    assert mixtral.moe_rows(cfg, 4)["moe_row_tile"] == 0
    registry.set_backend(the_kernel, None)      # run on the CPU's reference
    np.testing.assert_array_equal(
        engine.generate(prompt, max_new_tokens=4), want)


def test_a_one_device_engine_serves_the_grouped_form_token_for_token():
    """``build_engine_v2`` + mixed steps (a split prompt's chunks beside two
    live sequences, then decodes alone) on one device, where every MoE call
    is grouped, against the same engine over the host's eight devices, which
    keeps the slabs: the same tokens, and each says its form on its spans."""
    from deepspeed_tpu.inference import build_engine_v2

    cfg = mixtral.MixtralConfig.tiny(max_seq_len=32)
    params = mixtral.init(cfg, jax.random.PRNGKey(0))
    config = {"prefill_bucket": 8, "split_prefill_chunk": 8,
              "trace": {"enabled": True},
              "ragged": {"max_tracked_sequences": 4,
                         "max_ragged_batch_size": 4,
                         "memory_config_blocks": 40, "block_size": 4}}

    def served(eng):
        rng = np.random.RandomState(11)
        eng.put(1, rng.randint(1, 200, 5).tolist())
        eng.put(2, rng.randint(1, 200, 9).tolist())
        eng.put_split(3, rng.randint(1, 200, 21).tolist())
        tokens = [eng.step(seed=step) for step in range(5)]
        tiles = {e["args"]["moe_row_tile"] for e in eng.tracer.events()
                 if "moe_row_tile" in e["args"]}
        return tokens, tiles

    one = build_engine_v2(mixtral, cfg, params, config=config)
    assert one.mesh_mgr.world_size == 1
    got, tiles = served(one)
    assert tiles == {16}
    mesh_lib.set_mesh(None)
    eight = build_engine_v2(mixtral, cfg, params, config=config)
    assert eight.mesh_mgr.world_size == 8
    want, tiles = served(eight)
    assert tiles == {0}
    assert got == want and all(got) and 3 in got[-1]


# --- the counter ----------------------------------------------------------- #
def test_moe_rows_count_what_the_grouped_call_computes():
    """``moe_rows_computed``: the tiles in use times the tile, in
    expectation under uniform routing - every expert's one tile where the
    call is many rows an expert, the experts REACHED where it is few."""
    # Mixtral: a tile of 256 rows, worked in sub-tiles of 128 - one an expert
    wide = mixtral.MixtralConfig(num_experts=8, top_k=2)
    assert mixtral.moe_rows(wide, 272) == {
        "moe_rows_routed": 544, "moe_rows_computed": 1024,
        "moe_row_tile": 256}
    olmoe = mixtral.MixtralConfig(num_experts=64, top_k=8, hidden_size=2048,
                                  intermediate_size=1024)
    assert mixtral.moe_rows(olmoe, 272) == {
        "moe_rows_routed": 2176, "moe_rows_computed": 4096,
        "moe_row_tile": 64}
    # 16 rows: an expert is reached with probability 1 - (7/8)^16 = 0.882
    assert mixtral.moe_rows(olmoe, 16) == {
        "moe_rows_routed": 128, "moe_rows_computed": round(64 * 0.8819 * 16),
        "moe_row_tile": 16}
    keye = mixtral.MixtralConfig(num_experts=128, top_k=8,
                                 intermediate_size=768,
                                 experts_held=(16, 16))
    assert mixtral.moe_rows(keye, 520) == {
        "moe_rows_routed": 520, "moe_rows_computed": 1024,
        "moe_row_tile": 64}
    compact = mixtral.MixtralConfig(num_experts=8, top_k=2,
                                    moe_dispatch="compact")
    assert mixtral.moe_rows(compact, 272) == {
        "moe_rows_routed": 544, "moe_rows_computed": 8 * 272,
        "moe_row_tile": 0}
    # every expert every token's: each takes the call's rows, whole tiles
    dense = mixtral.MixtralConfig(num_experts=4, top_k=4)
    assert mixtral.moe_rows(dense, 300) == {
        "moe_rows_routed": 1200, "moe_rows_computed": 4 * 384,
        "moe_row_tile": 256}
