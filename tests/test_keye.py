"""Keye-VL-2.0-30B-A3B's language model through ``models/mixtral.py`` (ISSUE
38) against the benchmark's plain reference (``benchmark/reference/keye.py``)
at toy widths on the CPU, float32 and seeded: the learned token selection
(contexts under, at and over a toy ``topk``) along every path - the full
forward, the dense cache, chunked prefill then decode through the paged
cache, ``engine_v2.step`` and ``ServingScheduler.tick`` (mixed and
overlapped) -, the five new ops interpreted against their gathered XLA
forms, the selected set itself (ties and short contexts included), one
chip's share of the expert bank tied to the whole layer, and what the engine
does with a cache of three pools.
"""

import dataclasses
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.families import keye as family
from benchmark.reference import keye as reference
from benchmark.reference import keye_variants
from deepspeed_tpu.inference.engine_v2 import (IndexPoolError,
                                               build_engine_v2)
from deepspeed_tpu.inference.serving import (Request, SchedulerConfig,
                                             ServingScheduler)
from deepspeed_tpu.models import mixtral
from deepspeed_tpu.models._paged import MixedCall
from deepspeed_tpu.moe.layer import MoELayer, init_moe_ffn
from deepspeed_tpu.ops.pallas import paged_sparse_attention as sparse

TOPK = 8
TINY = dict(
    attention_bias=False, decoder_sparse_step=1, head_dim=32,
    hidden_act="silu", hidden_size=64, intermediate_size=192,
    max_position_embeddings=128, max_window_layers=2, mlp_only_layers=[],
    model_type="KeyeVL2", moe_intermediate_size=32, norm_topk_prob=True,
    num_attention_heads=4, num_experts=8, num_experts_per_tok=4,
    num_hidden_layers=2, num_key_value_heads=2, num_local_experts=8,
    rms_norm_eps=1e-6,
    rope_scaling={"mrope_section": [4, 6, 6], "rope_type": "default",
                  "type": "default"},
    rope_theta=10000000,
    sa_config={"indexer_head_dim": 16, "indexer_num_heads": 4,
               "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
               "q_chunk_size": 512, "topk": TOPK},
    sliding_window=None, tie_word_embeddings=False, use_sliding_window=False,
    vocab_size=256)
HELD = {**TINY, "num_experts": 2, "experts_first": 4}   # one share of four
PROMPT, CHUNK, STEPS, BLOCK = 21, 8, 6, 4     # three chunks, the last short
PATHS = ("apply", "apply_cached", "apply_paged")
TOL = 1e-4      # float32 on both sides in another order: 1e-5 of unit logits


def build(hf=TINY, dtype=jnp.float32):
    """The configuration, seeded random weights (the norms' too, which
    ``init`` leaves flat) and a row of tokens, prompt and answer. ``HELD``:
    the same model with this share's two experts cut out of the bank."""
    cfg = family.build_cfg(TINY, drop_tokens=False)
    params = family.init(cfg, jax.random.PRNGKey(0))
    layers = params["layers"]
    for i, name in enumerate(("attn_norm", "mlp_norm", "q_norm", "k_norm")):
        layers[name] = layers[name] * (1.0 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(10 + i), layers[name].shape))
    if hf is not TINY:
        first, count = reference.held_experts(hf)
        cfg = family.build_cfg(hf, drop_tokens=False)
        layers["moe"] = {k: v if k == "router" else v[:, first:first + count]
                         for k, v in layers["moe"].items()}
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    row = np.random.default_rng(0).integers(0, 256, PROMPT + STEPS)
    return cfg, params, row


def pieces(row):
    cuts = list(range(0, PROMPT, CHUNK)) + list(range(PROMPT, len(row)))
    return [(a, row[a:b]) for a, b in zip(cuts, cuts[1:] + [len(row)])]


def program_logits(path, cfg, params, row, dtype=jnp.float32):
    """Logits ``[len(row), vocab]`` of the program along ``path``
    (``tests/test_olmoe.py``'s three ways through the model)."""
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
    return float(jnp.abs(jnp.asarray(a) - jnp.asarray(b)).max())


@pytest.fixture(scope="module", params=["whole", "held"])
def f32(request):
    hf = TINY if request.param == "whole" else HELD
    cfg, params, row = build(hf)
    return hf, cfg, params, row, reference.logits(hf, family.Weights(params),
                                                  row)


@pytest.mark.parametrize("path", PATHS)
def test_program_agrees_with_the_plain_reference_in_float32(f32, path,
                                                            one_device):
    """Every position of a 27-token row: contexts of 1-8 select everything,
    9-27 select 8 (``TOPK``), along each path - and with one chip's share of
    the bank the partial sum is the reference's partial sum."""
    _, cfg, params, row, want = f32
    with jax.default_matmul_precision("highest"):
        got = program_logits(path, cfg, params, row)
    assert got.shape == want.shape and gap(got, want) < TOL


@pytest.mark.parametrize("variant", keye_variants.NAMES)
def test_each_wrong_variant_fails_the_tolerance(f32, variant):
    """No selection, the newest 8 in place of the learned 8, the top 4, and
    index vectors without rope each lie a thousand times beyond what the
    program, along every path, is held to."""
    hf, _, params, row, want = f32
    wrong = keye_variants.logits(variant, hf, family.Weights(params), row)
    assert gap(wrong, want) > 1000 * TOL


# --- what a cell's probes are held to beside their served tokens ------------ #
ROLE = {"program_options": {"drop_tokens": False}, "weights_dtype": "float32",
        "engine": {"split_prefill_chunk": CHUNK,
                   "ragged": {"block_size": BLOCK}},
        "held": {"why": "float32 on both sides",
                 "logits_mean_abs_diff": TOL, "selected_share": 1.0}}


@pytest.mark.parametrize("name", ("right",) + keye_variants.NAMES)
def test_the_probes_comparison_passes_the_right_form_alone(f32, name):
    """``reference.held`` - the program's ``apply_paged`` logits (19 tokens
    in chunks of 8, then 8 single tokens) and its selected sets at both
    layers against a reference's - reads inside the limits for the right
    reference and beyond one for each deliberately wrong one."""
    hf, _, params, row, want = f32
    weights = family.Weights(params, role=ROLE)
    with jax.default_matmul_precision("highest"):
        got = weights.program.logits(hf, row, reference.HELD_DECODE)
    assert got.shape == (reference.HELD_DECODE + 1, 256)
    form = None if name == "right" else keye_variants.form(name, hf)
    keep = {0: None, 1: None}
    if form is None:
        reference.hidden(hf, weights, row, keep=keep)
    else:
        want = keye_variants.logits(name, hf, weights, row, keep=keep)
    with jax.default_matmul_precision("highest"):
        seen = reference.held(hf, weights, got, want[-got.shape[0]:], keep,
                              form)
    why = reference.disagreements(seen, weights.program.limits)
    assert bool(why) == (name != "right"), (seen, why)
    assert len(seen["selected"]) == 2


def test_a_probe_beyond_a_limit_raises(f32, capsys):
    """``logits_and_margin`` with the family's weights: the probe's readings
    are a line of the output, and a program whose indexer is not the
    reference's (its index queries' matrix negated) raises by name."""
    hf, _, params, row, want = f32
    weights = family.Weights(params, role=ROLE)
    with jax.default_matmul_precision("highest"):
        got, margin = reference.logits_and_margin(hf, weights, row)
    assert gap(got, want) == 0 and bool(jnp.isinf(margin).all())
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "held" and line["why_not"] == []
    assert line["limits"] == {"logits_mean_abs_diff": TOL,
                              "selected_share": 1.0}
    layers = dict(params["layers"])
    layers["wq_idx"] = -layers["wq_idx"]
    weights.program.params = {**params, "layers": layers}
    with jax.default_matmul_precision("highest"), \
            pytest.raises(reference.Disagreement, match="selects"):
        reference.logits_and_margin(hf, weights, row)
    # weights that come without their program are the reference's alone
    bare = family.Weights(params)
    del bare.program
    assert gap(reference.logits_and_margin(hf, bare, row)[0], want) == 0


@pytest.mark.parametrize("f32", ["whole"], indirect=True)   # one bank is enough
def test_index_keys_of_a_lower_precision_select_as_many_tokens(f32):
    """``Program.selected(keys=)``, the precision control of
    ``tools/keye_check.py``: keys rounded to their own type select what
    unrounded keys do; rounded to fp8 every row still takes ``topk`` tokens
    (the selection is exact whatever it is given) and some row takes
    others."""
    hf, _, params, row, _ = f32
    program = family.Weights(params, role=ROLE).program
    y = reference.hidden(hf, family.Weights(params), row, layers=0)
    exact = program.selected(hf, 0, y, 16)
    assert (program.selected(hf, 0, y, 16, keys="float32") == exact).all()
    below = program.selected(hf, 0, y, 16, keys="float8_e4m3fn")
    assert (below.sum(1) == exact.sum(1)).all() and (exact.sum(1) == TOPK).all()
    assert (below != exact).any()


@pytest.mark.parametrize("f32", ["whole"], indirect=True)   # one bank is enough
def test_a_mixed_call_is_its_two_segments(f32):
    """One chunk's rows beside two decode rows in ONE call of
    ``apply_paged``: the chunk scores and selects over its own table, each
    decode row over its own, and every row's logits are the reference's."""
    hf, cfg, params, row, want = f32
    rng = np.random.default_rng(5)
    others = [rng.integers(0, 256, n) for n in (13, 19)]
    wants = [reference.logits(hf, family.Weights(params), o) for o in others]
    paged = jax.jit(functools.partial(mixtral.apply_paged, cfg,
                                      compute_dtype=jnp.float32))
    cache = mixtral.init_paged_cache(cfg, 32, BLOCK, dtype=jnp.float32)
    tables = np.zeros((4, 8), np.int32)
    tables[0, :5], tables[1, :5], tables[2, :7] = (np.arange(1, 6),
                                                   np.arange(6, 11),
                                                   np.arange(11, 18))
    with jax.default_matmul_precision("highest"):
        for i, o in enumerate(others):       # the decode rows' contexts
            pad = np.zeros((1, 24), np.int32)
            pad[0, :len(o) - 1] = o[:-1]
            _, cache = paged(
                params, jnp.asarray(pad), cache,
                jnp.asarray(tables[i:i + 1]), jnp.zeros((1,), jnp.int32),
                valid=jnp.arange(24)[None] < len(o) - 1)
        _, cache = paged(                    # the chunk's first 16 tokens
            params, jnp.asarray(row[None, :16]), cache,
            jnp.asarray(tables[2:3]), jnp.zeros((1,), jnp.int32))
        call = MixedCall(
            tables=jnp.asarray(tables), lens=jnp.asarray([12, 18, 0, 0]),
            active=jnp.asarray([True, True, False, False]),
            chunk_table=jnp.asarray(tables[2]), chunk_ctx=jnp.asarray(16),
            chunk_valid=jnp.asarray(5))
        tokens = np.zeros((1, 4 + 8), np.int32)
        tokens[0, 0], tokens[0, 1] = others[0][-1], others[1][-1]
        tokens[0, 4:9] = row[16:21]
        got, _ = paged(params, jnp.asarray(tokens), cache, call, None,
                       valid=call.valid(12))
    assert gap(got[0, 0], wants[0][-1]) < TOL
    assert gap(got[0, 1], wants[1][-1]) < TOL
    assert gap(got[0, 4:9], want[16:21]) < TOL


# --- the selected set ------------------------------------------------------ #
def selected_sets(select, scores, q_abs, topk):
    tau, cut = select(jnp.asarray(scores), jnp.asarray(q_abs), topk=topk)
    pos = np.arange(scores.shape[1])[None]
    keep = np.asarray(sparse.selected(jnp.asarray(scores), pos, tau[:, None],
                                      cut[:, None]))
    return keep & (pos <= np.asarray(q_abs)[:, None])


@pytest.mark.parametrize("select", ["xla", "interpreted"])
@pytest.mark.parametrize("topk", [4, 8, 64])
def test_the_selected_set_is_the_references(select, topk):
    """Scores with many exact ties (half-integers, zeros of both signs),
    rows whose context is under, at and over ``topk``: the threshold form
    (``tau``, ``cut``) selects exactly what the reference's ``lax.top_k``
    with its tie rule selects."""
    rng = np.random.default_rng(topk)
    scores = np.round(rng.normal(size=(24, 64)) * 2) / 2
    scores[:, ::7] = -0.0
    scores = np.where(scores == 0.0, 0.0, scores).astype(np.float32)
    q_abs = np.concatenate([np.arange(12), rng.integers(12, 64, 12)])
    fn = sparse.paged_sparse_select_xla if select == "xla" \
        else sparse.paged_sparse_select
    got = selected_sets(fn, scores, q_abs.astype(np.int32), topk)
    want = np.asarray(reference.learned_selection(
        jnp.asarray(scores), jnp.asarray(q_abs), jnp.arange(64), topk))
    np.testing.assert_array_equal(got, want)
    assert (got.sum(1) == np.minimum(q_abs + 1, topk)).all()


# --- each new op, interpreted, against its gathered XLA form --------------- #
@pytest.fixture(scope="module")
def pools():
    rng = np.random.default_rng(0)
    L, nb, bs, d, H, nkv, g, hd = 2, 24, 8, 64, 4, 2, 2, 32
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    tables = jnp.asarray(rng.permutation(np.arange(1, 17)).reshape(2, 8),
                         jnp.int32)
    return dict(L=L, bs=bs, d=d, H=H, tables=tables,
                pool=jnp.zeros(sparse.index_pool_shape(L, nb, bs, d)),
                keys=f(2, 64, d), q_idx=f(2, 16, H, d), w_idx=f(2, 16, H),
                k=f(L, nb, nkv, bs, hd), v=f(L, nb, nkv, bs, hd),
                q=f(2, 16, nkv * g, hd))


def written(p, impl):
    """The index pool after a context of (5, 19) tokens and a step of (16,
    11) more, written by ``impl``."""
    ctx, lens = jnp.asarray([5, 19]), jnp.asarray([16, 11])
    pool = impl(p["keys"][:, :32], p["pool"], p["tables"],
                jnp.zeros(2, jnp.int32), ctx, layer=1)
    return impl(p["keys"][:, 32:48], pool, p["tables"], ctx, lens, layer=1), \
        ctx, lens


def test_index_write_interpreted_is_the_scatter(pools):
    got, _, _ = written(pools, sparse.paged_index_write)
    want, _, _ = written(pools, sparse.paged_index_write_xla)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert float(jnp.abs(want[0]).max()) == 0.0    # the other layer: untouched
    # a page is the block's first half of tokens beside its second
    keys = sparse._gathered_keys(want, pools["tables"], jnp.asarray([1]), 64)
    np.testing.assert_array_equal(np.asarray(keys[0, :5]),
                                  np.asarray(pools["keys"][0, :5]))


def live_rows(ctx, lens, rows, width):
    pos = np.arange(width)[None, None]
    q_abs = (np.asarray(ctx)[:, None] + np.arange(rows)[None])[..., None]
    return (pos <= q_abs) & (np.arange(rows)[None, :, None]
                             < np.asarray(lens)[:, None, None])


@pytest.mark.parametrize("t", [16, 1])
def test_index_scores_interpreted_are_the_gathered_einsum(pools, t):
    pool, ctx, lens = written(pools, sparse.paged_index_write_xla)
    rows = 16
    if t == 1:      # a decode row a sequence: its heads are the tile's rows
        ctx, lens, rows = jnp.asarray([21, 30]), jnp.ones(2, jnp.int32), 8
    args = (pools["q_idx"][:, :t], pools["w_idx"][:, :t], pool,
            pools["tables"], ctx, lens)
    got = sparse.paged_index_scores(*args, layer=1, rows=rows)
    want = sparse.paged_index_scores_xla(*args, layer=1, rows=rows)
    live = live_rows(ctx, lens, rows, 64)
    assert float(np.abs(np.where(live, got[..., :64] - want, 0)).max()) < 1e-5
    # the reference's own scores, from the same vectors
    ref = reference.index_scores(
        pools["q_idx"][0, :t], sparse._gathered_keys(
            pool, pools["tables"], jnp.asarray([1]), 64)[0],
        pools["w_idx"][0, :t])
    assert float(np.abs(np.where(live[0, :t], ref - want[0, :t],
                                 0)).max()) < 1e-4


# The decode rows SCORE their own pages (ISSUE 54): one token a sequence, the
# index pool's pages fetched by the kernel itself. Blocks of 16 tokens, two
# 64-wide keys a pool row, a table 10 wide and a tile held to two pages: five
# tiles of 32 tokens. A context of n is n cached tokens plus the row's own:
# 31 is exactly a tile, 32 the first token of the second, 159 the table. A
# slot with no row (``rows`` 0) is idle: its whole table row is garbage.
SCORES_OWN_PAGES = {
    "scattered_blocks": dict(ctx=[40, 5, 77, 100]),
    "idle_first_between_and_last": dict(ctx=[9, 33, 0, 80, 50],
                                        rows=[0, 1, 0, 1, 0]),
    "every_slot_idle": dict(ctx=[0, 12, 40, 0], rows=[0, 0, 0, 0]),
    "contexts_round_a_tile": dict(ctx=[30, 31, 32, 63, 64]),
    "contexts_round_a_page": dict(ctx=[14, 15, 16, 0, 7]),
    "a_sequence_fills_the_table": dict(ctx=[159, 3, 159, 158]),
    "narrow_heads_keep_the_grid": dict(ctx=[40, 31, 32, 159],
                                       rows=[1, 0, 1, 1], d=48),
    "through_the_selection_and_the_masked_walk": dict(ctx=[159, 70, 3, 100],
                                                      rows=[1, 1, 0, 1],
                                                      chain=True),
}


@pytest.mark.parametrize("case", sorted(SCORES_OWN_PAGES))
def test_decode_rows_score_their_own_pages(case, monkeypatch):
    """``paged_index_scores`` at one token a sequence (interpreted: the
    interpreter runs its DMAs, its semaphores and its SMEM carry) on every
    entry the selection reads: against the gathered XLA op, and BIT FOR BIT
    against the grid of ``BlockSpec`` pages at the same tile. The kernels'
    copy of the operands is POISONED wherever they must not look: the table
    past a sequence's last block - an idle slot's whole row - holds a block
    of NaN keys, an index past the pool and a negative one, in turn, and
    every block no live sequence holds is NaN. An index head width that
    does not divide 128 keeps the grid; ``chain``: the scores go on through
    ``paged_sparse_select`` and ``paged_sparse_decode`` - whose tile is
    another width - and the attention is the XLA chain's."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    c = dict(dict(rows=None, d=64, chain=False), **SCORES_OWN_PAGES[case])
    monkeypatch.setattr(sparse, "_INDEX_PAGES", 2)
    rng = np.random.default_rng(4)
    L, nb, bs, mb, H, d = 2, 64, 16, 10, 4, c["d"]
    ctx = np.asarray(c["ctx"], np.int32)
    rows = np.asarray(c["rows"] or [1] * len(ctx), np.int32)
    B, poison = len(ctx), nb - 1
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    pool = f(*sparse.index_pool_shape(L, nb, bs, d))
    own = sparse._fetches_index_pages(pool.shape)
    assert own == (d == 64) and sparse._index_pages(1, mb) == 2
    tables = np.zeros((B, mb), np.int32)
    garbage = np.resize(np.asarray([poison, 10 ** 6, -3], np.int32), (B, mb))
    for b in np.flatnonzero(rows):
        need = ctx[b] // bs + 1
        tables[b, :need] = garbage[b, :need] = rng.choice(
            np.arange(1, poison), need, replace=False)
    held = np.unique(tables)
    poisoned = jnp.full_like(pool, jnp.nan).at[:, held].set(pool[:, held])
    q_idx, w_idx = f(B, 1, H, d), f(B, 1, H)
    args = (q_idx, w_idx, poisoned, jnp.asarray(garbage), jnp.asarray(ctx),
            jnp.asarray(rows))
    got = sparse.paged_index_scores(*args, layer=1, rows=8)
    grid = sparse._index_scores(sparse._index_grid, *args, layer=1, rows=8)
    want = sparse.paged_index_scores_xla(q_idx, w_idx, pool,
                                         jnp.asarray(tables), None, None,
                                         layer=1)
    assert got.shape == grid.shape == (B, 8, mb * bs)
    read = (np.arange(mb * bs)[None] <= ctx[:, None]) & (rows[:, None] > 0)
    assert np.isfinite(np.asarray(got[:, 0])[read]).all()
    np.testing.assert_array_equal(np.asarray(got[:, 0])[read],
                                  np.asarray(grid[:, 0])[read])
    assert gap(jnp.where(read, got[:, 0], 0), jnp.where(read, want[:, 0], 0)) \
        < 1e-5
    # the span's counter says what the call takes: each decoding slot's own
    # tiles, or every slot as far as the longest where it is the grid
    tiles = int((ctx[rows > 0] // 32 + 1).sum())
    assert sparse.index_tile_counts(ctx, rows, pool.shape, bs, mb) == (
        tiles, tiles if own else B * min(-(-int((ctx + rows).max()) // 32), 5))
    if not c["chain"]:
        return
    monkeypatch.setattr(pa, "_DECODE_KV_TOKENS", 64)
    nkv, g, hd = 2, 2, 128
    pages, _, n_kv = pa._decode_tiles(nkv, g, hd, bs, mb, 4, False)
    assert pages * n_kv * bs > got.shape[2]     # its last tile overhangs
    k, v, q = f(L, nb, nkv, bs, hd), f(L, nb, nkv, bs, hd), f(B, nkv * g, hd)
    live = rows > 0
    tau, cut = sparse.paged_sparse_select(
        got[:, 0], jnp.asarray(np.where(live, ctx, -1)), topk=TOPK)
    out = sparse.paged_sparse_decode_attention(
        q, *(p.at[:, poison].set(jnp.nan) for p in (k, v)), got, tau, cut,
        jnp.asarray(np.where(live[:, None], garbage, 0)), jnp.asarray(ctx),
        layer=1)
    tau_x, cut_x = sparse.paged_sparse_select_xla(want[:, 0],
                                                  jnp.asarray(ctx), topk=TOPK)
    out_x = sparse.paged_sparse_decode_attention_xla(
        q, k, v, want, tau_x, cut_x, jnp.asarray(tables), jnp.asarray(ctx),
        layer=1)
    assert gap(out[live], out_x[live]) < 1e-5


# The decode rows walk their own pages (ISSUE 51): heads of 128 lanes, blocks
# of 8 tokens, a table 20 wide and a KV tile held to 64 tokens - two whole
# tiles and half a third. A context of n is n cached tokens plus the current
# one: 63 is exactly a tile, 64 the first token of the second, 159 the table.
OWN_PAGES = {
    "contexts_round_a_block": dict(ctx=[0, 7, 8, 9]),
    "contexts_round_a_tile": dict(ctx=[62, 63, 64, 65, 127, 128]),
    # scores as wide as the table, which is no multiple of the tile: the
    # last tile's slice of them must not be fetched past their end
    "table_no_multiple_of_the_tile": dict(ctx=[159, 100, 130], width=160),
    "trash_block_beside_full_slots": dict(ctx=[159, 0, 159, 0],
                                          trash=[1, 3]),
    "contexts_under_topk": dict(ctx=[3, 6, 0]),
    "two_head_blocks": dict(ctx=[0, 63, 64, 159], nkv=4, g=2,
                            vmem=288 << 10),
}


def _decode_rows_walk_their_own_pages(case, monkeypatch):
    """``paged_sparse_decode`` (interpreted: the interpreter runs its DMAs,
    its semaphores and its SMEM carry) against the gathered XLA op. The
    kernel's copy of the operands is POISONED wherever it must not look:
    table entries past a sequence's last block hold a block of NaN rows, an
    index past the pool and a negative one, in turn, and the index scores
    past each row's own position - which ``paged_index_scores`` never
    writes - are NaN."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    c = dict(dict(nkv=2, g=4, width=256, trash=(), vmem=None),
             **OWN_PAGES[case])
    monkeypatch.setattr(pa, "_DECODE_KV_TOKENS", 64)
    if c["vmem"]:
        monkeypatch.setattr(pa, "_TILE_VMEM", c["vmem"])
    rng = np.random.default_rng(3)
    L, nb, bs, mb, hd = 2, 48, 8, 20, 128
    nkv, nh, B, poison = c["nkv"], c["nkv"] * c["g"], len(c["ctx"]), nb - 1
    assert pa._fetches_pages(hd, False)
    pages, heads, n_kv = pa._decode_tiles(nkv, c["g"], hd, bs, mb, 4, False)
    assert (pages, heads, n_kv) == (8, 2, 3)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    k, v, q = f(L, nb, nkv, bs, hd), f(L, nb, nkv, bs, hd), f(B, nh, hd)
    tables = np.zeros((B, mb), np.int32)
    garbage = np.resize(np.asarray([poison, 10 ** 6, -3], np.int32), (B, mb))
    for b, x in enumerate(c["ctx"]):
        need = 0 if b in c["trash"] else x // bs + 1
        tables[b, :need] = garbage[b, :need] = rng.integers(1, poison, need)
    garbage[list(c["trash"])] = 0
    ctx = jnp.asarray(c["ctx"], jnp.int32)
    idx = f(B, 8, c["width"])
    tau, cut = sparse.paged_sparse_select_xla(idx[:, 0], ctx, topk=TOPK)
    past = np.arange(c["width"])[None, None] > np.asarray(c["ctx"])[:, None,
                                                                    None]
    got = sparse.paged_sparse_decode_attention(
        q, *(p.at[:, poison].set(jnp.nan) for p in (k, v)),
        jnp.where(past, jnp.nan, idx), tau, cut, jnp.asarray(garbage), ctx,
        layer=1)
    want = sparse.paged_sparse_decode_attention_xla(
        q, k, v, idx, tau, cut, jnp.asarray(tables), ctx, layer=1)
    assert got.shape == (B, nh, hd) and gap(got, want) < 1e-5
    # the span's counter says what the walk takes: each slot's own tiles
    tiles = sum(x // 64 + 1 for x in c["ctx"]) * (nkv // heads)
    assert pa.decode_tile_counts(c["ctx"], nh, k.shape, 4, mb,
                                 False) == (tiles, tiles)


@pytest.mark.parametrize("t", [16, 1] + sorted(OWN_PAGES))
def test_sparse_attention_interpreted_is_the_gathered_softmax(pools, t,
                                                              monkeypatch):
    """The two masked walks against the gathered softmax. ``t`` = 1 at the
    fixture's heads of 32 lanes is the grid of ``BlockSpec`` pages a decode
    call keeps where Mosaic cannot slice a page out of the pool (the
    multi-token walk at one token a sequence); the named cases are the walk
    that fetches its own pages."""
    if t in OWN_PAGES:
        return _decode_rows_walk_their_own_pages(t, monkeypatch)
    pool, ctx, lens = written(pools, sparse.paged_index_write_xla)
    if t == 1:
        ctx, lens, rows = jnp.asarray([21, 30]), jnp.ones(2, jnp.int32), 8
    else:
        rows = 16
    q_idx, w_idx, q = (pools[n][:, :t] for n in ("q_idx", "w_idx", "q"))
    idx = sparse.paged_index_scores_xla(q_idx, w_idx, pool, pools["tables"],
                                        ctx, lens, layer=1, rows=rows)
    q_abs = np.where(np.arange(rows)[None] < np.asarray(lens)[:, None],
                     np.asarray(ctx)[:, None] + np.arange(rows)[None], -1)
    width = 1 if t == 1 else rows
    tau, cut = sparse.paged_sparse_select_xla(
        idx[:, :width].reshape(2 * width, -1),
        jnp.asarray(q_abs[:, :width].reshape(-1)), topk=TOPK)
    tau, cut = tau.reshape(2, width), cut.reshape(2, width)
    kv = (pools["k"], pools["v"])
    if t == 1:
        from deepspeed_tpu.ops.pallas.paged_attention import _fetches_pages

        assert not _fetches_pages(q.shape[-1], False)
        got = sparse.paged_sparse_decode_attention(
            q[:, 0], *kv, idx, tau[:, 0], cut[:, 0], pools["tables"], ctx,
            layer=1)
        want = sparse.paged_sparse_decode_attention_xla(
            q[:, 0], *kv, idx, tau[:, 0], cut[:, 0], pools["tables"], ctx,
            layer=1)
        assert gap(got, want) < 1e-5
        return
    got = sparse.paged_sparse_prefill_attention(
        q, *kv, idx, tau, cut, pools["tables"], ctx, lens, layer=1)
    want = sparse.paged_sparse_prefill_attention_xla(
        q, *kv, idx, tau, cut, pools["tables"], ctx, lens, layer=1)
    real = (np.arange(16)[None, :] < np.asarray(lens)[:, None])[..., None, None]
    assert float(np.abs(np.where(real, got - want, 0)).max()) < 1e-5


# --- the masked prefill walk fetches its own pages (ISSUE 63) -------------- #
# heads of 128 lanes (whole lane tiles, so the walk fetches its own pages),
# blocks of 8 tokens, a table 24 wide, query tiles of 16 tokens and a KV tile
# held to 8 pages = 64 keys: ``ctx`` cached tokens, ``lens`` real rows of ``t``
MASKED_WALKS = {
    "many_tiles": dict(ctx=[150], lens=[16]),
    "context_zero": dict(ctx=[0], lens=[16]),
    "under_one_tile": dict(ctx=[30], lens=[16]),
    "ends_on_a_tiles_edge": dict(ctx=[48], lens=[16]),
    "one_key_past_a_tiles_edge": dict(ctx=[49], lens=[16]),
    "context_fills_the_table": dict(ctx=[176], lens=[16]),
    "three_query_tiles": dict(t=40, ctx=[140], lens=[40]),
    # fewer real rows than the call's: query tile 1 is cut short, tile 2
    # holds none, fetches nothing and writes zeros
    "chunk_shorter_than_its_last_query_tile": dict(t=40, ctx=[100],
                                                   lens=[20]),
    "padded_last_chunk": dict(t=40, ctx=[100], lens=[7]),
    "zero_length_dummies": dict(ctx=[140, 0, 30, 0], lens=[9, 0, 16, 0]),
    "every_sequence_a_dummy": dict(ctx=[0, 0], lens=[0, 0]),
    "group_1": dict(ctx=[150, 70], lens=[16, 16], nh=2, nkv=2),
    "group_8": dict(ctx=[150], lens=[16], nh=8, nkv=1),
    # 21 blocks: the last tile is five pages, and the scores are padded to
    # whole tiles
    "table_no_multiple_of_the_tile": dict(ctx=[150], lens=[16], table=21),
    "one_layers_4d_pools": dict(ctx=[150, 3], lens=[16, 5], layers=0),
    "fewer_keys_than_topk": dict(ctx=[2], lens=[3]),
}


def _masked_walk(c):
    """``(the walk's operands, the reference's)`` of one small case: the
    kernel's copy is POISONED wherever it must not look - table entries past
    a sequence's blocks (every entry of a zero-length dummy) hold a block of
    NaN rows, an index past the pool and a negative one, in turn, and the
    index scores past each row's own position, which the chunk's
    ``paged_index_scores`` never writes, are NaN and +inf."""
    c = dict(dict(t=16, nh=4, nkv=2, table=24, layers=2), **c)
    t, nh, nkv, table, bs, hd, nb = (c["t"], c["nh"], c["nkv"], c["table"],
                                     8, 128, 64)
    rng = np.random.default_rng(7)
    B, poison = len(c["ctx"]), nb - 1
    lead = (c["layers"],) if c["layers"] else ()
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    q, k, v = f(B, t, nh, hd), f(*lead, nb, nkv, bs, hd), \
        f(*lead, nb, nkv, bs, hd)
    tables = np.zeros((B, table), np.int32)
    garbage = np.resize(np.asarray([poison, 10 ** 6, -3], np.int32),
                        (B, table)).copy()
    for b, (x, n) in enumerate(zip(c["ctx"], c["lens"])):
        need = -(-(x + n) // bs) if n else 0
        tables[b, :need] = garbage[b, :need] = rng.integers(1, poison, need)
    rows = sparse.prefill_rows(t, nh, nkv, hd, bs, table)
    width = table * bs
    idx = f(B, rows, width)
    q_abs = np.where(np.arange(rows)[None] < np.asarray(c["lens"])[:, None],
                     np.asarray(c["ctx"])[:, None] + np.arange(rows)[None], -1)
    tau, cut = sparse.paged_sparse_select_xla(
        idx.reshape(B * rows, -1), jnp.asarray(q_abs.reshape(-1)), topk=TOPK)
    tau, cut = tau.reshape(B, rows), cut.reshape(B, rows)
    past = np.arange(width)[None, None] > q_abs[..., None]
    bad_idx = jnp.where(past, jnp.where(np.arange(width) % 2 == 0, jnp.nan,
                                        jnp.inf), idx)
    ctx, lens = (jnp.asarray(c[n], jnp.int32) for n in ("ctx", "lens"))
    kw = {"layer": lead[0] - 1} if lead else {}
    bad = [p.at[..., poison, :, :, :].set(jnp.nan) for p in (k, v)]
    return ((q, *bad, bad_idx, tau, cut, jnp.asarray(garbage), ctx, lens),
            (q, k, v, idx, tau, cut, jnp.asarray(tables), ctx, lens), kw)


def _small_masked_tiles(monkeypatch):
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_Q_ROWS", 32)
    monkeypatch.setattr(pa, "_KV_TOKENS", 16)
    monkeypatch.setattr(pa, "_WIDE_KV_TOKENS", 64)  # the plain walk's tile
    monkeypatch.setattr(sparse, "_PREFILL_PAGES", 8)


def _pallas_calls(fn, *args):
    """Every ``pallas_call`` equation of ``fn``'s jaxpr, jitted calls' too."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield e
            for v in e.params.values():
                if hasattr(getattr(v, "jaxpr", None), "eqns"):
                    yield from walk(v.jaxpr)
    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize("case", sorted(MASKED_WALKS))
def test_masked_prefill_walk_that_fetches_its_own_pages(case, monkeypatch):
    """``paged_sparse_prefill`` where ``_fetches_pages`` holds (interpreted:
    the interpreter runs its DMAs, its semaphores and its SMEM carry): ONE
    ``pallas_call`` on a grid (sequences, KV heads, query tiles) with no
    dimension of KV tiles, the pools and the index scores whole operands.
    Every real row is the gathered softmax's and - TO THE BIT - the grid of
    ``BlockSpec`` pages' at the same tile (the same tiles, the same flash
    sums in the same order), every row is finite over poisoned table entries
    and poisoned scores, and a query tile with no real row is zeros. The
    host's mirror counts the tiles the walk takes: the ones that hold
    context."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    _small_masked_tiles(monkeypatch)
    c = dict(dict(t=16, nh=4, nkv=2, table=24), **MASKED_WALKS[case])
    bad, clean, kw = _masked_walk(c)
    walk = functools.partial(sparse.paged_sparse_prefill_attention, **kw)
    assert pa._fetches_pages(128, False)
    shape = (c["nkv"], 8, 128)
    assert sparse.prefill_pages(c["t"], c["nh"], shape, c["table"], 4) == 8
    call, = _pallas_calls(walk, *bad)
    mapping, n_qt = call.params["grid_mapping"], -(-c["t"] // 16)
    assert mapping.grid == (len(c["ctx"]), c["nkv"], n_qt) \
        and mapping.num_dynamic_grid_bounds == 0
    out = np.asarray(walk(*bad))
    want = np.asarray(sparse.paged_sparse_prefill_attention_xla(*clean, **kw))
    assert out.shape == want.shape and np.isfinite(out).all()
    tiles = sum((x + min(q_lo + 16, n) - 1) // 64 + 1
                for x, n in zip(c["ctx"], c["lens"])
                for q_lo in range(0, n_qt * 16, 16) if q_lo < n)
    live, taken, wide = pa.prefill_tile_counts(
        c["ctx"], c["lens"], c["t"], c["nh"], shape, c["table"], itemsize=4,
        pages=8)
    assert live == taken == c["nkv"] * tiles \
        and wide == len(c["ctx"]) * c["nkv"] * n_qt * -(-c["table"] // 8)
    monkeypatch.setattr(sparse, "_fetches_pages", lambda *a: False)
    # a new function: nothing traced is kept
    call, = _pallas_calls(lambda *a: walk(*a), *bad)
    assert len(call.params["grid_mapping"].grid) == 4
    grid = np.asarray(walk(*bad))
    for b, n in enumerate(c["lens"]):
        assert gap(out[b, :n], want[b, :n]) < 1e-5 if n else True
        np.testing.assert_array_equal(out[b, :n], grid[b, :n])
        # whole query tiles of padding: nothing fetched, zeros written
        assert not out[b, -(-n // 16) * 16:].any()


def test_the_selection_is_an_operand_of_the_masked_walk_alone(monkeypatch):
    """The walk that fetches its own pages is ONE kernel body for both ops
    (``paged_attention._prefill_kernel``), and the selection exists in a
    call only where a caller hands it: ``paged_prefill`` keeps q and the two
    pools, two tile scratches and two rows of semaphores - the parent's
    program, which ``tests/test_chip_compile.py`` holds to its hash -, and
    ``paged_sparse_prefill`` has the rows' thresholds, the index scores, one
    more scratch (the scores' double-buffered ``[2, tq, KV]`` float32 tile)
    and one more row of semaphores."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    _small_masked_tiles(monkeypatch)
    (q, k, v, idx, tau, cut, tables, ctx, lens), _, kw = _masked_walk(
        MASKED_WALKS["many_tiles"])

    def operands(call):
        mapping = call.params["grid_mapping"]
        at = mapping.num_index_operands + mapping.num_inputs \
            + mapping.num_outputs
        scratch = [v.aval for v in call.params["jaxpr"].invars[at:]]
        return (call.params["name"], mapping.num_index_operands,
                mapping.num_inputs, [tuple(a.shape) for a in scratch])

    plain, = _pallas_calls(lambda *a: pa.paged_prefill_attention(*a, **kw),
                           q, k, v, tables, ctx, lens)
    flash = [(32, 128), (32, 128), (32, 128)]       # m, l, the accumulator
    assert operands(plain) == ("paged_prefill", 4, 3, [
        (2, 64, 128), (2, 64, 128), (2, 2), (1,)] + flash)
    masked, = _pallas_calls(
        lambda *a: sparse.paged_sparse_prefill_attention(*a, **kw),
        q, k, v, idx, tau, cut, tables, ctx, lens)
    assert operands(masked) == ("paged_sparse_prefill", 4, 6, [
        (2, 64, 128), (2, 64, 128), (2, 16, 64), (3, 2), (1,)] + flash)


# --- one chip's share of the expert bank ----------------------------------- #
def test_the_shares_of_the_bank_add_up_to_the_whole_layer():
    """Four shares of two experts each, attention counted once: the parts of
    the MoE layer's output that the shares give add up to the uncut layer's,
    in the program and in the reference."""
    rng = jax.random.PRNGKey(3)
    params = init_moe_ffn(rng, 8, 64, 32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 64))
    whole, aux = MoELayer(8, 4, drop_tokens=False)(params, x)
    parts = []
    for first in (0, 2, 4, 6):
        share = {k: v if k == "router" else v[first:first + 2]
                 for k, v in params.items()}
        out, aux_s = MoELayer(8, 4, drop_tokens=False,
                              held=(first, 2))(share, x)
        assert float(aux_s) == float(aux)     # the gating is the whole layer's
        parts.append(out)
    assert gap(sum(parts), whole) < 1e-5
    # the reference: an uncut layer against its four shares' expert sums
    cfg, params, row = build()
    weights = family.Weights(params)
    x = weights.embed[jnp.asarray(row)].astype(jnp.float32)
    frozen = reference._freeze(TINY)
    with jax.default_matmul_precision("highest"):
        whole = reference.layer(x, weights.layer(0), frozen)
        attn, _, _ = reference._attention_and_route(
            x, {k: v for k, v in weights.layer(0).items() if k != "experts"},
            frozen, reference.learned_selection, True)
        total = attn
        for first in (0, 2, 4, 6):
            hf = {**TINY, "num_experts": 2, "experts_first": first}
            w = dict(weights.layer(0))
            w["experts"] = w["experts"][first:first + 2]
            total = total + reference.layer(x, w, reference._freeze(hf)) - attn
    assert gap(total, whole) < 1e-4


def test_a_held_range_of_every_expert_is_the_layer_bit_for_bit():
    params = init_moe_ffn(jax.random.PRNGKey(3), 8, 64, 32)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, 64))
    whole, _ = MoELayer(8, 4, drop_tokens=False)(params, x)
    held, _ = MoELayer(8, 4, drop_tokens=False, held=(0, 8))(params, x)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(held))
    with pytest.raises(ValueError, match="range"):
        MoELayer(8, 4, held=(6, 4))
    with pytest.raises(ValueError, match="einsum"):
        MoELayer(8, 4, dispatch="compact", held=(0, 2))


def test_rows_of_a_held_range_are_the_shares(one_device):
    cfg = family.build_cfg(HELD, drop_tokens=False)
    # 16 rows x 4 experts a token, 2 of 8 experts held: 16 routed rows
    # expected here; the grouped bank computes a 16-row tile of each of the
    # two (each is reached: 1 - 2^-16)
    assert mixtral.moe_rows(cfg, 16) == {"moe_rows_routed": 16,
                                         "moe_rows_computed": 32,
                                         "moe_row_tile": 16}
    assert mixtral.sparse_rows(cfg, [3, 8, 20]) == {
        "sparse_rows": 3, "sparse_ctx_scored": 31, "sparse_kv_selected": 19}
    assert mixtral.sparse_rows(mixtral.MixtralConfig.tiny(), [3]) == {}
    shapes = jax.eval_shape(lambda k: mixtral.init(cfg, k),
                            jax.random.PRNGKey(0))["layers"]
    assert shapes["moe"]["router"].shape == (2, 64, 8)
    assert shapes["moe"]["w_gate"].shape == (2, 2, 64, 32)
    assert shapes["wq"].shape == (2, 64, 4 * 32)      # head_dim is its own
    assert shapes["q_norm"].shape == (2, 32)
    assert shapes["wq_idx"].shape == (2, 64, 64) and \
        shapes["wk_idx"].shape == (2, 64, 16) and \
        shapes["ww_idx"].shape == (2, 64, 4)
    axes = mixtral.param_logical_axes(cfg)["layers"]
    assert set(axes) == set(shapes)


# --- the engine over three pools ------------------------------------------- #
ENGINE = {"dtype": "float32", "prefill_bucket": 8, "split_prefill_chunk": 16,
          "trace": {"enabled": True},
          "ragged": {"max_tracked_sequences": 4, "max_ragged_batch_size": 4,
                     "memory_config_blocks": 64, "block_size": 8}}


@pytest.fixture(scope="module")
def served():
    cfg, params, _ = build(HELD)
    return cfg, params


def engine(served, **config):
    """An engine that computes in float32 and whose pools are float32 too
    (the engine's ``dtype`` is its weights'; ``apply_paged`` computes in bf16
    and the pools are bf16 unless told otherwise, and a selection over bf16
    index keys may rightly take another token than the float32 reference's
    at a threshold - at 8 of 40 tokens one token is a large part of the
    mix)."""
    cfg, params = served
    module = family.module()
    module.apply_paged = functools.partial(mixtral.apply_paged,
                                           compute_dtype=jnp.float32)
    eng = build_engine_v2(module, cfg, params, config={**ENGINE, **config})
    eng.cache = jax.tree.map(lambda c: c.astype(jnp.float32), eng.cache)
    return eng


def gaps(eng, prompt, out):
    tokens = np.asarray(list(prompt) + out[:-1], np.int32)
    want = reference.logits(HELD, family.Weights(eng.params),
                            tokens)[len(prompt) - 1:]
    return want.max(-1) - want[np.arange(len(out)), out]


def prompts(*lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n).tolist() for n in lengths]


def test_step_serves_the_references_tokens(served):
    """A one-shot prompt and a prompt that enters by chunks beside its
    decodes (the mixed program), contexts from under ``TOPK`` to five times
    it: every served token is the top of the reference's logits, and the
    spans and counters say what one layer's selection did."""
    eng = engine(served)
    a, b = prompts(6, 37)
    first = [int(eng.put(1, a))]
    eng.put_split(2, b)
    second = []
    while len(second) < 5:
        out = eng.step()
        first += [out[1]] if 1 in out else []
        second += [out[2]] if 2 in out else []
    assert float(gaps(eng, a, first).max()) < 1e-3
    assert float(gaps(eng, b, second).max()) < 1e-3
    cache = eng.cache
    assert set(cache) == {"k", "v", "kI"}
    assert cache["kI"].shape == (2, 64, 1, 1, 128)   # 8 keys of 16 a row
    # a block, two layers: K and V (2 heads x 8 tokens x 32) and 8 index
    # keys of 16, in these pools' float32
    assert eng.kv_headroom()["block_bytes"] == 2 * (
        2 * 2 * 8 * 32 * 4 + 8 * 16 * 4)
    steps = [s for s in eng.tracer.events() if s["name"] == "decode_step"]
    mixed = [s for s in steps if s["args"].get("chunk_tokens")]
    assert mixed and all("chunk_sparse_ctx_scored" in s["args"]
                         for s in mixed)
    one = mixed[0]["args"]      # b's first chunk: rows at contexts 1..16
    assert one["chunk_sparse_ctx_scored"] == 16 * 17 // 2
    assert one["chunk_sparse_kv_selected"] == 36 + 8 * TOPK
    assert one["sparse_ctx_scored"] == len(a) + 1   # the one decode row
    # its scores' one tile; these pools' pages are one row of eight keys, no
    # whole tile, so the call is the grid: every slot as far as the longest
    assert (one["index_tiles_live"], one["index_tiles_grid"],
            one["index_live_tile_share"]) == (1, 4, 0.25)
    events = dict((n, v) for n, v, _ in eng.sparse_events())
    assert events["Serving/sparse/rows"] > 37
    assert events["Serving/sparse/kv_selected"] < \
        events["Serving/sparse/ctx_scored"]
    from deepspeed_tpu.telemetry.schema import SERVING_SERIES
    assert set(events) <= SERVING_SERIES


def test_generate_lands_the_selections_counts_in_the_hub(served):
    """``generate`` with a telemetry hub publishes ``Serving/sparse/*`` as
    it does a recurrent family's ``Serving/state/*``."""
    class Hub:
        def __init__(self):
            self.events = []

        def serving_event(self, name, value, step=0):
            self.events.append((name, value, step))

    cfg, params = served
    eng = build_engine_v2(family.module(), cfg, params,
                          telemetry_hub=(hub := Hub()), config=ENGINE)
    eng.generate(prompts(12, 20), max_new_tokens=3)
    landed = {n: v for n, v, _ in hub.events if n.startswith("Serving/sparse")}
    assert landed == {n: v for n, v, _ in eng.sparse_events()}
    # the chunked prompt's rows and the decodes' (a one-shot prefill's span
    # carries none)
    assert landed["Serving/sparse/rows"] >= 20


def test_the_scheduler_serves_the_references_tokens_overlapped(served):
    """``ServingScheduler.tick``: the launch ahead of the read and the chunk
    lane, three requests of which two enter by chunks."""
    eng = engine(served)
    sched = ServingScheduler(eng, SchedulerConfig(decode_quantum=1,
                                                  max_admissions_per_tick=1))
    sent = prompts(9, 40, 27, seed=1)
    handles = [sched.submit(Request(prompt=p, max_new_tokens=5)) for p in sent]
    for _ in range(40):
        sched.tick()
        if all(h.done for h in handles):
            break
    assert all(h.done for h in handles)
    for p, h in zip(sent, handles):
        assert float(gaps(eng, p, [int(t) for t in h.tokens]).max()) < 1e-3
    assert eng.mixed_steps > 0 and eng.overlapped_steps > 0


def test_a_fork_carries_the_index_keys(served):
    """``fork`` shares every block, the partial tail too, and copy-on-write
    copies a block of EVERY pool: parent and child both continue as the
    reference does."""
    eng = engine(served)
    (p,) = prompts(21, seed=2)
    out = [int(eng.put(1, p))]
    out.append(int(eng.step()[1]))
    eng.fork(1, 2)
    both = {1: list(out), 2: list(out)}
    for _ in range(4):
        for uid, tok in eng.step().items():
            both[uid].append(int(tok))
    assert both[1] == both[2]                 # greedy: the same stream
    assert float(gaps(eng, p, both[2]).max()) < 1e-3
    eng.state.debug_check()


REFUSED_AT_CONFIGURATION = {
    "prefix_cache": {"prefix_cache": {"enabled": True}},
    "host_spill": {"prefix_cache": {"enabled": False, "host_spill": True}},
    "speculative": {"speculative": {"enabled": True}},
    "kv_quant": {"kv_quant": {"enabled": True}},
}


@pytest.mark.parametrize("feature", sorted(REFUSED_AT_CONFIGURATION))
def test_what_knows_two_pools_is_refused_at_configuration(served, feature):
    with pytest.raises(IndexPoolError, match="learned token"):
        engine(served, **REFUSED_AT_CONFIGURATION[feature])


@pytest.mark.parametrize("call", ["export_kv_blocks", "import_kv_blocks"])
def test_the_disagg_wire_is_refused_at_its_call(served, call):
    eng = engine(served)
    eng.put(1, prompts(9)[0])
    args = {"export_kv_blocks": (1,), "import_kv_blocks": ([], [])}[call]
    with pytest.raises(IndexPoolError, match=call):
        getattr(eng, call)(*args)
    eng.state.debug_check()


def test_a_quantized_index_pool_is_refused_by_the_family():
    cfg = family.build_cfg(HELD, drop_tokens=False)
    with pytest.raises(IndexPoolError, match="kv_quant"):
        mixtral.init_paged_cache(cfg, 8, 8, kv_quant_group=32)
    off = dataclasses.replace(cfg, sparse_attention=None)
    assert set(mixtral.init_paged_cache(off, 8, 8)) == {"k", "v"}


def test_other_families_load_none_of_the_selections_kernels():
    import subprocess
    import sys

    code = ("import sys, deepspeed_tpu, deepspeed_tpu.models.mixtral, "
            "deepspeed_tpu.inference.engine_v2, deepspeed_tpu.ops.pallas\n"
            "bad = [m for m in sys.modules if 'paged_sparse' in m]\n"
            "assert not bad, bad")
    subprocess.run([sys.executable, "-c", code], check=True,
                   env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
                        "PYTHONPATH": ":".join(sys.path)})
