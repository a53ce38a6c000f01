"""Compile — not just lower — for a described TPU v5e, without the chip.

The TPU compiler is installed here and compiles for a ``v5e:2x2`` that is
described, not attached (``jax.experimental.topologies``): Mosaic refuses a
misaligned slice or too much VMEM, XLA refuses a program that does not fit
16 GB, and ``compiled.as_text()`` shows whether the kernel is really in the
program (``tpu_custom_call``). Nothing runs, so this says nothing about
results or times. The cases are the kernels of the two main paths at the
widths ``chip_smoke.py`` uses (Mistral-7B: 32 query / 8 KV heads of 128,
hidden 4096) and its serving pool geometry.

Code that asks ``jax.default_backend()`` still sees the CPU here, so the
tests steer the one platform test of the op tier (``registry.on_tpu``).
"""

import dataclasses
import functools
import math
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs in /tmp
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

import chip_smoke

MOSAIC = chip_smoke.MOSAIC
_CFG, _SERVE = chip_smoke.mistral_7b(2), chip_smoke.ServeSize()
NQ, NKV, HD, HIDDEN = (_CFG.num_heads, _CFG.num_kv_heads, _CFG.head_size,
                       _CFG.hidden_size)            # Mistral-7B widths
SLOTS, POOL, BS = _SERVE.slots, _SERVE.pool_blocks, _SERVE.block_size
MAX_BLOCKS = _CFG.max_seq_len // BS


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    """Kernels lower through Mosaic and the registry prefers Pallas, as on
    the chip, and the process's mesh is one device's, as a one-chip host's
    is (a test over four chips makes its own); the persistent compile cache
    is off, because an executable compiled for a described chip cannot be
    read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import registry
    from deepspeed_tpu.ops.pallas import _common

    monkeypatch.setattr(registry, "on_tpu", lambda: True)
    monkeypatch.setattr(_common, "on_tpu", lambda: True)
    mesh_lib.init_mesh({"data": 1}, devices=jax.devices()[:1])
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes, device):
    """``shapes``: (shape, dtype) pairs, placed on one described chip."""
    sh = SingleDeviceSharding(device)
    args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _flash(**kw):
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    return lambda q, k, v: flash_attention(q, k, v, causal=True, **kw)


def _flash_under_highest(q, k, v):
    # bf16 operands at fp32 contract precision are what Mosaic refuses; the
    # kernels pin their own precision so a caller's scope cannot ask for it
    with jax.default_matmul_precision("highest"):
        return _flash()(q, k, v)


def _flash_grad(**kw):
    f = _flash(**kw)

    def loss(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32) ** 2)

    return jax.grad(loss, argnums=(0, 1, 2))


def _qkv(b, sq, skv=None):
    bf = jnp.bfloat16
    skv = skv or sq
    return (((b, sq, NQ, HD), bf), ((b, skv, NKV, HD), bf),
            ((b, skv, NKV, HD), bf))


def _paged(**kw):
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    return lambda q, kp, vp, bt, cl, *scales: pa.paged_decode_attention(
        q, kp, vp, bt, cl, **kw,
        **(dict(k_scale=scales[0], v_scale=scales[1]) if scales else {}))


def _pool_args(q_shape, pool_dtype=jnp.bfloat16):
    pool = ((POOL, NKV, BS, HD), pool_dtype)
    return ((q_shape, jnp.bfloat16), pool, pool,
            ((SLOTS, MAX_BLOCKS), jnp.int32), ((SLOTS,), jnp.int32))


def _spec_verify(q, kp, vp, bt, cl):
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    return pa.paged_spec_verify_attention(q, kp, vp, bt, cl)


def _rms_norm(x, w):
    from deepspeed_tpu.ops.pallas.norms import rms_norm_pallas

    return rms_norm_pallas(x, w)


def _quantize(x):
    from deepspeed_tpu.ops.pallas.quantize import quantize_int8_pallas

    return quantize_int8_pallas(x, group_size=HD)


_SCALES = (((POOL, NKV, BS, 1), jnp.float32),) * 2
KERNEL_CASES = {
    "flash_fwd_s2048": (_flash(), _qkv(2, 2048)),
    "flash_fwd_under_highest_precision": (_flash_under_highest,
                                          _qkv(2, 2048)),
    "flash_fwd_bwd_s2048": (_flash_grad(), _qkv(2, 2048)),
    "flash_fwd_bwd_s8192": (_flash_grad(), _qkv(1, 8192)),
    "flash_windowed_fwd_bwd": (_flash_grad(window=4096), _qkv(1, 8192)),
    "flash_q512_on_kv4096_offset": (_flash(q_offset=3584),
                                    _qkv(1, 512, 4096)),
    "paged_decode": (_paged(), _pool_args((SLOTS, NQ, HD))),
    "paged_decode_windowed": (_paged(window=4096),
                              _pool_args((SLOTS, NQ, HD))),
    "paged_decode_int8_kv": (_paged(),
                             _pool_args((SLOTS, NQ, HD), jnp.int8) + _SCALES),
    "spec_verify_t4": (_spec_verify, _pool_args((SLOTS, 4, NQ, HD))),
    "rms_norm_fwd_h4096": (_rms_norm, (((2 * 2048, HIDDEN), jnp.bfloat16),
                                       ((HIDDEN,), jnp.bfloat16))),
    "int8_group_quantize": (_quantize, (((2048 * HIDDEN,), jnp.bfloat16),)),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    fn, shapes = KERNEL_CASES[case]
    compiled = _compile(fn, *shapes, device=v5e.devices[0])
    assert MOSAIC in compiled.as_text(), \
        f"{case}: compiled without a Mosaic kernel"


# the serve cells' geometry (benchmark/configs: max context 8192 in blocks of
# 32, so a table 256 wide; serve-chat's pool of 896 blocks)
CELL_BS, CELL_TABLE, CELL_POOL = 32, 256, 896


def _prefill_step(quant, window=None):
    """The multi-token branch of the model step as a chunk's ``[1, t]``
    call traces it: each sequence's K/V written into the pool, then the op. A
    ``"traced"`` window is the step's last argument."""
    from deepspeed_tpu.models._paged import LayerPool, paged_attention_step

    def step(q, k, v, kp, vp, table, ctx, n_valid, *rest):
        t = q.shape[1]
        positions = ctx[:, None] + jnp.arange(t)[None, :]
        valid = jnp.arange(t)[None, :] < n_valid[:, None]
        # the pools of a one-layer model, at its layer
        kp, vp = (LayerPool(p[None], rest[i][None] if quant else None,
                            jnp.int32(0)) for i, p in enumerate((kp, vp)))
        return paged_attention_step(
            q, k, v, kp, vp, table, ctx, positions, valid,
            window=rest[-1] if window == "traced" else window)[0]

    return step


def _prefill_args(t, quant):
    bf, i32 = jnp.bfloat16, jnp.int32
    pool = ((CELL_POOL, NKV, CELL_BS, HD), jnp.int8 if quant else bf)
    scales = (((CELL_POOL, NKV, CELL_BS, 1), jnp.float32),) * 2
    return (((1, t, NQ, HD), bf), ((1, t, NKV, HD), bf),
            ((1, t, NKV, HD), bf), pool, pool, ((1, CELL_TABLE), i32),
            ((1,), i32), ((1,), i32)) + (scales if quant else ())


@pytest.mark.parametrize("t", [256, 2816])   # a chunk; a whole prompt, unsplit
@pytest.mark.parametrize("pool", ["bf16", "int8"])
def test_prefill_attention_walks_the_table_at_the_cells_geometry(v5e, t,
                                                                 pool):
    """Prefill at a context offset is the flash kernel over the block table:
    the Mosaic call is in the program, and no f32 score buffer over the
    table's 8192 positions is (the gathered path's ``[1, 32, t, 8192]``)."""
    import re

    from deepspeed_tpu.ops import registry

    assert registry.resolved()["paged_prefill_attention"] == "pallas"
    quant = pool == "int8"
    text = _compile(_prefill_step(quant), *_prefill_args(t, quant),
                    device=v5e.devices[0]).as_text()
    assert MOSAIC in text and "paged_prefill" in text
    wide = CELL_TABLE * CELL_BS
    assert not re.search(rf"f32\[[0-9,]*\b{wide}\]", text)


# the serve cells' decode geometry: slots, query heads, KV heads, key width,
# value width (None: a V pool), block, table width, pool blocks, the kind's
# window -> (pages a KV tile, KV heads a grid step, tiles the table holds)
CELL_DECODES = {
    "mistral-7b.serve-chat": (32, 32, 8, HD, None, 32, 256, 896, None,
                              (16, 8, 16)),
    "mixtral-8x7b.serve-longprompt": (16, 32, 8, HD, None, 32, 256, 1536,
                                      None, (16, 8, 16)),
    "olmoe-1b-7b.serve-longprompt": (16, 16, 16, HD, None, 32, 128, 1536,
                                     None, (8, 16, 16)),
    "command-a-plus-05-2026.serve-longctx/full": (
        16, 128, 8, HD, None, 32, 1024, 12544, None, (16, 8, 64)),
    "command-a-plus-05-2026.serve-longctx/window": (
        16, 128, 8, HD, None, 32, 145, 2321, 4096, (16, 8, 10)),
    "a.x-k1.serve-longctx/latent": (16, 64, 1, 640, 512, 128, 256, 3152,
                                    None, (8, 1, 32)),
    "keye-vl-2.0-30b-a3b.serve-longctx": (8, 32, 4, HD, None, 32, 1024, 7808,
                                          None, (32, 4, 32)),
}
# the cells whose decode rows bring a learned selection: the same walk
# through ``paged_sparse_decode_attention``, with the row's index scores
# ``[slots, 8, table tokens]`` and its threshold as operands
SELECTED = {"keye-vl-2.0-30b-a3b.serve-longctx"}
# every cell's walk as the cell runs it (its kind's window, a traced layer
# of 5-D pools), and the three oldest cells' under a window and over int8
# pools, which no cell runs
DECODE_LOWERINGS = [(cell, "bf16") for cell in sorted(CELL_DECODES)] + [
    (cell, pool) for cell in sorted(CELL_DECODES)[-3:]
    for pool in ("windowed", "int8")]


@pytest.mark.parametrize("cell,pool", DECODE_LOWERINGS)
def test_decode_kernel_lowers_at_the_cells_geometry(v5e, cell, pool):
    """The decode walk compiles for the chip at each serve cell's shapes -
    page DMAs out of pools left where they lie, a double-buffered tile of
    every KV head in VMEM; int8 pools on their grid of ``BlockSpec`` pages -
    as ONE Mosaic call, still the instruction ``paged_decode.N`` that
    ``paged_decode_roofline``, ``mixed_kv_decode_roofline`` and
    ``mla_decode_roofline`` look for - ``paged_sparse_decode.N``, which
    ``sparse_attn_roofline`` looks for, where the rows bring a selection."""
    import re

    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.pallas import paged_sparse_attention as sparse

    slots, nq, nkv, hd, vd, bs, table, blocks, window, _ = CELL_DECODES[cell]
    quant = pool == "int8"
    name = "paged_sparse_decode" if cell in SELECTED else "paged_decode"
    window = 4096 if pool == "windowed" else window
    lead = () if quant else (2,)    # a layer's scale pools are cut out
    kv = (lead + (blocks, nkv, bs, hd), jnp.int8 if quant else jnp.bfloat16)
    shapes = (((slots, nq, hd), jnp.bfloat16),) + (kv,) * (1 if vd else 2) \
        + (((slots, table), jnp.int32), ((slots,), jnp.int32), ((), jnp.int32))
    if quant:
        shapes += (((blocks, nkv, bs, 1), jnp.float32),) * 2
    if cell in SELECTED:
        shapes += (((slots, 8, table * bs), jnp.float32),) \
            + (((slots,), jnp.int32),) * 2

    def fn(q, k, *rest):
        v, (bt, cl, layer, *more) = (None, rest) if vd \
            else (rest[0], rest[1:])
        if cell in SELECTED:
            return sparse.paged_sparse_decode_attention(
                q, k, v, *more, bt, cl, layer=layer)
        return pa.paged_decode_attention(
            q, k, v, bt, cl, window=window, value_width=vd,
            layer=None if quant else layer,
            **(dict(k_scale=more[0], v_scale=more[1]) if quant else {}))

    text = _compile(fn, *shapes, device=v5e.devices[0]).as_text()
    calls = re.findall(r"%(\S+) = \S+ custom-call\(.*" + MOSAIC, text)
    assert len(calls) == 1 and re.fullmatch(name + r"(\.\d+)?", calls[0])


def test_the_decode_rows_scores_fetch_their_own_index_pages(v5e):
    """``paged_index_scores`` at one token a sequence and the Keye cell's
    geometry (8 slots, a 1 024-block table, 16 index heads of 64 over the
    packed ``[12, 7808, 1, 16, 128]`` pool) compiles for the chip as ONE
    Mosaic call, still the instruction ``paged_index_scores.N`` that
    ``sparse_index_roofline`` looks for, and the index pool reaches it ONCE,
    where it lies - not once a page of a grid step's tile."""
    import re

    from deepspeed_tpu.ops.pallas import paged_sparse_attention as sparse

    slots, _, _, _, _, bs, table, blocks, _, _ = CELL_DECODES[
        "keye-vl-2.0-30b-a3b.serve-longctx"]
    heads, d = 16, 64
    pool = sparse.index_pool_shape(12, blocks, bs, d)
    assert pool == (12, 7808, 1, 16, 128)
    assert sparse._fetches_index_pages(pool)

    def fn(q_idx, w_idx, pool, tables, ctx, lens, layer):
        return sparse.paged_index_scores(q_idx, w_idx, pool, tables, ctx,
                                         lens, layer=layer, rows=8)

    compiled = _compile(
        fn, ((slots, 1, heads, d), jnp.bfloat16),
        ((slots, 1, heads), jnp.bfloat16), (pool, jnp.bfloat16),
        ((slots, table), jnp.int32), ((slots,), jnp.int32),
        ((slots,), jnp.int32), ((), jnp.int32), device=v5e.devices[0])
    text = compiled.as_text()
    calls = re.findall(r"%(\S+) = (\S+) custom-call\((.*?)\), .*" + MOSAIC,
                       text)
    assert len(calls) == 1, calls
    name, result, operands = calls[0]
    assert re.fullmatch(r"paged_index_scores(\.\d+)?", name)
    assert result.startswith(f"f32[{slots},8,{table * bs}]")
    # the tables, contexts, rows and layer, the queries and their weights,
    # and the pool: 7 operands (the grid of pages had the pool 32 times)
    assert operands.count("%") == 7, operands


def test_the_masked_prefill_walk_fetches_its_own_pages(v5e):
    """``paged_sparse_prefill`` at the Keye cell's geometry (a 512-row chunk
    of 32 query heads over 4 KV heads of 128, 32-token blocks, a 1 024-block
    table, the ``[12, 7808, 4, 32, 128]`` pools) compiles for the chip -
    inside Mosaic's VMEM, the scores' double-buffered ``[128, 1024]`` float32
    tile beside the plain walk's - as ONE Mosaic call, still the instruction
    ``paged_sparse_prefill.N`` that ``sparse_attn_roofline`` looks for, and
    the K and V pools reach it ONCE each, where they lie - not once a page
    of a grid step's tile (the grid of ``BlockSpec`` pages had 64 page
    operands). Its page copies are unrolled where the loop is lowered: the
    plain walk's starts and waits a pool and the scores' one a site."""
    import re

    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.pallas import paged_sparse_attention as sparse

    _, nh, nkv, hd, _, bs, table, blocks, _, _ = CELL_DECODES[KEYE_CELL]
    t, bf, i32 = 512, jnp.bfloat16, jnp.int32
    assert pa._fetches_pages(hd, False)
    assert sparse.prefill_rows(t, nh, nkv, hd, bs, table) == t
    assert sparse.prefill_pages(t, nh, (nkv, bs, hd), table) * bs == 1024

    def fn(q, k, v, idx, tau, cut, tables, ctx, lens, layer):
        return sparse.paged_sparse_prefill_attention(
            q, k, v, idx, tau, cut, tables, ctx, lens, layer=layer)

    pool = ((12, blocks, nkv, bs, hd), bf)
    shapes = (((1, t, nh, hd), bf), pool, pool,
              ((1, t, table * bs), jnp.float32), ((1, t), i32), ((1, t), i32),
              ((1, table), i32), ((1,), i32), ((1,), i32), ((), i32))
    text = _compile(fn, *shapes, device=v5e.devices[0]).as_text()
    calls = re.findall(r"%(\S+) = (\S+) custom-call\((.*?)\), .*" + MOSAIC,
                       text)
    assert len(calls) == 1, calls
    name, result, operands = calls[0]
    assert re.fullmatch(r"paged_sparse_prefill(\.\d+)?", name)
    assert result.startswith(f"bf16[1,{nkv},{t * nh // nkv},{hd}]")
    # the tables, contexts, lengths and layer, q, tau and cut, K, V and the
    # index scores: 10 operands
    assert operands.count("%") == 10, operands
    counts = _count_equations(jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(*s) for s in shapes)).jaxpr, {})
    assert (counts["pallas_call"], counts["dma_start"],
            counts["dma_wait"]) == (1, 4 * 2 + 2, 2 * 2 + 1)
    assert sum(counts.values()) < 500


@pytest.mark.parametrize("cell", sorted(CELL_DECODES))
def test_decode_grid_is_sized_by_the_shapes(cell):
    """The walk's tile comes from the cell's shapes and the VMEM budget
    alone: every KV head a grid step, and the widest doubling of ~256 tokens
    up to ``_DECODE_KV_TOKENS`` whose two tiles fit - and a call takes the
    tiles its slots' contexts hold, not slots x the longest's."""
    from deepspeed_tpu.ops.pallas.paged_attention import (_decode_tiles,
                                                          decode_tile_counts)

    slots, nq, nkv, hd, vd, bs, table, _, _, tiles = CELL_DECODES[cell]
    pools = 1 if vd else 2
    assert _decode_tiles(nkv, nq // nkv, hd, bs, table, 2, False,
                         pools) == tiles
    pages, heads, n_kv = tiles
    assert heads == nkv and n_kv == -(-table // pages)
    ctx = [0, pages * bs - 1, pages * bs, table * bs - 1][:slots]
    assert decode_tile_counts(ctx, nq, (nkv, bs, hd), 2, table, False,
                              pools) == (4 + n_kv,) * 2


# --- the pools stay where they are (ISSUE 29) ------------------------------ #
SERVE_CELLS = ("mistral-7b.serve-chat", "mixtral-8x7b.serve-longprompt",
               "olmoe-1b-7b.serve-longprompt")
POOL_DEPTH = 2      # the layer scan makes the program the same at any depth


def _cell_forward(cell_name, quant, depth=POOL_DEPTH):
    """A serve cell's paged forward on shapes, at its published widths and
    its pool geometry (``benchmark/configs``), ``depth`` layers deep (None:
    as the cell runs it):
    ``(forward(params, cache, tokens, tables, ctx, valid, rows=None) ->
    (logits, cache), params, cache, slots, chunk, table width)``."""
    from benchmark.harness.manifest import Cell

    cell = Cell(cell_name)
    engine = cell.role["engine"]
    ragged = engine["ragged"]
    cfg = cell.family.build_cfg(
        {**cell.model,
         "num_hidden_layers": depth or cell.model["num_hidden_layers"]},
        **cell.role["program_options"])
    module = cell.family.module()
    params = jax.eval_shape(lambda k: module.init(cfg, k),
                            jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16), params)
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], ragged["block_size"],
        **({"kv_quant_group": cfg.head_size} if quant else {})))

    def forward(params, cache, tokens, tables, ctx, valid, rows=None):
        return module.apply_paged(cfg, params, tokens, cache, tables, ctx,
                                  valid=valid, rows=rows)

    return (forward, params, cache, ragged["max_tracked_sequences"],
            engine["split_prefill_chunk"],
            cfg.max_seq_len // ragged["block_size"])


def _pool_program(cell_name, program, quant):
    """``(function, arguments)`` of ``program`` over the cell's pools, the
    cache its second argument: one chunk of one sequence, one decode tick of
    every slot, or ``decode_many``'s scan of ticks around the layer scan
    (greedy, as ``engine_v2._decode_fn`` nests it)."""
    forward, params, cache, slots, chunk, table = _cell_forward(cell_name,
                                                                quant)
    i32, s = jnp.int32, jax.ShapeDtypeStruct
    b, t = (1, chunk) if program == "chunk" else (slots, 1)
    args = (params, cache, s((b, t), i32), s((b, table), i32), s((b,), i32),
            s((b, t), bool))
    if program != "decode_many":
        return forward, args

    def decode_many(params, cache, tokens, tables, ctx, valid):
        def tick(carry, _):
            tokens, ctx, cache = carry
            logits, cache = forward(params, cache, tokens, tables, ctx, valid)
            nxt = jnp.argmax(logits[:, 0], axis=-1).astype(i32)
            return (nxt[:, None], ctx + 1, cache), nxt

        (_, _, cache), toks = jax.lax.scan(tick, (tokens, ctx, cache), None,
                                           length=4)
        return toks, cache

    return decode_many, args


POOL_PROGRAMS = [(cell, program, pool) for cell in SERVE_CELLS
                 for program in ("chunk", "decode")
                 for pool in ("bf16", "int8")] \
    + [(cell, "decode_many", "bf16") for cell in SERVE_CELLS]


@pytest.mark.parametrize("cell,program,pool", POOL_PROGRAMS)
def test_the_pools_stay_where_they_are(compiled_for_v5e, cell, program, pool):
    """The KV pools are ONE ``[L, ...]`` buffer from a program's donated
    argument to its result: the compiled program holds no copy, slice,
    update, buffer or loop fusion the size of a pool or of a layer's pool
    (``pool_copy_bytes``, the compile span's counter), aliases the pools
    argument-to-result, keeps less than a layer's pool of temporaries, and
    its layer body is one ``paged_kv_write`` and one attention kernel. In
    int8 mode that holds for the code pools; a layer's ``[.., bs, 1]`` f32
    scale pool still goes to the kernels lane-padded (PERF.md section 7)."""
    import math
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    compiled, args = compiled_for_v5e("pool", cell, program, pool == "int8")
    text, mem = compiled.as_text(), compiled.memory_analysis()
    cache = args[1]

    def nbytes(a):
        return math.prod(a.shape) * a.dtype.itemsize

    assert pool_copy_bytes(text, [cache["k"], cache["v"]]) == 0
    assert mem.alias_size_in_bytes >= sum(map(nbytes, jax.tree.leaves(cache)))
    layer_pool = nbytes(cache["k"]) // POOL_DEPTH
    if pool == "bf16":
        assert pool_copy_bytes(text, jax.tree.leaves(cache)) == 0
        # (the scan of ticks keeps the model's own temporaries twice)
        assert mem.temp_size_in_bytes < layer_pool * (
            2 if program == "decode_many" else 1)
    else:   # two layer scale pools, their last dim of 1 padded to 128 lanes
        padded = 2 * 128 * nbytes(cache["k_scale"]) // POOL_DEPTH
        assert mem.temp_size_in_bytes < padded + layer_pool
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    # a chunk's walk is ONE Mosaic call: over bf16 pools the walk that
    # fetches its own pages, at one tile width (ISSUE 62: no ``cond`` over a
    # narrow walk and a wide one, ISSUE 48's); int8 pools keep the grid's
    # one narrow walk, and ONE padded copy of the scales
    attn = "paged_prefill" if program == "chunk" else "paged_decode"
    assert calls.count("paged_kv_write") == 1 and calls.count(attn) == 1


def test_pool_copy_bytes_counts_what_the_scanned_pools_cost(v5e):
    """The counter sees the traffic this PR removed: a layer scan that takes
    the stacked pool as a scanned input and stacks the written slices back
    (the parent's ``scan_layers``) holds pool-shaped copies; a scatter on a
    carried pool holds whole-pool copies in the loop."""
    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    L, blocks, nkv, bs, hd = 2, 896, 8, 32, 128

    def scanned(pool, rows, blk, off):
        def body(_, pool_l):
            return None, pool_l.at[blk, :, off].set(rows)

        return jax.lax.scan(body, None, pool)[1]

    def carried(pool, rows, blk, off):
        def body(pool, layer):
            return pool.at[layer, blk, :, off].set(rows), None

        return jax.lax.scan(body, pool, jnp.arange(L))[0]

    shapes = (((L, blocks, nkv, bs, hd), jnp.bfloat16),
              ((16, nkv, hd), jnp.bfloat16), ((16,), jnp.int32),
              ((16,), jnp.int32))
    for fn in (scanned, carried):
        sh = SingleDeviceSharding(v5e.devices[0])
        args = [jax.ShapeDtypeStruct(s, d, sharding=sh) for s, d in shapes]
        text = jax.jit(fn, donate_argnums=(0,)).lower(*args).compile() \
            .as_text()
        assert pool_copy_bytes(text, [shapes[0][0]]) \
            >= blocks * nkv * bs * hd * 2, fn.__name__


# --- a family with recurrent state (ISSUE 31) ------------------------------ #
GRANITE_CELL = "granite-4.0-h-micro.serve-chat-64"


def _granite_program(program, periods=1):
    """The Granite cell's paged forward on shapes at its published widths
    and its pool geometry, ``periods`` of its 10-layer pattern deep, as the
    engine calls it: ``(forward, arguments)`` with the cache second."""
    from benchmark.harness.manifest import Cell

    cell = Cell(GRANITE_CELL)
    ragged = cell.role["engine"]["ragged"]
    kinds = cell.model["layer_types"][:10] * periods
    cfg = cell.family.build_cfg(
        {**cell.model, "layer_types": kinds, "num_hidden_layers": len(kinds)},
        **cell.role["program_options"])
    module = cell.family.module()
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: module.init(cfg, k), jax.random.PRNGKey(0)))
    slots = ragged["max_tracked_sequences"]
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], ragged["block_size"],
        slots=slots))

    def forward(params, cache, tokens, tables, ctx, valid, rows):
        return module.apply_paged(cfg, params, tokens, cache, tables, ctx,
                                  valid=valid, slots=rows)

    i32, s = jnp.int32, jax.ShapeDtypeStruct
    b, t = (slots, 1) if program == "decode" else (
        1, cell.role["engine"]["split_prefill_chunk"])
    table = cfg.max_seq_len // ragged["block_size"]
    return forward, (params, cache, s((b, t), i32), s((b, table), i32),
                     s((b,), i32), s((b, t), bool), s((b,), i32))


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_the_state_pool_stays_where_it_is(v5e, program):
    """Granite-4.0-H-Micro's ``decode`` (64 rows) and one ``chunk`` (256
    tokens) at the cell's geometry, one period deep, compiled for the chip:
    no copy - plain or ``copy-start`` -, slice, update, buffer or loop
    fusion of the state pool's, the KV pools' or one layer's shape; every
    pool aliased argument-to-result; ONE ``ssm_decode_update`` a Mamba layer
    body (the nest has two: the run of five and the run of four) and none in
    a multi-token program; the attention layer still one ``paged_kv_write``
    and one attention kernel."""
    import math
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    fn, args = _granite_program(program)
    sh = SingleDeviceSharding(v5e.devices[0])
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), args)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    pools = jax.tree.leaves(args[1])
    assert {tuple(p.shape) for p in pools} == {
        (1, 2816, 4, 32, 128), (9, 65, 136, 4096)}
    assert pool_copy_bytes(text, pools) == 0
    # nor moved to the chip's fast memory and back around a kernel (what a
    # pool of the convolution tails alone, 62 MB, was: a ``copy-start`` of
    # the whole pool a layer, which ``pool_copy_bytes`` does not count)
    dims = {",".join(map(str, shape)) for p in pools
            for shape in (p.shape, p.shape[1:])}
    assert not [line for line in text.splitlines() if "copy-start(" in line
                and any(f"[{d}]" in line for d in dims)]
    assert mem.alias_size_in_bytes >= sum(
        math.prod(p.shape) * p.dtype.itemsize for p in pools)
    # less than one layer's state of temporaries: nothing pool-sized hides
    assert mem.temp_size_in_bytes < math.prod(pools[-1].shape[1:]) * 4
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    assert calls.count("ssm_decode_update") == (2 if program == "decode"
                                                else 0)
    # a chunk's scan is ONE kernel a Mamba layer body (ISSUE 58)
    assert calls.count("ssm_chunk_scan") == (0 if program == "decode" else 2)
    # (lane-packed pools, 128 lanes a row: both walks fetch their own pages,
    # and a chunk's has one tile width - ISSUE 62)
    attn = "paged_decode" if program == "decode" else "paged_prefill"
    assert calls.count("paged_kv_write") == 1 and calls.count(attn) == 1


# --- a step's chunk rides in its decode program (ISSUE 32) ----------------- #
V5E_BYTES_LIMIT = int(15.75 * 2 ** 30)     # what the chip's allocator holds


def _mixed_program(cell_name):
    """A serve cell's forward of a MIXED call (``_paged.MixedCall``) on
    shapes, at the cell's own depth: every slot's decode token and one
    SplitFuse chunk as ``slots + chunk`` rows. ``(forward, arguments)``
    with the cache second."""
    from deepspeed_tpu.models._paged import MixedCall

    i32, s = jnp.int32, jax.ShapeDtypeStruct
    if cell_name == GRANITE_CELL:
        forward, (params, cache, _, tables, *_) = _granite_program(
            "decode", periods=4)
        slots, table, chunk, slot = *tables.shape, 256, [s((), i32)]
    else:
        forward, params, cache, slots, chunk, table = _cell_forward(
            cell_name, False, depth=None)
        slot = []
    call = MixedCall(s((slots, table), i32), s((slots,), i32),
                     s((slots,), bool), s((table,), i32), s((), i32),
                     s((), i32), *slot)
    rows = slots + chunk
    args = (params, cache, s((1, rows), i32), call, None, s((1, rows), bool))
    return forward, args + ((None,) if slot else ())


def _cell_program(kind, cell_name, *how):
    """``(function, arguments)`` of one of a serve cell's programs, the cache
    its second argument: ``"pool"`` (``_pool_program``: then the program's
    name and whether the pools are int8), ``"mixed"`` (``_mixed_program``;
    a two-kind cell's own) or ``"greedy_mixed"`` (the mixed call inside the
    engine's sampling, ``_greedy_mixed_step``: then whether it reads its
    rows)."""
    if kind == "pool":
        return _pool_program(cell_name, *how)
    forward, args = _two_kind_mixed_program(cell_name) \
        if cell_name in TWO_KIND_CELLS else _mixed_program(cell_name)
    if kind == "greedy_mixed":
        forward = _greedy_mixed_step(forward, *how)
    return forward, args


@pytest.fixture(scope="module")
def compiled_for_v5e(v5e):
    """``(kind, cell, ...) -> (compiled, arguments)``: a cell's program
    (``_cell_program``) compiled for the described chip with its cache
    donated, ONCE a module - several tests read different facts of one
    compiled program (its pools, its bank, its head's rows, its grids)."""
    @functools.cache
    def compiled(*key):
        fn, args = _cell_program(*key)
        sh = SingleDeviceSharding(v5e.devices[0])
        args = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            args)
        return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile(), args

    return compiled


KEYE_CELL = "keye-vl-2.0-30b-a3b.serve-longctx"


def test_the_learned_selections_mixed_program_keeps_three_pools_in_place(
        compiled_for_v5e):
    """The Keye cell's mixed call (8 decode rows + a 512-row chunk) at its
    real configuration, compiled for the chip: K, V AND the index keys' pool
    stay where they are (no pool-shaped copy - the 64-wide index keys lie
    two tokens a 128-lane row, so no program re-lays them out -, every pool
    aliased argument-to-result), a layer body is the two segments' writes,
    scores, thresholds and masked walks, and the whole program with its
    12 layers, 16 held experts of 128 and 6.5 GB of pools fits the chip."""
    import math
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    compiled, args = compiled_for_v5e("mixed", KEYE_CELL)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    cache = args[1]
    assert set(cache) == {"k", "v", "kI"}
    assert cache["kI"].shape == (12, 7808, 1, 16, 128)
    pools = jax.tree.leaves(cache)
    assert pool_copy_bytes(text, pools) == 0
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert pool_bytes == 7808 * 32 * 12 * 2176
    assert mem.alias_size_in_bytes >= pool_bytes
    assert 0 < mem.peak_memory_in_bytes < V5E_BYTES_LIMIT
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    for name, count in (("paged_kv_write", 2), ("paged_index_write", 2),
                        ("paged_index_scores", 2), ("paged_sparse_select", 2),
                        ("paged_sparse_decode", 1),
                        ("paged_sparse_prefill", 1), ("paged_decode", 0),
                        ("paged_prefill", 0)):
        assert calls.count(name) == count, (name, calls.count(name))
    # the bank holds 16 experts: no matmul over all 128
    assert not re.findall(r"= bf16\[128,\d+,768\]", text)


COMMAND_A_CELL = "command-a-plus-05-2026.serve-longctx"
MELLUM_CELL = "mellum2-12b-a2.5b-instruct.serve-mixedlen-32"
# a cell of window AND full layers: (the window kind's blocks a sequence and
# in all; each kind's (layers, blocks))
TWO_KIND_CELLS = {
    COMMAND_A_CELL: ((145, 2321), {"": (1, 12544), "_window": (3, 2321)}),
    MELLUM_CELL: ((49, 1569), {"": (2, 10240), "_window": (6, 1569)}),
}


def _two_kind_mixed_program(cell_name):
    """A two-kind cell's forward of a mixed call (its slots' decode rows + a
    512-row chunk) on shapes, at its real configuration and both kinds'
    pool geometry: ``(forward(params, cache, tokens, call, valid,
    rows=None), arguments)`` with the cache second."""
    from benchmark.harness.manifest import Cell
    from deepspeed_tpu.inference.ragged import WindowKind
    from deepspeed_tpu.models._paged import MixedCall

    cell = Cell(cell_name)
    engine = cell.role["engine"]
    ragged = engine["ragged"]
    slots, bs = ragged["max_tracked_sequences"], ragged["block_size"]
    chunk = engine["split_prefill_chunk"]
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    params = jax.eval_shape(    # as served: bf16, a float32 router apart
        lambda k: module.init(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    kind = WindowKind.sized("window", cfg.sliding_window, slots, chunk, bs)
    per_seq, pools = TWO_KIND_CELLS[cell_name]
    assert (kind.blocks_per_seq, kind.num_blocks) == per_seq
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], bs,
        window_blocks={"window": kind.num_blocks}))
    assert {k: v.shape[:2] for k, v in cache.items()} == {
        name + suffix: shape for suffix, shape in pools.items()
        for name in "kv"}
    table = cfg.max_seq_len // bs + 1 + kind.blocks_per_seq
    i32, s = jnp.int32, jax.ShapeDtypeStruct
    call = MixedCall(s((slots, table), i32), s((slots,), i32),
                     s((slots,), bool), s((table,), i32), s((), i32),
                     s((), i32))
    rows = slots + chunk

    def forward(params, cache, tokens, tables, valid, rows=None):
        return module.apply_paged(cfg, params, tokens, cache, tables, None,
                                  valid=valid, rows=rows)

    return forward, (params, cache, s((1, rows), i32), call,
                     s((1, rows), bool))


@pytest.mark.parametrize("cell", sorted(TWO_KIND_CELLS))
def test_two_kinds_of_kv_state_stay_where_they_are_in_the_mixed_program(
        compiled_for_v5e, cell):
    """A two-kind cell's mixed call (its decode rows + a 512-row chunk) at
    its real configuration, compiled for the chip - Command A+'s (a parallel
    block, group 16, a window of 4096, 16 of 128 experts held) and
    Mellum 2's (a sequential block, group 8, a window of 1024 = two chunks,
    a rope table a kind, all 64 experts of width 896): the full kind's pools
    AND the window kind's stay where they are (no pool-shaped copy, every
    pool aliased argument-to-result), a period's body is two writes, one
    decode walk and ONE chunk walk a kind (ISSUE 62: the walk that fetches
    its own pages has one tile width) - the window layers' scan and the
    full layer's -, and the whole program with its weights and pools fits
    the chip."""
    import math
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    compiled, args = compiled_for_v5e("mixed", cell)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    pools = jax.tree.leaves(args[1])
    assert pool_copy_bytes(text, pools) == 0
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    kv_heads = pools[0].shape[2]
    assert pool_bytes == sum(
        layers * blocks for layers, blocks in TWO_KIND_CELLS[cell][1].values()
    ) * 32 * 2 * kv_heads * 128 * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert 0 < mem.peak_memory_in_bytes < V5E_BYTES_LIMIT
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    for name, count in (("paged_kv_write", 4), ("paged_decode", 2),
                        ("paged_prefill", 2), ("moe_grouped_matmul", 2)):
        assert calls.count(name) == count, (name, calls.count(name))


@pytest.mark.parametrize("cell", SERVE_CELLS + (GRANITE_CELL,))
def test_the_mixed_program_reads_a_layers_weights_once(compiled_for_v5e, cell):
    """The four serve cells' mixed call (``slots + 256`` rows) at their real
    configurations, compiled for the chip: the pools stay where they are
    (no pool-shaped copy, every pool - Granite's state pool too - aliased
    argument-to-result), a layer body that attends is one ``paged_decode``,
    one ``paged_prefill`` (ISSUE 62: the walk that fetches its own pages has
    one tile width) and a ``paged_kv_write`` a segment, a Mamba layer
    body one ``ssm_decode_update`` beside the chunk's state rows, the whole
    program fits the chip, and each FFN / expert-bank weight meets ONE
    matmul a layer body: ``slots + 256`` rows wide, where the two programs
    had one of 256 rows and one of ``slots``."""
    import math
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    compiled, args = compiled_for_v5e("mixed", cell)
    text, mem = compiled.as_text(), compiled.memory_analysis()
    pools = jax.tree.leaves(args[1])
    assert pool_copy_bytes(text, pools) == 0
    assert mem.alias_size_in_bytes >= sum(
        math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert 0 < mem.peak_memory_in_bytes < V5E_BYTES_LIMIT
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    assert (calls.count("paged_decode"), calls.count("paged_prefill"),
            calls.count("paged_kv_write")) == (1, 1, 2)
    assert calls.count("ssm_decode_update") == calls.count(
        "ssm_chunk_scan") == (2 if cell == GRANITE_CELL else 0)
    # every matmul against a weight (bf16; the blocked scan's own are f32)
    # runs over the call's rows: none over one segment's alone. A MoE
    # cell's three expert matmuls are its one grouped call (ISSUE 41): q,
    # k, v, o, the router and the head are left
    rows = args[2].shape[1]
    matmuls = [tuple(map(int, dims.split(","))) for dims in re.findall(
        r"= bf16\[([\d,]+)\]\S* convolution\(", text)]
    grouped = calls.count("moe_grouped_matmul")
    assert grouped == (0 if cell in (SERVE_CELLS[0], GRANITE_CELL) else 1)
    assert len(matmuls) >= 7 - grouped \
        and all(rows in dims for dims in matmuls)


# --- the head scores the rows a program reads (ISSUE 44) -------------------- #
def _greedy_mixed_step(forward, reads_its_rows: bool):
    """``forward`` of a mixed call inside the sampling the engine's
    ``decode_chunk`` wraps it in (greedy): ``slots + 1`` tokens and the
    cache. ``reads_its_rows``: the call hands the family the rows it reads
    (the slots' and the chunk's last real one), as the engine does; else
    the family scores every row and the rows are picked from the result
    with the parent's (407791c) expressions."""
    def step(params, cache, tokens, call, *rest):
        b = call.slots
        last = jnp.maximum(call.chunk_valid - 1, 0)
        if reads_its_rows:
            rows = jnp.concatenate([jnp.arange(b), b + last[None]])[None]
            logits, cache = forward(params, cache, tokens, call, *rest,
                                    rows=rows)
            nxt, first = logits[0, :b], logits[0, b]
        else:
            logits, cache = forward(params, cache, tokens, call, *rest)
            nxt = logits[0, :b]
            first = jnp.take_along_axis(
                logits[:, b:], last[None, None, None], axis=1)[0, 0]
        return jnp.concatenate([jnp.argmax(nxt, axis=-1),
                                jnp.argmax(first, axis=-1)[None]]).astype(
                                    jnp.int32), cache

    return step


@pytest.mark.parametrize("cell", [COMMAND_A_CELL, KEYE_CELL, SERVE_CELLS[0]])
def test_the_mixed_programs_head_scores_the_rows_it_reads(compiled_for_v5e,
                                                          cell):
    """The mixed call at Command A+'s (16 + 512 rows, a TIED table of
    262 144), Keye's (8 + 512, 151 936) and chat's (32 + 256, 32 000) cell
    shapes with ``rows=`` as the engine hands them, compiled for the chip
    beside the program that scores every row: no array of ``slots + chunk``
    rows by the vocabulary is left, of any type, and so no slice of one
    (the parent's holds both) - the logits are ``slots + 1`` rows -, the
    table is read where it lies (nothing of its shape but the argument and
    bitcasts of it: no copy, no transpose for the tied ``embed.T``), and the
    program's peak is lower."""
    import re

    text, peak = {}, {}
    for reads in (True, False):
        compiled, args = compiled_for_v5e("greedy_mixed", cell, reads)
        text[reads] = compiled.as_text()
        peak[reads] = compiled.memory_analysis().peak_memory_in_bytes
    params, rows, slots = args[0], args[2].shape[1], args[3].slots
    table = params.get("lm_head", params["embed"]).shape
    vocab = max(table)
    every_row = rf"\w+\[(\d+,)*({rows}|{rows - slots}),{vocab}\]"
    assert re.search(every_row, text[False])
    assert not re.search(every_row, text[True])
    assert re.search(rf"f32\[(\d+,)*{slots + 1},{vocab}\]", text[True])
    made = re.findall(
        r"= bf16\[(?:%d,%d|%d,%d)\]\S* ([\w-]+)\((?:.*calls=%%(\w+))?"
        % (*table, *table[::-1]), text[True])
    assert made and all(
        op in ("parameter", "bitcast")
        or (op == "fusion" and called.startswith("bitcast_fusion"))
        for op, called in made), made
    assert 0 < peak[True] < peak[False] < V5E_BYTES_LIMIT


# --- the prefill walk ends where the context ends (ISSUE 45) ---------------- #
# the parent's (cd0afcf) compiled peak of the engine's mixed program
PARENT_MIXED_PEAK = {COMMAND_A_CELL: 14_182_393_856,
                     SERVE_CELLS[0]: 5_031_206_912}


@pytest.mark.parametrize("cell", sorted(PARENT_MIXED_PEAK))
def test_the_mixed_programs_prefill_walk_takes_a_traced_grid(
        compiled_for_v5e, cell):
    """Command A+'s and chat's mixed program as the engine runs it, compiled
    for the chip: both walks' grids are static - ``paged_decode``'s since
    ISSUE 49, ``paged_prefill``'s (one call a table kind) since ISSUE 62: a
    grid step is a whole walk, whose length is a loop's trip count inside
    the kernel, so a call's FIRST operand is the prefetched block table (a
    Mosaic call's dynamic grid bound, which the grid of ``BlockSpec`` pages
    took, would be an ``s32[]`` ahead of it: the name the test keeps) -,
    the pools stay where they are, also those the walks take whole
    (``memory_space=pl.ANY``), and the program's peak is the parent's
    (nothing the size of a row, a tile or a table is added)."""
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    compiled, args = compiled_for_v5e("greedy_mixed", cell, True)
    text = compiled.as_text()
    first = {kernel: re.findall(
        rf"%{kernel}(?:\.\d+)? = .*? custom-call\(.*" + MOSAIC
        + r".*?operand_layout_constraints=\{([^,]*),", text)
        for kernel in ("paged_prefill", "paged_decode", "paged_kv_write")}
    kinds = 2 if cell == COMMAND_A_CELL else 1
    assert first["paged_decode"] == [f"s32[{args[3].slots}"] * kinds
    # one walk a kind, one tile width (ISSUE 62): the chunk's one table row
    assert first["paged_prefill"] == ["s32[1"] * kinds
    assert len(first["paged_kv_write"]) == 2 * kinds \
        and "s32[]" not in first["paged_kv_write"]      # a static grid's
    assert pool_copy_bytes(text, jax.tree.leaves(args[1])) == 0
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert 0 < peak < PARENT_MIXED_PEAK[cell] + (64 << 10)


# --- the expert bank is read where it lies (ISSUE 41) ----------------------- #
MOE_CELLS = ("mixtral-8x7b.serve-longprompt", "olmoe-1b-7b.serve-longprompt",
             KEYE_CELL)
BANK_PROGRAMS = [(cell, "mixed") for cell in MOE_CELLS] + [
    (cell, program) for cell in MOE_CELLS[:2]
    for program in ("chunk", "decode", "decode_many")]


@pytest.mark.parametrize("cell,program", BANK_PROGRAMS)
def test_the_expert_bank_is_read_where_it_lies(compiled_for_v5e, cell,
                                               program):
    """A MoE cell's forward programs compiled for the chip with the grouped
    form: a layer body is ONE ``moe_grouped_matmul`` over the STACKED bank
    (the layer a prefetched scalar), so the compiled program holds no copy,
    slice or loop fusion the size of a layer's bank (``pool_copy_bytes``
    pointed at the bank's shapes) - a scanned slice handed to a Mosaic call
    would be copied out of the stack first, 2.8 GB a layer at Mixtral's
    widths -, no matmul over ``[E, rows, ...]`` slabs and no ``[T, E, C]``
    mask; the pools stay where they are as before."""
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    compiled, args = compiled_for_v5e("mixed", cell) if program == "mixed" \
        else compiled_for_v5e("pool", cell, program, False)
    text = compiled.as_text()
    moe = args[0]["layers"]["moe"]
    bank = [moe[n] for n in ("w_gate", "w_up", "w_down")]
    assert pool_copy_bytes(text, bank) == 0
    assert pool_copy_bytes(text, jax.tree.leaves(args[1])) == 0
    assert 0 < compiled.memory_analysis().peak_memory_in_bytes \
        < V5E_BYTES_LIMIT
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    assert calls.count("moe_grouped_matmul") == 1
    experts, rows = bank[0].shape[1], math.prod(args[2].shape)
    assert not re.findall(rf"= bf16\[{experts},{rows},\d+\]", text)
    assert not re.findall(rf"\[{rows},{experts},{rows}\]", text)


# sha256 of the mixed program's jaxpr in the two cells whose families hold no
# bank, taken on ISSUE 41's parent (166de2b) and again on its finished tree:
# ``_paged.scan_layers`` is every paged family's, and the bank's way through
# it (a closure of ``models/mixtral.py``) left theirs alone. Re-taken on
# ISSUE 45's finished tree, which means to change them: the chunk's
# ``paged_prefill`` takes a traced grid bound (they were 4c1badd32e6be1bd and
# adee81593bcafbca), and again on ISSUE 48's, which means to change them too:
# the chunk's walk is a ``cond`` over two tile widths (0185c835790f0de0 and
# c8639994fc5fe43d), and again on ISSUE 49's, which means to change them too:
# the slots' ``paged_decode`` fetches its own pages (958bdb530c8e0656 and
# a7f9637cb43eb148). Granite's alone on ISSUE 58's, which means to change
# it: the chunk's scan is one ``ssm_chunk_scan`` kernel (it was
# 9d8372bd5608f6e3); chat's stands. Both again on ISSUE 62's, which means to
# change them: the chunk's ``paged_prefill`` fetches its own pages, one jitted
# call at one tile width (1a7c864441030658 and 0a3ea1df291b5e31).
NO_BANK_PROGRAMS = {
    "mistral-7b.serve-chat": "758c4cbf0144abce",
    GRANITE_CELL: "a4066403284661a2",
}


@pytest.mark.parametrize("cell", sorted(NO_BANK_PROGRAMS))
def test_a_family_with_no_bank_traces_to_the_parents_program(cell):
    import hashlib
    import re

    fn, args = _mixed_program(cell)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))
    assert "moe_grouped_matmul" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == NO_BANK_PROGRAMS[cell]


# head size 64 (half a lane tile) in plain pools, and the lane-packed geometry
# Granite runs (``_paged.init_paged_pools(lane_pack=True)``: two heads a row)
HEAD_64 = {"unpacked_hd64_group4": (8, 64), "packed_hd128_group8": (4, 128)}


@pytest.mark.parametrize("geometry", sorted(HEAD_64))
@pytest.mark.parametrize("op", ["decode", "prefill"])
def test_paged_kernels_compile_at_head_size_64(v5e, op, geometry):
    """32 query heads over 8 KV heads of 64 (Granite-4.0-H-Micro's attention):
    both paged kernels and the write compile for the chip as they are, and
    with two KV heads side by side in a 128-lane row."""
    from deepspeed_tpu.ops.pallas import paged_attention as pa

    nkv, hd = HEAD_64[geometry]
    b, t = (64, 1) if op == "decode" else (1, 256)
    bf, i32 = jnp.bfloat16, jnp.int32

    def step(q, k, v, kp, vp, table, ctx, n):
        kp, vp, *_ = pa.paged_kv_write(k, v, kp, vp, table, ctx, n,
                                       layer=jnp.int32(1))
        if op == "decode":
            return pa.paged_decode_attention(q[:, 0], kp, vp, table, ctx,
                                             scale=1 / 64, layer=jnp.int32(1))
        return pa.paged_prefill_attention(q, kp, vp, table, ctx, n,
                                          scale=1 / 64, layer=jnp.int32(1))

    pool = ((4, 2816, nkv, 32, hd), bf)
    text = _compile(step, ((b, t, 32, hd), bf), ((b, t, nkv, hd), bf),
                    ((b, t, nkv, hd), bf), pool, pool, ((b, 256), i32),
                    ((b,), i32), ((b,), i32),
                    device=v5e.devices[0]).as_text()
    assert text.count(MOSAIC) >= 2


# --- the multi-token walk takes a wide KV tile (ISSUE 48) and fetches its
# own pages (ISSUE 62) ------------------------------------------------------- #
# query heads, KV heads, key width, value width (None: a V pool), block,
# table width, pool blocks, window -> pages of the tile (a.x-k1's 2: the VMEM
# budget leaves its 640-lane rows the 256 keys they had; Mellum 2's window
# kind has a 49-block table, which holds ONE 32-page tile)
WIDE_WALKS = {
    "command_a_full": (128, 8, 128, None, 32, 1024, 12544, None, 32),
    "command_a_full_under_a_window": (128, 8, 128, None, 32, 1024, 12544,
                                      4096, 32),
    "command_a_window_kind": (128, 8, 128, None, 32, 145, 2321, 4096, 32),
    "command_a_window_kind_no_window": (128, 8, 128, None, 32, 145, 2321,
                                        None, 32),
    "axk1_latent": (64, 1, 640, 512, 128, 256, 3152, None, 2),
    "mellum_full": (32, 4, 128, None, 32, 1024, 10240, None, 32),
    "mellum_window_kind": (32, 4, 128, None, 32, 49, 1569, 1024, 32),
}


def _count_equations(jaxpr, counts):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold (a
    kernel's body, a loop's, a branch's), by primitive."""
    for e in jaxpr.eqns:
        counts[e.primitive.name] = counts.get(e.primitive.name, 0) + 1
        for v in e.params.values():
            for j in v if isinstance(v, (list, tuple)) else [v]:
                inner = getattr(j, "jaxpr", j)
                if hasattr(inner, "eqns"):
                    _count_equations(inner, counts)
    return counts


@pytest.mark.parametrize("t", [512, 5])     # a chunk; a verify window (tq 16)
@pytest.mark.parametrize("geometry", sorted(WIDE_WALKS))
def test_the_wide_prefill_walk_lowers_at_the_long_context_cells(v5e,
                                                                geometry, t):
    """The multi-token walk at the long-context cells' geometries -
    command-a's group of 16 over both table widths, with and without a
    window, a.x-k1's one latent pool (keys 640 lanes, values the first 512,
    128-token blocks), Mellum 2's group of 8 over its two kinds - compiles
    for the chip as ONE Mosaic call named ``paged_prefill`` that fetches its
    own pages: its operands are the prefetched scalars, q and the POOLS,
    whole - no page of them -, its tile is the wide one, and its page copies
    are unrolled where the loop is lowered, not in Python: a start a pool at
    four sites (a whole tile and a part of one, this walk's first and the
    next tile), a wait at two, whatever the tile's 32 pages - and the whole
    call a few hundred traced equations (a traced copy costs a TPU host
    ~18 ms a program: PERF.md section 6, PR 54)."""
    import re

    from deepspeed_tpu.ops.pallas import paged_attention as pa

    nh, nkv, hd, vd, bs, table, blocks, window, pages = WIDE_WALKS[geometry]
    bf, i32 = jnp.bfloat16, jnp.int32
    n_pools = 1 if vd else 2

    def step(q, pool, *rest):
        v_pool = None if vd else rest[0]
        tables, ctx, n = rest[-3:]
        return pa.paged_prefill_attention(
            q, pool, v_pool, tables, ctx, n, layer=jnp.int32(1),
            window=window, value_width=vd)

    pool = ((4, blocks, nkv, bs, hd), bf)
    shapes = (((1, t, nh, hd), bf), *((pool,) * n_pools), ((1, table), i32),
              ((1,), i32), ((1,), i32))
    text = _compile(step, *shapes, device=v5e.devices[0]).as_text()
    calls = re.findall(r"%paged_prefill(?:\.\d+)? = .*? custom-call\((.*?)\), "
                       r"custom_call_target=\"" + MOSAIC, text)
    # operands: 4 scalars (5 under a window), q, the pools
    assert [c.count("%") for c in calls] \
        == [5 + (window is not None) + n_pools]
    assert pa.prefill_kv_pages([0], [t], t, nh, pool[0][1:], table,
                               pools=n_pools) == pages
    counts = _count_equations(jax.make_jaxpr(step)(
        *(jax.ShapeDtypeStruct(*s) for s in shapes)).jaxpr, {})
    assert (counts["pallas_call"], counts["dma_start"],
            counts["dma_wait"]) == (1, 4 * n_pools, 2 * n_pools)
    assert sum(counts.values()) < 400


# --- the ``t > 1`` programs are the parent's ------------------------------- #
# b, t, query heads, KV heads, head size, block, pool blocks, table width,
# int8 pools (scale groups), window
MULTI_TOKEN_PROGRAMS = {
    "mistral_chunk256_bf16": (1, 256, 32, 8, 128, 32, 896, 256, 0, None),
    "mistral_prompt2816_int8": (1, 2816, 32, 8, 128, 32, 896, 256, 1, None),
    "olmoe_chunk256_window": (1, 256, 16, 16, 128, 32, 1536, 128, 0, 4096),
    "verify_t5_traced_window_int8_ng2": (16, 5, 32, 8, 128, 32, 896, 256, 2,
                                         "traced"),
    "batched_prefill_mqa": (4, 40, 8, 1, 64, 16, 64, 20, 0, None),
}
# sha256 of each program's jaxpr (kernel body included): ISSUE 45's, re-taken
# on its finished tree (the walk's last grid dimension is a value of the
# program and the body ends on ``num_programs``; before it they were ISSUE
# 29's, whose step first wrote through ``paged_kv_write``).
# A PR that means to change the multi-token walk replaces these; one that does
# not has changed it by accident. ISSUE 48 replaced the two whose tables hold
# a long walk over bf16 pools (a ``cond`` over two tile widths; they were
# 65274280e7fb1e5c and 54cb8a57fa9599f8): int8 pools and a 20-entry table
# keep the one walk they had, to the letter. ISSUE 62 replaced the same two
# (the walk fetches its own pages: one jitted call, no ``cond``; they were
# d63d7041f4528c75 and e89f3ddd0d3a8bda): int8 pools and heads of 64 keep the
# grid of ``BlockSpec`` pages, to the letter.
PARENT_HASHES = {
    "mistral_chunk256_bf16": "09c8c67e928666f8",
    "mistral_prompt2816_int8": "adc273e57d65238f",
    "olmoe_chunk256_window": "c97010867091a38a",
    "verify_t5_traced_window_int8_ng2": "a87eb11e2b12777f",
    "batched_prefill_mqa": "a5d8074491835204",
}


@pytest.mark.parametrize("program", sorted(MULTI_TOKEN_PROGRAMS))
def test_multi_token_paged_program_is_the_parents(program):
    """Decode and prefill share one flash body; what that sharing traces to
    for ``t > 1`` (a chunk, an unsplit prompt, a verify window, a batched
    prefill) is the parent's jaxpr to the letter."""
    import hashlib
    import re

    b, t, nq, nkv, hd, bs, blocks, table, ng, window = \
        MULTI_TOKEN_PROGRAMS[program]
    step = _prefill_step(bool(ng), window)
    shape, bf, i32 = jax.ShapeDtypeStruct, jnp.bfloat16, jnp.int32
    pool = shape((blocks, nkv, bs, hd), jnp.int8 if ng else bf)
    args = [shape((b, t, nq, hd), bf), shape((b, t, nkv, hd), bf),
            shape((b, t, nkv, hd), bf), pool, pool, shape((b, table), i32),
            shape((b,), i32), shape((b,), i32)]
    args += [shape((blocks, nkv, bs, ng), jnp.float32)] * 2 if ng else []
    args += [shape((), i32)] if window == "traced" else []
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(step)(*args)))
    assert "paged_prefill" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == PARENT_HASHES[program]


def _sq_sum_grad(fn, argnums):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) ** 2),
                    argnums=argnums)


def _attention_op(q, k, v):
    from deepspeed_tpu.ops import attention

    return attention(q, k, v, causal=True)


def _rms_norm_op(x, w):
    from deepspeed_tpu.ops import rms_norm

    return rms_norm(x, w, 1e-5)


def _quantize_op(x):
    from deepspeed_tpu.ops import quantize_int8

    return quantize_int8(x, HD)[0]


# op as the models call it -> (function, shapes, how many lead with the batch)
FOUR_CHIP_CASES = {
    "attention_fwd_bwd": (_sq_sum_grad(_attention_op, (0, 1, 2)),
                          _qkv(4, 2048), 3),
    "rms_norm_fwd_bwd": (_sq_sum_grad(_rms_norm_op, (0, 1)),
                         (((4, 2048, HIDDEN), jnp.bfloat16),
                          ((HIDDEN,), jnp.bfloat16)), 1),
    "int8_group_quantize": (_quantize_op,
                            (((4 * 2048, HIDDEN), jnp.bfloat16),), 1),
}


@pytest.mark.parametrize("case", sorted(FOUR_CHIP_CASES))
def test_pallas_op_compiles_in_a_program_over_four_chips(v5e, case):
    """A Mosaic kernel under a multi-device jit does not lower ("cannot be
    automatically partitioned") — which is what ZeRO-3 over ``data=4`` hit.
    The registry runs the kernel per device (``registry._per_device``): the
    op is still the kernel, and batch-sharded in, batch-sharded out, it
    moves nothing between the chips."""
    from deepspeed_tpu.comm import mesh as mesh_lib

    fn, shapes, rows = FOUR_CHIP_CASES[case]
    mm = mesh_lib.MeshManager.create({"data": 4}, devices=v5e.devices)
    args = [jax.ShapeDtypeStruct(
        s, d, sharding=mm.sharding(mesh_lib.BATCH_AXES) if i < rows
        else mm.replicated()) for i, (s, d) in enumerate(shapes)]
    with mm.activate():
        hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert MOSAIC in hlo
    assert " all-gather(" not in hlo
    # only a replicated weight's gradient is summed over the chips
    assert hlo.count(" all-reduce(") == (case == "rms_norm_fwd_bwd")


def test_kernel_with_no_mesh_in_its_trace_does_not_lower_over_four_chips(v5e):
    """The inference engines trace with no mesh context, so the registry
    calls the paged kernel as is; over several chips Mosaic itself refuses —
    an error, never the gather reference under the kernel's name."""
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.ops import registry

    mm = mesh_lib.MeshManager.create({"data": 4}, devices=v5e.devices)
    args = [jax.ShapeDtypeStruct(s, d, sharding=mm.replicated())
            for s, d in _pool_args((SLOTS, NQ, HD))]
    assert registry.resolved()["paged_decode_attention"] == "pallas"
    with pytest.raises(NotImplementedError, match="automatically partition"):
        jax.jit(registry.get_op("paged_decode_attention")).lower(*args)


def test_in_jit_host_offload_is_not_an_identity(v5e):
    """``memory.placement.to_host`` under a trace must put the value in host
    memory: every in-jit offload path (ZeRO-Offload state, FPDT host KV, the
    KV spill) routes through it, and an annotation that silently does nothing
    leaves them all running in HBM."""
    from deepspeed_tpu.memory import placement

    def f(x):
        parked = placement.to_host(x * 2)
        return placement.to_device(parked) + 1, parked

    compiled = _compile(f, ((1024, 1024), jnp.float32),
                        device=v5e.devices[0])
    mem = compiled.memory_analysis()
    assert mem.host_output_size_in_bytes == 1024 * 1024 * 4, mem
    assert "S(5)" in compiled.as_text()  # XLA's host memory space


# --------------------------------------------------------------------------- #
# whole programs at full width: the engines' own step functions, built here on
# CPU devices and handed the described chips (15 s and more each: slow lane)
# --------------------------------------------------------------------------- #
def _on_described_chips(tree, mesh_mgr, devices):
    """Point ``mesh_mgr`` at ``devices`` and return ``tree`` as shapes with
    the same partition specs on the new mesh."""
    old = mesh_mgr.mesh
    mesh_mgr.mesh = Mesh(np.asarray(devices).reshape(old.devices.shape),
                         old.axis_names)

    def abstract(x):
        spec = getattr(x.sharding, "spec", jax.sharding.PartitionSpec())
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh_mgr.mesh, spec))

    return jax.tree.map(abstract, tree)


def compile_train_step_for(devices, cfg, batch, seq, cpu_devices):
    """The trainer's real step function (``chip_smoke`` config), compiled for
    ``devices``. The engine is built on ``cpu_devices`` (same count), then its
    mesh and cached sharding trees are re-pointed at the described chips."""
    from deepspeed_tpu.comm import overlap

    engine = chip_smoke.build_trainer(cfg, batch, seed=0,
                                      devices=cpu_devices)
    tokens = engine._shard_batch(
        {"tokens": np.zeros((batch, seq + 1), np.int32)}, with_gas_dim=True)
    state, tokens = _on_described_chips((engine.state, tokens),
                                        engine.mesh_mgr, devices)
    p = engine.partitioner
    engine._param_shardings = p.shardings(engine.param_specs)
    engine._grad_shardings = p.shardings(engine.grad_specs)
    engine._master_shardings = p.shardings(engine.opt_param_specs)
    if overlap._SCAN_SLICE["shardings"] is not None:  # ZeRO-3 over >1 chip
        overlap.configure_scan_slice_layout(
            engine._layer_prefetch_shardings())
    # the engine's OWN program, lowered as a first train_batch lowers it: no
    # mesh context from here (one was once added here, and the rehearsal
    # then passed where the chip failed)
    return engine._build_train_step().lower(
        state, tokens, engine._lr_override).compile()


# slow: the WHOLE train step at Mistral-7B widths through the TPU compiler
# (50-70 s); `chip_smoke.py` runs this very step on the chip on every PR, and
# the kernels' own described-chip compiles above run in tier-1
@pytest.mark.slow
def test_train_step_compiles_for_v5e_at_mistral_7b_widths(v5e):
    size = chip_smoke.TrainSize()
    cfg = chip_smoke.mistral_7b(size.layers, remat=True)
    compiled = compile_train_step_for(
        v5e.devices[:1], cfg, size.batch, size.seq, jax.devices()[:1])
    assert compiled.as_text().count(MOSAIC) > 0
    mem = compiled.memory_analysis()
    live = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert live < 16 * 10 ** 9, mem


# slow: two whole-step compiles, one over four described chips (85-140 s);
# `chip_smoke.py --chips 4` and the `train-zero3-x4` cell run this step on
# four real chips on every PR
@pytest.mark.slow
def test_zero3_step_over_four_chips_keeps_every_kernel(v5e):
    """ZeRO-3 over ``data=4`` as ``chip_smoke.py --chips 4`` runs it: the step
    compiles, and holds as many Mosaic kernels as the one-chip step (an op
    that ran its XLA reference over the mesh would be missing)."""
    size = chip_smoke.TrainSize()
    cfg = chip_smoke.mistral_7b(size.layers, remat=True)
    mosaic = [compile_train_step_for(
        v5e.devices[:n], cfg, size.batch, size.seq,
        jax.devices()[:n]).as_text().count(MOSAIC) for n in (4, 1)]
    assert mosaic[0] == mosaic[1] > 0, mosaic


def test_decode_step_compiles_for_v5e_at_mistral_7b_widths(v5e):
    """The server's decode program over the real pool geometry (depth 2: the
    layer scan makes the program the same at any depth)."""
    from deepspeed_tpu.comm import mesh as mesh_lib

    # one device, as on the chip: the paged kernels have no layout over a
    # mesh yet, and over several devices they do not lower
    mesh_lib.init_mesh({"data": 1}, devices=jax.devices()[:1])
    size = dataclasses.replace(chip_smoke.ServeSize(), layers=2)
    eng = chip_smoke.build_server(chip_smoke.mistral_7b(size.layers), size,
                                  seed=0)
    # the arguments as the engine's own launch hands them over
    # (``_launch_decode``: ``_dispatch`` of ``_slots`` with the result
    # before), so a change of the program's signature changes this call too
    args = eng._dispatch(lambda *args: args, eng._slots(), seed=0, prev=True)
    sh = SingleDeviceSharding(v5e.devices[0])
    args = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh), args)
    decode = eng._decode_fn(1, False)
    compiled = decode._jitted.lower(*args).compile()
    assert MOSAIC in compiled.as_text()


AXK1_CELL = "a.x-k1.serve-longctx"


def _axk1_mixed_program():
    """The A.X-K1 cell's forward of a mixed call (16 decode rows + a 512-row
    chunk) on shapes, at its real configuration and its latent pool's
    geometry: ``(forward(params, cache, tokens, call, valid), arguments)``
    with the cache second."""
    from benchmark.harness.manifest import Cell
    from deepspeed_tpu.models._paged import MixedCall

    cell = Cell(AXK1_CELL)
    engine = cell.role["engine"]
    ragged = engine["ragged"]
    slots, bs = ragged["max_tracked_sequences"], ragged["block_size"]
    chunk = engine["split_prefill_chunk"]
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: module.init(cfg, k), jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], bs))
    assert {k: v.shape for k, v in cache.items()} == {
        "latent": (5, 3152, 1, 128, 640)}
    table = cfg.max_seq_len // bs
    i32, s = jnp.int32, jax.ShapeDtypeStruct
    call = MixedCall(s((slots, table), i32), s((slots,), i32),
                     s((slots,), bool), s((table,), i32), s((), i32),
                     s((), i32))
    rows = slots + chunk

    def forward(params, cache, tokens, tables, valid, read):
        return module.apply_paged(cfg, params, tokens, cache, tables, None,
                                  valid=valid, rows=read)

    return forward, (params, cache, s((1, rows), i32), call,
                     s((1, rows), bool), s((1, slots + 1), i32))


def test_the_latent_pool_stays_where_it_is_in_the_mixed_program(v5e):
    """The A.X-K1 cell's mixed call (16 decode rows + a 512-row chunk) at
    its real configuration, compiled for the chip: the walk lowers at one KV
    head, a group of 64, keys 640 lanes wide and values the first 512 of the
    same page; the ONE pool stays where it is (no pool-shaped copy, aliased
    argument-to-result); the dense layer and the scanned sparse layer are
    one write and two walks each; and the whole program with its 11.1 GB of
    weights and 2.58 GB of pool fits the chip."""
    import math
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    forward, args = _axk1_mixed_program()
    sh = SingleDeviceSharding(v5e.devices[0])
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), args)
    compiled = jax.jit(forward, donate_argnums=(1,)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    pools = jax.tree.leaves(args[1])
    assert pool_copy_bytes(text, pools) == 0
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert pool_bytes == 5 * 3152 * 128 * 1280
    assert mem.alias_size_in_bytes >= pool_bytes
    assert 12e9 < mem.peak_memory_in_bytes < V5E_BYTES_LIMIT
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    for name, count in (("paged_kv_write", 4), ("paged_decode", 2),
                        ("paged_prefill", 2), ("moe_grouped_matmul", 1)):
        assert calls.count(name) == count, (name, calls.count(name))


# --- Nemotron-3-Nano: state and experts in one stack (ISSUE 50) ------------ #
NEMOTRON_CELL = "nemotron-3-nano-30b-a3b.serve-reason-64"


def _grouped_state_update(pool, layer, rows, fresh, decay, dtx, B, C):
    from deepspeed_tpu.ops.pallas.ssm import ssm_decode_update

    return ssm_decode_update(pool, layer, rows, fresh, decay, dtx, B, C)


def _chunk_scan(pool, layer, rows, fresh, x, dt, A, B, C):
    from deepspeed_tpu.ops.pallas.ssm_scan import ssm_chunk_scan

    return ssm_chunk_scan(pool, layer, rows, fresh, x, dt, A, B, C, 128)


def _chunk_scan_args(tokens, groups):
    """One row of ``tokens`` tokens at the published widths (64 heads of 64
    channels, N = 128) on the cells' ``[.., 65, 136, 4096]`` pool."""
    bc = (1, tokens, 128) if groups == 1 else (1, tokens, groups, 128)
    return (((23, 65, 136, 4096), jnp.float32), ((), jnp.int32),
            ((1,), jnp.int32), ((1,), jnp.bool_),
            ((1, tokens, 64, 64), jnp.bfloat16),
            ((1, tokens, 64), jnp.float32), ((64,), jnp.float32),
            (bc, jnp.bfloat16), (bc, jnp.bfloat16))


def _two_matrix_bank(tile):
    def call(x, w_up, w_down, tile_expert, tile_rows, num_tiles, layer):
        from deepspeed_tpu.ops.pallas.grouped_matmul import \
            moe_grouped_matmul

        return moe_grouped_matmul(x, None, w_up, w_down, tile_expert,
                                  tile_rows, num_tiles, layer, tile=tile)
    return call


def _bank_args(rows, tile, inter):
    tiles = (rows * 6 + 8 * (tile - 1)) // tile
    i32 = jnp.int32
    return (((tiles * tile, 2688), jnp.bfloat16),
            ((23, 8, 2688, inter), jnp.bfloat16),
            ((23, 8, inter, 2688), jnp.bfloat16), ((tiles,), i32),
            ((tiles,), i32), ((), i32), ((), i32))


# the state update over 8 groups of B and C at the cell's pool (a 2048-lane
# block spans four 512-lane groups); the two-matrix grouped matmul at 2688 /
# 1920 (the bank's layout: 1856 in whole lane tiles) over a decode's rows and
# a mixed call's, and at the published 1856 whole (Mosaic takes it: it is the
# STACK's layout in HBM that wants whole tiles, the mixed program below)
NEMOTRON_KERNELS = {
    "ssm_decode_update_8_groups": (_grouped_state_update, (
        ((23, 65, 136, 4096), jnp.float32), ((), jnp.int32),
        ((64,), jnp.int32), ((64,), jnp.bool_), ((64, 4096), jnp.float32),
        ((64, 4096), jnp.float32), ((64, 8, 128), jnp.bfloat16),
        ((64, 8, 128), jnp.bfloat16))),
    # a segment of many tokens (ISSUE 58): Nemotron's 512-row chunk over 8
    # groups, Granite's 256 rows over one, the probes' 2048-row call
    "ssm_chunk_scan_8_groups_512": (_chunk_scan, _chunk_scan_args(512, 8)),
    "ssm_chunk_scan_1_group_256": (_chunk_scan, _chunk_scan_args(256, 1)),
    "ssm_chunk_scan_8_groups_2048": (_chunk_scan, _chunk_scan_args(2048, 8)),
    "relu2_bank_decode_rows": (_two_matrix_bank(64), _bank_args(64, 64, 1920)),
    "relu2_bank_mixed_rows": (_two_matrix_bank(256),
                              _bank_args(576, 256, 1920)),
    "relu2_bank_unpadded_1856": (_two_matrix_bank(64),
                                 _bank_args(64, 64, 1856)),
}


@pytest.mark.parametrize("case", sorted(NEMOTRON_KERNELS))
def test_nemotron_kernel_compiles_for_v5e(v5e, case):
    fn, shapes = NEMOTRON_KERNELS[case]
    compiled = _compile(fn, *shapes, device=v5e.devices[0])
    assert MOSAIC in compiled.as_text(), \
        f"{case}: compiled without a Mosaic kernel"


def test_state_and_experts_stay_where_they_are_in_nemotrons_mixed_program(
        v5e):
    """The Nemotron-3-Nano cell's mixed call (64 decode rows + a 512-row
    chunk) at its real configuration - all 52 layers, 8 of 128 experts, 65
    rows of state and 8256 KV blocks - compiled for the chip: the three
    pools stay where they are (no pool-shaped copy, aliased argument-to-
    result); the expert bank is read where it lies - no buffer of a bank's
    shape in ANOTHER layout (what the unpadded 1856 columns cost: a copy of
    the whole 1.8 GB of ``w_up`` a program) and next to no temporaries; the
    nest compiles 6 Mamba and 6 expert bodies and 2 attention bodies for
    the 23 + 23 + 6 layers; and the program with its 8.2 GB of weights and
    4.95 GB of pools fits the chip with room for a probe's reference."""
    import re

    from benchmark.harness.manifest import Cell
    from deepspeed_tpu.models._paged import MixedCall
    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    cell = Cell(NEMOTRON_CELL)
    engine = cell.role["engine"]
    ragged = engine["ragged"]
    slots, bs = ragged["max_tracked_sequences"], ragged["block_size"]
    chunk = engine["split_prefill_chunk"]
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    params = jax.eval_shape(
        lambda k: module.init(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    assert params["moe"]["router"].dtype == jnp.float32
    assert params["moe"]["w_up"].shape == (23, 8, 2688, 1920)
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], bs, slots=slots))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (6, 8256, 2, 32, 128), "v": (6, 8256, 2, 32, 128),
        "ssm": (23, 65, 136, 4096)}
    table = cfg.max_seq_len // bs
    i32, s = jnp.int32, jax.ShapeDtypeStruct
    call = MixedCall(s((slots, table), i32), s((slots,), i32),
                     s((slots,), bool), s((table,), i32), s((), i32),
                     s((), i32), s((), i32))
    rows = slots + chunk

    def forward(params, cache, tokens, tables, valid, read):
        return module.apply_paged(cfg, params, tokens, cache, tables, None,
                                  valid=valid, rows=read)

    sh = SingleDeviceSharding(v5e.devices[0])
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        (params, cache, s((1, rows), i32), call, s((1, rows), bool),
         s((1, slots + 1), i32)))
    compiled = jax.jit(forward, donate_argnums=(1,)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    pools = jax.tree.leaves(args[1])
    assert pool_copy_bytes(text, pools) == 0
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= pool_bytes > 4.9e9
    layouts = re.findall(
        r"bf16\[23,8,(?:2688,1920|1920,2688)\]\{([\d,]+)", text)
    assert layouts and set(layouts) == {"3,2,1,0"}
    assert mem.temp_size_in_bytes < 0.2e9
    assert 12.9e9 < mem.peak_memory_in_bytes < V5E_BYTES_LIMIT - 2.5e9
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    for name, count in (("ssm_decode_update", 6), ("ssm_chunk_scan", 6),
                        ("moe_grouped_matmul", 6),
                        ("paged_kv_write", 4), ("paged_decode", 2),
                        ("paged_prefill", 2)):      # one tile width: ISSUE 62
        assert calls.count(name) == count, (name, calls.count(name))


# --- a state and no cache: Brumby's retention layers (ISSUE 55) ------------- #
BRUMBY_CELL = "brumby-14b-base.serve-reason-32"


def _brumby_program(program):
    """The Brumby cell's paged forward on shapes at its published widths,
    all of the cell's 5 layers, as the engine calls it: ``decode`` (32
    rows of one token), ``chunk`` (one row of 512) or ``mixed`` (both as
    ``slots + chunk`` rows). ``(forward, arguments)`` with the cache
    second."""
    from benchmark.harness.manifest import Cell
    from deepspeed_tpu.models._paged import MixedCall

    cell = Cell(BRUMBY_CELL)
    engine = cell.role["engine"]
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: module.init(cfg, k), jax.random.PRNGKey(0)))
    slots = engine["ragged"]["max_tracked_sequences"]
    chunk = engine["split_prefill_chunk"]
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, 2, engine["ragged"]["block_size"], slots=slots))

    def forward(params, cache, tokens, tables, ctx, valid, rows):
        return module.apply_paged(cfg, params, tokens, cache, tables, ctx,
                                  valid=valid, slots=rows)

    i32, s = jnp.int32, jax.ShapeDtypeStruct
    if program == "mixed":
        call = MixedCall(s((slots, 1), i32), s((slots,), i32),
                         s((slots,), bool), s((1,), i32), s((), i32),
                         s((), i32), s((), i32))
        rows = slots + chunk
        return forward, (params, cache, s((1, rows), i32), call, None,
                         s((1, rows), bool), None)
    b, t = (slots, 1) if program == "decode" else (1, chunk)
    return forward, (params, cache, s((b, t), i32), s((b, 1), i32),
                     s((b,), i32), s((b, t), bool), s((b,), i32))


@pytest.mark.parametrize("program", ["decode", "chunk", "mixed"])
def test_a_state_and_no_cache_stays_where_it_is(v5e, program):
    """The Brumby cell's ``decode`` (32 rows), one ``chunk`` (512 tokens)
    and the ``mixed`` call of both at the published widths, its 5 layers,
    compiled for the chip: the cache is the state pool alone (5.9 GB), it is
    aliased argument-to-result with no copy - plain or ``copy-start`` - of
    its shape or a layer's, the weights are read where they lie, the whole
    program fits the chip, and a layer body is ONE Mosaic call of each
    kernel its segments need, by its literal name."""
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    fn, args = _brumby_program(program)
    sh = SingleDeviceSharding(v5e.devices[0])
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), args)
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    (pool,) = jax.tree.leaves(args[1])
    assert tuple(pool.shape) == (5, 33, 1032, 8704) and pool.dtype == "float32"
    assert pool_copy_bytes(text, [pool]) == 0
    dims = {",".join(map(str, shape)) for shape in (pool.shape,
                                                    pool.shape[1:])}
    assert not [line for line in text.splitlines() if "copy-start(" in line
                and any(f"[{d}]" in line for d in dims)]
    pool_bytes = math.prod(pool.shape) * 4
    assert mem.alias_size_in_bytes >= pool_bytes
    assert 0 < mem.peak_memory_in_bytes < V5E_BYTES_LIMIT
    # less than one slot's rows of temporaries: nothing pool-sized hides
    assert mem.temp_size_in_bytes < pool_bytes // 33
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    assert (calls.count("retention_decode_update"),
            calls.count("retention_chunk")) == {
        "decode": (1, 0), "chunk": (0, 1), "mixed": (1, 1)}[program]


# --- delta-rule state beside KV blocks under experts: Solar-Open2 (ISSUE 57) #
SOLAR_CELL = "solar-open2-250b.serve-longctx"


def _solar_program(program):
    """The Solar-Open2 cell's paged forward on shapes at its published
    widths, the cell's 4 layers and 40 held experts, as the engine calls it:
    ``decode`` (16 rows of one token), ``chunk`` (one row of 512) or
    ``mixed`` (both as ``slots + chunk`` rows). ``(forward, arguments)``
    with the cache second."""
    from benchmark.harness.manifest import Cell
    from deepspeed_tpu.models._paged import MixedCall

    cell = Cell(SOLAR_CELL)
    engine = cell.role["engine"]
    ragged = engine["ragged"]
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    params = jax.eval_shape(
        lambda k: module.init(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    slots, bs = ragged["max_tracked_sequences"], ragged["block_size"]
    chunk = engine["split_prefill_chunk"]
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], bs, slots=slots))
    table = cfg.max_seq_len // bs

    def forward(params, cache, tokens, tables, ctx, valid, rows):
        return module.apply_paged(cfg, params, tokens, cache, tables, ctx,
                                  valid=valid, slots=rows)

    i32, s = jnp.int32, jax.ShapeDtypeStruct
    if program == "mixed":
        call = MixedCall(s((slots, table), i32), s((slots,), i32),
                         s((slots,), bool), s((table,), i32), s((), i32),
                         s((), i32), s((), i32))
        rows = slots + chunk
        return forward, (params, cache, s((1, rows), i32), call, None,
                         s((1, rows), bool), None)
    b, t = (slots, 1) if program == "decode" else (1, chunk)
    return forward, (params, cache, s((b, t), i32), s((b, table), i32),
                     s((b,), i32), s((b, t), bool), s((b,), i32))


@pytest.mark.parametrize("program", ["decode", "chunk", "mixed"])
def test_delta_state_kv_blocks_and_experts_stay_where_they_are(v5e, program):
    """The Solar-Open2 cell's ``decode`` (16 rows), one ``chunk`` (512
    tokens) and the ``mixed`` call of both at the published widths - 1 GQA
    and 3 KDA layers, 40 of 320 experts a layer, 17 rows of state and 3152
    KV blocks - compiled for the chip: the three pools are aliased argument-
    to-result with no copy of their shape, the expert banks are read where
    they lie (next to no temporaries), the program with its 9.45 GB of
    weights fits the chip with room for a probe's reference, and a KDA layer
    body is ONE Mosaic call of the state update by its literal name and ONE
    of the chunk's delta rule by its own (``delta_chunk``: it reads the
    row's state where it lies, so no read of the rows stands before it, and
    one write of them after it)."""
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    fn, args = _solar_program(program)
    sh = SingleDeviceSharding(v5e.devices[0])
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), args)
    params, cache = args[0], args[1]
    assert params["delta"]["moe"]["router"].dtype == jnp.float32
    assert params["delta"]["moe"]["w_up"].shape == (3, 40, 4096, 1280)
    assert {k: (tuple(v.shape), v.dtype.name) for k, v in cache.items()} == {
        "k": ((1, 3152, 8, 128, 128), "bfloat16"),
        "v": ((1, 3152, 8, 128, 128), "bfloat16"),
        "delta": ((3, 17, 144, 8192), "float32")}
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    pools = jax.tree.leaves(cache)
    assert pool_copy_bytes(text, pools) == 0
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= pool_bytes > 1.85e9
    assert mem.temp_size_in_bytes < 1.0e9
    assert 11.3e9 < mem.peak_memory_in_bytes < V5E_BYTES_LIMIT - 2.5e9
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    decode, chunk = program != "chunk", program != "decode"
    assert calls.count("delta_decode_update") == int(decode)
    assert calls.count("delta_chunk") == int(chunk)
    # the tail's rows a segment, and the chunk's new state after its kernel
    assert calls.count("state_rows_read") == decode + chunk
    assert calls.count("state_rows_write") == decode + 2 * chunk
    assert calls.count("moe_grouped_matmul") >= 2


# --- a tail on the slot pool BESIDE paged K/V in every layer, under a top-1
# bank with a skip output: ZAYA1-8B (ISSUE 64) ------------------------------- #
ZAYA_CELL = "zaya1-8b.serve-reason-64"


def _zaya_program(program):
    """The ZAYA1 cell's paged forward on shapes at its published widths, the
    cell's 20 layers and all 16 experts, as the engine calls it: ``decode``
    (64 rows of one token), ``chunk`` (one row of 512) or ``mixed`` (both as
    ``slots + chunk`` rows, the head on 65). ``(forward, arguments)`` with
    the cache second."""
    from benchmark.harness.manifest import Cell
    from deepspeed_tpu.models._paged import MixedCall

    cell = Cell(ZAYA_CELL)
    engine = cell.role["engine"]
    ragged = engine["ragged"]
    cfg = cell.family.build_cfg(cell.model, **cell.role["program_options"])
    module = cell.family.module()
    params = jax.eval_shape(
        lambda k: module.init(cfg, k, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    slots, bs = ragged["max_tracked_sequences"], ragged["block_size"]
    chunk = engine["split_prefill_chunk"]
    cache = jax.eval_shape(lambda: module.init_paged_cache(
        cfg, ragged["memory_config_blocks"], bs, slots=slots))
    table = cfg.max_seq_len // bs

    def forward(params, cache, tokens, tables, ctx, valid, rows, read):
        return module.apply_paged(cfg, params, tokens, cache, tables, ctx,
                                  valid=valid, slots=rows, rows=read)

    i32, s = jnp.int32, jax.ShapeDtypeStruct
    if program == "mixed":
        call = MixedCall(s((slots, table), i32), s((slots,), i32),
                         s((slots,), bool), s((table,), i32), s((), i32),
                         s((), i32), s((), i32))
        rows = slots + chunk
        return forward, (params, cache, s((1, rows), i32), call, None,
                         s((1, rows), bool), None, s((1, slots + 1), i32))
    b, t = (slots, 1) if program == "decode" else (1, chunk)
    return forward, (params, cache, s((b, t), i32), s((b, table), i32),
                     s((b,), i32), s((b, t), bool), s((b,), i32),
                     s((b, 1), i32))


@pytest.mark.parametrize("program", ["decode", "chunk", "mixed"])
def test_a_tail_beside_kv_blocks_in_every_layer_stays_where_it_is(v5e,
                                                                  program):
    """The ZAYA1 cell's ``decode`` (64 rows), one ``chunk`` (512 tokens) and
    the ``mixed`` call of both at the published widths - 20 layers, each
    with paged K and V AND a row of the tail pool, 16 experts a layer, 2 560
    blocks of 64 tokens, 65 rows of tails - compiled for the chip: the
    three pools and the router's carried state ride ONE layer scan, the
    pools aliased argument-to-result with no copy of their shape, the expert
    banks read where they lie, the program with its 9.38 GB of weights and
    3.36 GB of pools fits the chip with room for a probe's own pools, and a
    layer body reads and writes its segment's tails by the state pool's two
    row ops beside the paged write and walk."""
    import re

    from deepspeed_tpu.telemetry.compile import pool_copy_bytes

    fn, args = _zaya_program(program)
    sh = SingleDeviceSharding(v5e.devices[0])
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), args)
    params, cache = args[0], args[1]
    moe = params["layers"]["moe"]
    assert moe["router_out"].dtype == jnp.float32
    assert moe["w_up"].shape == (20, 16, 2048, 2048)
    assert {k: (tuple(v.shape), v.dtype.name) for k, v in cache.items()} == {
        "k": ((20, 2560, 2, 64, 128), "bfloat16"),
        "v": ((20, 2560, 2, 64, 128), "bfloat16"),
        "tail": ((20, 65, 16, 256), "bfloat16")}
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    pools = jax.tree.leaves(cache)
    assert pool_copy_bytes(text, pools) == 0
    bank = [moe[n] for n in ("w_gate", "w_up", "w_down")]
    assert pool_copy_bytes(text, bank) == 0
    pool_bytes = sum(math.prod(p.shape) * p.dtype.itemsize for p in pools)
    assert mem.alias_size_in_bytes >= pool_bytes > 3.36e9
    assert mem.temp_size_in_bytes < 0.6e9
    # ... beside a probe's own pools (families/mixed_program.py: 2.73 GB)
    assert 12.7e9 < mem.peak_memory_in_bytes < V5E_BYTES_LIMIT - 2.9e9
    calls = [re.sub(r"\.\d+$", "", c) for c in re.findall(
        r"%(\S+) = .*? custom-call\(.*" + MOSAIC, text)]
    decode, chunk = program != "chunk", program != "decode"
    # ONE layer body: a read and a write of the tails a segment
    assert calls.count("state_rows_read") == decode + chunk
    assert calls.count("state_rows_write") == decode + chunk
    assert calls.count("paged_decode") == int(decode)
    assert calls.count("moe_grouped_matmul") >= 1
