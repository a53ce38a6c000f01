"""Paged (blocked-KV) flash attention Pallas kernels: ``paged_decode`` for
one query token a sequence, ``paged_prefill`` for more (prefill chunks,
batched prefill, speculative verification) - and ``paged_kv_write``, which
puts a step's K/V rows into the pool pages they belong to, in place.

The pools are ``[num_layers, num_blocks, nkv, bs, hd]`` and stay where they
are: all three kernels take the layer as a prefetched scalar and address
pages ``(layer, block, ...)``, so no program slices a layer's pool out of
the buffer or stacks one back (``models/_paged.scan_layers`` carries the
buffer through the layer scan). A 4-D pool is one layer's and needs no
layer; each operand says which it is by its rank.

Reference parity: the inference v2 ragged kernels
(``inference/v2/kernels/ragged_ops/`` — blocked flash attention over the
``BlockedKVCache``, ``inference/v2/ragged/kv_cache.py``). Round-1 shipped a
gather-based XLA path (``models/llama.py apply_paged``) that materializes a
dense [B, max_blocks*bs, ...] KV view per layer; the kernels read KV blocks
straight out of the shared pool via the block table (scalar-prefetch:
page DMAs both walks issue themselves, table-indexed ``BlockSpec``s where
Mosaic cannot slice a page), online-softmax accumulating — no dense copy,
HBM traffic = exactly the live context. The gathered expressions survive as the ops' XLA
references (``*_xla``), which the registry resolves to off a TPU.

Decode layout: one query token per sequence.
  q            [B, nh, hd]
  k/v pool     [(L,) num_blocks, nkv, bs, hd]   (block 0 = trash block; kv-head
               axis ahead of the token axis so one page of EVERY KV head is
               one contiguous ``(nkv, bs, hd)`` block with a ``(bs, hd)``
               tail — a squeezed dim in the last two positions is rejected
               by the Mosaic TPU lowering's tiling check)
  block_tables [B, max_blocks] int32
  context_lens [B] int32 — tokens ALREADY cached; the current token's K/V
               must be written to the pool before calling (so the effective
               length is context_lens + 1).
Walk: grid (B, KV-head blocks), both sequential; a grid step is one
sequence's whole walk over one block of KV heads (every KV head where they
fit, a divisor of ``nkv`` where not: :func:`_decode_tiles`). The pools reach
the kernel where they lie (``memory_space=pl.ANY``) and the walk FETCHES ITS
OWN PAGES (:func:`_decode_kernel`): an in-kernel loop with a dynamic trip
count runs from the sequence's first live page (its window's; page 0 without
one) to the page of ``context_lens[b]`` and no further, a KV tile of several
pages (up to ``_DECODE_KV_TOKENS`` tokens) an iteration, and each page of a
tile is one DMA a pool - ``pool[layer, tables[b, pg], head block]``, read
from the table in SMEM, into the page's ``bs`` rows of a double-buffered
``[2, heads, tile tokens, hd]`` VMEM scratch, which is the layout the matmul
wants (no join in VMEM). The next tile is in flight while this one is
computed, across sequences too: a walk's last iteration starts the first
tile of the next grid step, and which half of the scratch holds it is
carried in SMEM. No step is dead: a slot at context 0 takes the one tile that
initialises and writes its row, pages past a sequence's end are never
fetched, and table entries there are never read. The scores are one
head-batched ``[nkv, gpad, hd] x [nkv, kv, hd]`` contraction, the GQA query
group (g = nh/nkv rows, sublane-padded) on the MXU sublanes. Mosaic slices a
page out of a pool for a DMA only where the pool's rows are whole 128-lane
tiles (:func:`_fetches_pages`): int8 pools (their ``[.., bs, ngroups]`` f32
scales) and plain pools of heads narrower than a tile keep the multi-token
walk below at one token a sequence and every KV head a step - a grid
``(B, KV-head blocks, 1, KV tiles)`` as long as the longest context. The
same walk serves ``paged_sparse_decode`` (``paged_sparse_attention.py``: a
learned selection's decode rows) with one more mask, whose operands - the
row's threshold, two more prefetched scalars, and the tile's slice of its
index scores, one more DMA a tile - exist in the call only where a caller
hands them (:func:`_page_walk`).
``paged_prefill`` shares the flash body (:func:`_flash_update`) at ``tq``
query tokens a tile and one KV head a step, and where the pools' rows are
whole lane tiles (:func:`_fetches_pages`, the decode walk's rule) it
FETCHES ITS OWN PAGES too (:func:`_prefill_kernel`): grid ``(B, KV heads,
query tiles)``, sequential, the pools where they lie, and a grid step is one
(sequence, KV head, query tile)'s WHOLE walk - an in-kernel loop with a
dynamic trip count from the tile of its first row's window (tile 0 without
one) to the tile of its last REAL row, one DMA a live page a pool into a
double-buffered ``[2, tile tokens, hd]`` scratch, the next tile (this
walk's, or the next grid step's first) in flight while this one is
computed. No step is dead and none is folded; a query tile of padding
fetches and computes nothing and writes zeros. Its KV tile has ONE width,
the wide one (up to 1024 tokens: :func:`_wide_pages`, from the shapes and a
VMEM budget), whatever the walk's length: what a step pays whatever its
width - the flash rescale and the row reductions of its ``[rows, 128]``
scratch, 3.5 us at 1 024 rows - is most of a 256-key step, and a page that
is not fetched costs nothing (PERF.md section 6, PR 62). The same walk
serves ``paged_sparse_prefill`` (``paged_sparse_attention.py``: a learned
selection's chunk rows) with one more mask, whose operands - the rows'
thresholds, two ``[tq, 1]`` blocks, and the query tile's slice of the index
scores, one more DMA a tile - exist in the call only where a caller hands
them (:func:`_own_pages_parts`). int8 pools and
heads under 128 lanes keep the walk that went before (:func:`_paged_kernel`,
:func:`_table_walk`; ``paged_sparse_attention.py``'s masked grid walk is
built from the same parts): a grid ``(B, KV heads, query tiles, KV tiles)``
of table-indexed ``BlockSpec`` pages, joined in VMEM, whose last dimension
is DYNAMIC: the tiles up to the longest sequence's last REAL row, ``clip(
ceil(max(context_lens + lengths) / KV tile), 1, table tiles)``, computed in
the program from the call's own operands. The bound cuts the END of the walk
alone: inside it an early query tile's steps above its last row, a window's
steps below its first and a shorter sequence's fold onto a live page, and a
call of zero-length dummies still takes the one step that initialises and
writes it. That grid has TWO tile widths, and the program picks: ~256 tokens
where the walk is short, the wide tile where the same bound reaches two of
them (:func:`_takes_wide`; a ``lax.cond`` over two calls of the one walk,
both named ``paged_prefill``) - every page of a step is an operand the
pipeline pays for (~57 ns), dead or live, so at few query rows one mostly
dead wide step costs a short walk more than its one or two narrow ones. A
context that is a constant of the program bounds the walk statically, and a
walk that cannot be long is built narrow alone. :func:`decode_tile_counts`
and :func:`prefill_tile_counts` say on the host, from the same tile sizes
and the same rules (:func:`prefill_kv_pages`), how many tiles a call takes
and how many hold context - the same tiles where a walk fetches its own
pages.

Quantized KV mode (``inference.kv_quant``, docs/serving.md "Quantized KV
cache"): ``k_pool``/``v_pool`` hold int8 codes and ``k_scale``/``v_scale``
``[num_blocks, nkv, bs, ngroups]`` fp32 per-block-per-group scales ride the
same block-table-indexed BlockSpecs. The kernel loads the int8 tile plus its
scale tile and dequantizes IN-REGISTER (a lane broadcast at ngroups == 1 —
the default ``group_size >= hd`` config — or a grouped reshape-multiply
otherwise) immediately before the bf16 MXU dots. No standalone XLA
int8→bf16 convert pass over the pool ever runs: QUANT_TPU_LIVE.json pins
that path at 1.02–1.21× SLOWER than bf16, so the entire win is int8 HBM
traffic + residency with the convert hidden inside the flash loop.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import dim_semantics as _dim_semantics
from ._common import interpret as _interpret
from ._common import mxu_dot as _mxu_dot

NEG_INF = -1e30


def _dequant_tile(codes_ref, scale_ref, dtype):
    """In-register dequant of one ``[.., bs, hd]`` int8 KV tile with its
    ``[.., bs, ng]`` fp32 scale tile, emitted right before the MXU dot.
    ng == 1 (the default ``group_size >= hd`` config) is a pure lane
    broadcast; ng > 1 groups the lanes (blocked layout, matching
    ``ops.quantization.kv_quantize_int8``)."""
    x = codes_ref[...].astype(jnp.float32)
    s = scale_ref[...]
    ng = s.shape[-1]
    if ng == 1:
        x = x * s
    else:
        x = (x.reshape(x.shape[:-1] + (ng, x.shape[-1] // ng))
             * s[..., None]).reshape(x.shape)
    return x.astype(dtype)


def _contract(ndim, rhs_axis):
    """``dot_general`` numbers of ``[.., rows, n] x [.., *, *]``: the left's
    last axis contracts with the right's ``rhs_axis`` (-1 for keys ``[.., kv,
    hd]``, -2 for values); any leading (KV-head) axis is a batch."""
    batch = tuple(range(ndim - 2))
    return (((ndim - 1,), (ndim + rhs_axis,)), (batch, batch))


def _flash_init(j, m_scr, l_scr, acc_scr):
    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)


def _flash_update(s, v, m_scr, l_scr, acc_scr):
    """One online-softmax step: masked f32 scores ``s`` [.., rows, kv] and
    the KV tile's values ``v`` [.., kv, hd] into the running max / sum /
    output (a leading axis is the KV heads of one grid step)."""
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_curr = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(m_curr, m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[..., :1])
    l_scr[...] = l_prev * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=-1, keepdims=True), l_prev.shape)
    acc_scr[...] = acc_scr[...] * alpha[..., :1] + _mxu_dot(
        p.astype(v.dtype), v, _contract(s.ndim, -2),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _flash_finish(last, o_ref, l_scr, acc_scr):
    @pl.when(last)
    def _finish():
        l = l_scr[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe[..., :1]).astype(o_ref.dtype)


def _checked_window(window):
    """The kernels' window contract. window <= 0 is nonsensical: every score
    masks to NEG_INF and the all-masked softmax degenerates to a uniform
    average over a garbage block (ADVICE r5). Reject static values outright;
    clamp traced ones."""
    if isinstance(window, (int, np.integer)):
        assert window >= 1, f"sliding window must be >= 1, got {window}"
    return jnp.maximum(jnp.asarray(window, jnp.int32), 1)


def _layer_scalar(layer, *pools):
    """The ops' pool contract: a pool is ``[L, num_blocks, nkv, bs, *]`` and
    read at ``layer`` (int or traced scalar), or ``[num_blocks, nkv, bs, *]``
    and one layer's; each operand says which by its own rank. Returns the
    layer as the ``[1]`` int32 the kernels prefetch."""
    assert layer is not None or all(p is None or p.ndim == 4 for p in pools), \
        "an [L, ...] pool needs the layer to read"
    return jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)


def _page_spec(pool, heads, index_map):
    """One page of ``heads`` KV heads (None: one, squeezed) of ``pool`` as a
    block: ``index_map`` gives ``(layer, block, head block, 0, 0)``, and a
    one-layer pool drops the layer."""
    page = (None, heads) + pool.shape[-2:]
    if pool.ndim == 5:
        return pl.BlockSpec((None,) + page, index_map)
    return pl.BlockSpec(page, lambda *a: index_map(*a)[1:])


def _gathered_view(pool, block_tables, layer):
    """Dense [B, S, nkv, *] view of the rows of layer ``layer`` the tables
    reference: the XLA references' read of the pool (the kernels never build
    it)."""
    b, max_blocks = block_tables.shape
    g = pool[block_tables] if pool.ndim == 4 else pool[layer[0], block_tables]
    g = g.swapaxes(2, 3)                              # [b, mb, bs, nkv, *]
    return g.reshape((b, max_blocks * g.shape[2]) + g.shape[3:])


def _latent_values(keys, value_width):
    """A latent pool's values: the first ``value_width`` lanes of its key
    rows (the XLA references' form of the kernel's in-VMEM slice)."""
    assert value_width is not None, "a pool without a V pool is a latent " \
        "pool and says how wide its values are"
    return keys[..., :value_width]


# --------------------------------------------------------------------------- #
# the flash walks over the block table: ``paged_prefill`` at ``tq`` query
# tokens a tile and one KV head a grid step, ``paged_decode`` at one query
# token and every KV head of a sequence a grid step, each over pages it
# fetches itself (:func:`_prefill_kernel`, :func:`_decode_kernel`) or, where
# Mosaic cannot slice a page, over ``BlockSpec`` pages (:func:`_table_walk`).
# Tile sizes come from the shapes alone (:func:`_prefill_tiles`,
# :func:`_wide_pages`, :func:`_decode_tiles`).
# --------------------------------------------------------------------------- #
_Q_ROWS = 1024      # query rows (GQA group x tokens) of one tile at hd <= 128
_KV_TOKENS = 256    # KV tokens of one grid step: the matmul N, the softmax lanes
_MAX_PAGES = 8      # pool pages gathered into one KV tile (operands per pool)
_TILE_VMEM = 8 << 20    # a decode step's double-buffered KV tiles and scores
_DECODE_KV_TOKENS = 1024    # KV tokens of one tile of the decode walk, at most
_WIDE_KV_TOKENS = 1024  # KV tokens of one grid step of a LONG multi-token walk
_WIDE_WALK_TILES = 2    # ... long: its bound reaches this many wide tiles
_WIDE_VMEM = 12 << 20   # what a wide step may keep of Mosaic's 16 MiB of VMEM


def _prefill_tiles(t: int, g: int, hd: int, bs: int,
                   max_blocks: int) -> Tuple[int, int, int]:
    """(query tokens a tile, query tiles, pages a KV tile). A query tile is
    ``g * tq`` rows so that its q, f32 accumulator, m/l scratch and one
    ``[rows, KV]`` f32 score tile stay a few MB of VMEM whatever ``t`` is; a
    KV tile is as many pages as make ~256 tokens (a 32-token page alone is a
    32-wide matmul N and a quarter of the softmax's lanes)."""
    rows = _Q_ROWS * 128 // max(hd, 128)
    tq_max = max(16, rows // g // 16 * 16)
    n_qt = -(-t // tq_max)
    tq = -(-(-(-t // n_qt)) // 16) * 16     # balanced, sublane-aligned
    pages = max(1, min(_MAX_PAGES, _KV_TOKENS // bs, max_blocks))
    return tq, n_qt, pages


def _walk_vmem(rows: int, hd: int, kv: int, itemsize: int, pools: int = 2,
               mask_rows: int = 0) -> int:
    """Bytes of VMEM a step of a multi-token walk keeps at ``kv`` keys: the K
    and V (or one latent) tiles double-buffered, the ``[rows, KV]`` f32
    scores and probabilities, the q and output blocks double-buffered and
    the m / l / accumulator scratch - and under a learned selection
    (``mask_rows`` query tokens a tile) their index scores' ``[mask_rows,
    KV]`` f32 tile, double-buffered."""
    return (2 * pools * kv * hd * itemsize + 2 * rows * kv * 4
            + 4 * rows * hd * 2 + rows * (256 + hd) * 4
            + 2 * mask_rows * kv * 4)


def _wide_pages(rows: int, hd: int, bs: int, max_blocks: int, narrow: int,
                itemsize: int, quant: bool, pools: int = 2) -> int:
    """Pages of the WIDE KV tile of a multi-token walk, ``narrow`` (the ~256
    tokens of :func:`_prefill_tiles`) where there is no wider one. What a
    step pays whatever its tile's width - the flash rescale of the ``[rows,
    128]`` m / l scratch and the ``[rows, hd]`` f32 accumulator, the row
    reductions - is most of a 256-key step at 1 024 rows (PERF.md section 6,
    PRs 38, 48 and 62), so a walk takes up to ``_WIDE_KV_TOKENS`` keys a
    step: the widest doubling of ``narrow`` whose step (:func:`_walk_vmem`)
    fits ``_WIDE_VMEM`` and that the table holds: ONE of,
    where the walk fetches its own pages (:func:`_fetches_pages`; it takes
    the wide tile whatever its length - a page it does not fetch costs it
    nothing), a LONG walk of (``_WIDE_WALK_TILES``) on the grid of
    ``BlockSpec`` pages, where which tile a call takes is the program's to
    decide from its walk's bound (:func:`_takes_wide`) and a table that
    holds no long walk keeps the narrow tile alone (and the program it
    had). So do int8 pools: a layer's f32 scale pools reach each kernel
    lane-padded (PERF.md section 7), and a second walk would keep a second
    padded copy of both. From shapes alone."""
    tiles = 1 if _fetches_pages(hd, quant) else _WIDE_WALK_TILES
    pages = narrow
    while not quant and 2 * pages * bs <= _WIDE_KV_TOKENS \
            and tiles * 2 * pages <= max_blocks \
            and _walk_vmem(rows, hd, 2 * pages * bs, itemsize,
                           pools) <= _WIDE_VMEM:
        pages *= 2
    return pages


def _takes_wide(bound, wide: int, bs: int):
    """Whether a walk over the grid of ``BlockSpec`` pages whose longest
    sequence's last real row sits at ``bound`` (``max(context_lens +
    lengths)``: the program's value, or the host's integer) takes the wide
    tile of ``wide`` pages."""
    return bound >= _WIDE_WALK_TILES * wide * bs


def _group_rows(g: int) -> int:
    """Rows of one KV head's query group at one token: sublane-padded."""
    return max(8, 1 << (g - 1).bit_length())


def _fetches_pages(hd: int, quant: bool) -> bool:
    """Whether a walk (``paged_decode``'s, ``paged_prefill``'s) fetches its
    pools' pages itself. Mosaic slices a page out of a pool for a DMA only
    where the pool's rows are whole 128-lane tiles: not out of an int8
    pool's ``[.., bs, ngroups]`` f32 scales, nor out of plain pools of heads
    narrower than a tile (both reach a kernel with their rows padded to 128
    lanes). Those keep the grid of ``BlockSpec`` pages."""
    return not quant and hd % 128 == 0


def _decode_tiles(nkv: int, g: int, hd: int, bs: int, max_blocks: int,
                  itemsize: int, quant: bool,
                  pools: int = 2) -> Tuple[int, int, int]:
    """(pages a KV tile, KV heads a grid step, KV tiles the table holds) of
    the decode walk. One page of every KV head is one contiguous block of
    the pool, so a step takes them all, at as many pages as make ~256
    tokens - as long as what the walk keeps in VMEM for each (head, page)
    fits ``_TILE_VMEM``: the K and V rows of its two tiles (the one computed
    and the one in flight; an int8 page's f32 scale rows pad their group
    lanes to 128) and the group's f32 scores and probabilities. Many or wide
    KV heads get a head block that divides ``nkv``; only a single head over
    the budget gets fewer pages. Where the walk fetches its own pages
    (:func:`_fetches_pages`) the tile then WIDENS, by doubling, to up to
    ``_DECODE_KV_TOKENS`` where the same budget and the table hold it: a
    tile's fixed work (the loop's scalar side, the flash rescale, the
    matmuls' fill and drain) does not depend on its width, and at 64 query
    rows of 640 lanes it is a third of a 256-token tile (PERF.md section 6,
    PR 49). ``pools``: the
    pools a step reads a page of (1: a latent pool, whose values are lanes
    of its keys' page)."""
    pages = max(1, min(_MAX_PAGES, _KV_TOKENS // bs, max_blocks))
    page = 2 * pools * bs * (hd * itemsize + (128 * 4 if quant else 0)) \
        + 2 * _group_rows(g) * bs * 4
    room = _TILE_VMEM // page               # (head, page) pairs a step
    heads = max([h for h in range(1, nkv + 1)
                 if nkv % h == 0 and h * pages <= room], default=1)
    pages = max(1, min(pages, room // heads))
    while _fetches_pages(hd, quant) and 2 * pages * bs <= _DECODE_KV_TOKENS \
            and 2 * pages <= max_blocks and 2 * pages * heads <= room:
        pages *= 2
    return pages, heads, -(-max_blocks // pages)


def decode_tile_counts(context_lens, nh: int, pool_shape, itemsize: int,
                       max_blocks: int, quant: bool,
                       pools: int = 2) -> Tuple[int, int]:
    """(live, visited) KV tiles of ONE ``paged_decode`` call over slots at
    ``context_lens`` (host integers, no window) - or of one
    ``paged_sparse_decode`` call, the same walk under one more mask: the
    tiles that hold context and the tiles the walk takes. Each slot walks to
    its own end, so they are the same tiles - a slot at context 0 its one -
    but where the walk is the grid of ``BlockSpec`` pages
    (:func:`_fetches_pages`), which takes every slot as far as the longest.
    What the serving engine puts on its ``decode_step`` span."""
    nkv, bs, hd = pool_shape[-3:]
    pages, heads, n_kv = _decode_tiles(nkv, nh // nkv, hd, bs, max_blocks,
                                       itemsize, quant, pools)
    tiles = np.minimum(np.asarray(context_lens) // (pages * bs) + 1, n_kv)
    live = int(tiles.sum())
    return (live * (nkv // heads),
            (live if _fetches_pages(hd, quant)
             else int(tiles.max()) * tiles.size) * (nkv // heads))


def prefill_kv_pages(context_lens, lengths, t: int, nh: int, pool_shape,
                     max_blocks: int, itemsize: int = 2, quant: bool = False,
                     pools: int = 2) -> int:
    """Pages of the KV tile ONE ``paged_prefill`` call takes (host integers,
    the rule of the program: the wide tile where the walk fetches its own
    pages, and on the grid of ``BlockSpec`` pages where the longest
    sequence's last real row reaches :func:`_takes_wide`'s bound). Times the
    pool's block size it is the ``chunk_attn_kv_tile`` of a chunk's span."""
    nkv, bs, hd = pool_shape[-3:]
    tq, _, narrow = _prefill_tiles(t, nh // nkv, hd, bs, max_blocks)
    wide = _wide_pages(nh // nkv * tq, hd, bs, max_blocks, narrow, itemsize,
                       quant, pools)
    bound = int((np.asarray(context_lens) + np.asarray(lengths)).max())
    return wide if _fetches_pages(hd, quant) or (
        wide > narrow and _takes_wide(bound, wide, bs)) else narrow


def prefill_tile_counts(context_lens, lengths, t: int, nh: int, pool_shape,
                        max_blocks: int, window=None, itemsize: int = 2,
                        quant: bool = False, pools: int = 2,
                        pages: int = None) -> Tuple[int, int, int]:
    """(live, taken, table-wide) KV tiles of ONE ``paged_prefill`` call of
    ``t`` rows a sequence, ``lengths`` of them real, at ``context_lens``
    (host integers; ``window``: the layer's, an int or None), at the KV tile
    the call takes (:func:`prefill_kv_pages`; ``pages``: the tile of another
    walk of the same form, ``paged_sparse_prefill``'s): the tiles a real row of
    their query tile attends, the tiles the walk takes - the SAME tiles
    where it fetches its own pages (:func:`_fetches_pages`: each (sequence,
    KV head, query tile) walks from its own first tile to its own last); on
    the grid of ``BlockSpec`` pages every one of them walks as far as the
    longest sequence's last real row - and the steps of a grid as wide as
    the table. What the serving engine puts on a chunk's span."""
    nkv, bs, hd = pool_shape[-3:]
    tq, n_qt, _ = _prefill_tiles(t, nh // nkv, hd, bs, max_blocks)
    pages = pages or prefill_kv_pages(context_lens, lengths, t, nh,
                                      pool_shape, max_blocks, itemsize, quant,
                                      pools)
    kv, n_kv = pages * bs, -(-max_blocks // pages)
    ctx = np.asarray(context_lens)[:, None, None]
    n = np.asarray(lengths)[:, None, None]
    q_lo = (np.arange(n_qt) * tq)[None, :, None]
    j = np.arange(n_kv)[None, None, :]
    live = (q_lo < n) & (j * kv < ctx + np.minimum(q_lo + tq, n))
    if window is not None:
        live &= j * kv + kv - 1 > ctx + q_lo - window
    n_live = min(max(-(-int((ctx + n).max()) // kv), 1), n_kv)
    walks = ctx.size * nkv * n_qt
    live = int(live.sum()) * nkv
    return (live, live if _fetches_pages(hd, quant) else walks * n_live,
            walks * n_kv)


def _kv_tile(page_refs, scale_refs, dtype):
    """One KV tile from its pages' refs (int8 pages dequantize in-register
    with their scale tiles), joined along the token axis in VMEM."""
    tiles = [r[...] if s is None else _dequant_tile(r, s, dtype)
             for r, s in zip(page_refs, scale_refs)]
    return tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=-2)


def _chunk_scores(q, k, j, kv, ctx, n, q_lo, tq, wnd_ref, scale):
    """Masked f32 scores ``[.., rows, kv]`` of a query tile against KV tile
    ``j``. Rows are g-major/t-minor inside the tile: row r is query token
    ``q_lo + r % tq`` at absolute position ``ctx + q_lo + r % tq``; it
    attends itself, nothing past the sequence's ``n`` real rows, and under
    a window (``wnd_ref``, None: none) its last ``wnd_ref[0]`` positions."""
    s = _mxu_dot(q, k, _contract(q.ndim, -1),
                 preferred_element_type=jnp.float32) * scale
    rows = s.shape[-2]
    pos = j * kv + jax.lax.broadcasted_iota(jnp.int32, (1, kv), 1)
    q_abs = ctx + q_lo + jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0), tq)
    valid = jnp.logical_and(pos <= q_abs,   # row attends itself too
                            pos < ctx + n)
    if wnd_ref is not None:
        valid = jnp.logical_and(valid, pos > q_abs - wnd_ref[0])
    return jnp.where(valid, s, NEG_INF)


def _selected_chunk_scores(q, k, idx, tau, cut, selected, j, kv, ctx, n,
                           q_lo, tq, scale):
    """:func:`_chunk_scores` (no window) under one more mask, a learned
    selection's (``paged_sparse_attention.py``): ``selected(idx, positions,
    tau, cut)`` over the tile's index scores ``idx [tq, kv]`` and the rows'
    thresholds ``[tq, 1]``. One token's row of the mask - its own positions,
    of them the selected - serves its whole query group: computed once a
    token, repeated g-major as the rows are. The position mask is AND-ed
    over whatever ``selected`` says: scores past a row's position are never
    written."""
    s = _mxu_dot(q, k, _contract(q.ndim, -1),
                 preferred_element_type=jnp.float32) * scale
    g = s.shape[-2] // tq
    pos = j * kv + jax.lax.broadcasted_iota(jnp.int32, (1, kv), 1)
    q_abs = ctx + q_lo + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
    keep = jnp.logical_and(
        selected(idx, pos, tau, cut),
        jnp.logical_and(pos <= q_abs, pos < ctx + n))       # [tq, kv]
    # NEG_INF absorbs any (finite) score: a dropped token's is exactly NEG_INF
    drop = jnp.where(keep, 0.0, NEG_INF)
    if g > 1:       # rows are g-major: one mask row a token
        return (s.reshape(g, tq, kv) + drop[None]).reshape(g * tq, kv)
    return s + drop


def _paged_kernel(*refs, bs, pages, scale, n_kv, tq, has_window, quant,
                  vd=None):
    """q ``[.., rows, hd]`` against the KV tile ``[.., pages * bs, hd]`` of
    grid step ``j``; a leading axis is the KV heads of the step. ``n_kv``
    None: the grid's last dimension is dynamic. ``vd``: there is no V pool
    and a token's values are the first ``vd`` lanes of its key row (a latent
    pool: the key page is read once and serves both matmuls)."""
    ctx_ref, len_ref = refs[1], refs[2]    # after the tables, before the layer
    wnd_ref = refs[4] if has_window else None
    refs = refs[4 + int(has_window):]
    q_ref, refs = refs[0], refs[1:]
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]   # (no V: unread)
    ks_refs, vs_refs = ((refs[2 * pages:3 * pages], refs[3 * pages:4 * pages])
                        if quant else ((None,) * pages,) * 2)
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    b, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    kv = pages * bs

    _flash_init(j, m_scr, l_scr, acc_scr)

    # the tile's live range ends at its last REAL row (padded rows and
    # zero-length dummy sequences extend nothing) and starts at its first
    # row's window
    ctx, n = ctx_ref[b], len_ref[b]
    q_lo = qi * tq
    live = jnp.logical_and(q_lo < n,
                           j * kv < ctx + jnp.minimum(q_lo + tq, n))
    if has_window:
        live = jnp.logical_and(live,
                               j * kv + kv - 1 > ctx + q_lo - wnd_ref[0])

    @pl.when(live)
    def _compute():
        q = q_ref[...]                     # [.., rows, hd]
        k = _kv_tile(k_refs, ks_refs, q.dtype)   # [.., kv, hd]
        v = _kv_tile(v_refs, vs_refs, q.dtype) if vd is None \
            else k[..., :vd]
        s = _chunk_scores(q, k, j, kv, ctx, n, q_lo, tq, wnd_ref, scale)
        _flash_update(s, v, m_scr, l_scr, acc_scr)

    _flash_finish(j == (pl.num_programs(3) if n_kv is None else n_kv) - 1,
                  o_ref, l_scr, acc_scr)


@functools.partial(jax.jit,
                   static_argnames=("tq", "bs", "pages", "max_blocks"))
def _live_page(j, p, qi, ctx, n, window, *, tq, bs, pages, max_blocks):
    """The table entry page ``p`` of KV tile ``j`` reads, for query tile
    ``qi`` of a sequence at ``ctx`` with ``n`` real rows: the tile's live
    pages are ``[lo_pg, hi_pg]`` - up to its last real row, from its first
    row's window on - and every other (tile, page) folds onto the nearest of
    them. Jitted, and in ``lax`` primitives: a walk has one index map a page
    of its KV tile (64 at a wide tile), each traced and lowered on its own
    and evaluated on the scalar core every grid step; under one ``jit`` they
    share ONE trace (an index map is then a few reads and this call: set-up
    seconds a program otherwise), and ``jnp.clip`` or ``//`` would be a dozen
    scalar operations each where ``min`` / ``max`` / ``div`` are one (the
    operands are never negative where the result is used)."""
    add, mul, div = jax.lax.add, jax.lax.mul, jax.lax.div
    lo, hi = jax.lax.max, jax.lax.min
    q_lo = mul(qi, tq)
    last = add(add(ctx, hi(add(q_lo, tq), n)), -1)
    hi_pg = hi(lo(div(last, bs), 0), max_blocks - 1)
    lo_pg = 0 if window is None else hi(
        div(lo(add(add(ctx, q_lo), add(1, -window)), 0), bs), hi_pg)
    j_eff = hi(lo(j, 0 if window is None else div(lo_pg, pages)),
               div(hi_pg, pages))
    return hi(lo(add(mul(j_eff, pages), p), lo_pg), hi_pg)


def _table_walk(qg, k_pool, v_pool, block_tables, context_lens, lengths,
                layer, window, k_scale, v_scale, *, scale, rows, tq, pages,
                heads, n_kv, vd=None):
    """The kernel, grid and arguments of one walk over layer ``layer`` of the
    ``[L, num_blocks, nkv, bs, hd]`` pools: the layer is one more prefetched
    scalar and the first coordinate of every pool page, so the pools are read
    where they lie and no layer's pool is ever sliced out. ``qg`` ``[B, nkv,
    query tiles * rows, hd]``; row r of tile qi is query token ``qi * tq + r
    % tq`` of its sequence. Grid ``(B, KV-head blocks, query tiles, KV tiles)``, KV
    innermost; ``heads`` None is one KV head a step with the head axis
    squeezed, ``n_kv`` may be traced. A KV tile is ``pages`` pool pages, each
    its own table-indexed ``BlockSpec`` over the same pool, joined in VMEM.
    A step wholly above its query tile's last real row (causal) or wholly
    below its first row's window skips its compute and FOLDS onto a live
    page index: Pallas elides the DMA when consecutive steps map to the same
    block, so HBM traffic is the live context per (KV-head block, query
    tile), with or without a window. ``v_pool`` None with ``vd``: the key
    width and the value width differ and the values are the first ``vd``
    lanes of the key page (a latent pool) - one pool, one DMA a page, an
    output ``vd`` wide."""
    B, nkv, _, hd = qg.shape
    nblocks, bs = k_pool.shape[-4], k_pool.shape[-2]
    max_blocks = block_tables.shape[1]
    has_window, quant = window is not None, k_scale is not None
    assert (v_pool is None) == (vd is not None) and not (vd and quant), \
        "values come from a V pool or from the key page's lanes"
    static = isinstance(n_kv, int)
    kernel = functools.partial(_paged_kernel, bs=bs, pages=pages,
                               scale=float(scale),
                               n_kv=n_kv if static else None, tq=tq,
                               has_window=has_window, quant=quant, vd=vd)
    od = vd or hd                       # the output's (values') width

    # index maps are called positionally with one trailing arg per
    # prefetched scalar array
    def qmap(b, h, qi, j, *_):
        return (b, h, qi, 0)

    def page_map(p):
        def kvmap(b, h, qi, j, tables, ctx, lens, layer, *rest):
            pg = _live_page(j, p, qi, ctx[b], lens[b],
                            rest[0][0] if rest else None, tq=tq, bs=bs,
                            pages=pages, max_blocks=max_blocks)
            return (layer[0], jax.lax.min(jax.lax.max(tables[b, pg], 0),
                                          nblocks - 1), h, 0, 0)
        return kvmap

    # scale tiles ride the same maps as their code tiles, so a dead step
    # elides both DMAs together
    pools = (k_pool,) + (() if v_pool is None else (v_pool,)) \
        + ((k_scale, v_scale) if quant else ())
    in_specs = [pl.BlockSpec((None, heads, rows, hd), qmap)] + [
        _page_spec(pool, heads, page_map(p))
        for pool in pools for p in range(pages)]
    operands = [qg] + [pool for pool in pools for _ in range(pages)]
    lead = () if heads is None else (heads,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + int(has_window),
        grid=(B, nkv // (heads or 1), qg.shape[2] // rows, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, heads, rows, od), qmap),
        scratch_shapes=[
            pltpu.VMEM(lead + (rows, 128), jnp.float32),
            pltpu.VMEM(lead + (rows, 128), jnp.float32),
            pltpu.VMEM(lead + (rows, od), jnp.float32),
        ],
    )
    prefetch = [block_tables.astype(jnp.int32),
                context_lens.astype(jnp.int32), lengths.astype(jnp.int32),
                layer]
    if has_window:
        prefetch.append(jnp.asarray(window, jnp.int32).reshape(1))
    return kernel, grid_spec, prefetch + operands


_WALK_GRID = _dim_semantics("parallel", "parallel", "parallel", "arbitrary")


def _decode_kernel(*refs, bs, pages, heads, scale, max_blocks, nblocks,
                   has_window, vd, layered, selected=None):
    """``paged_decode``: grid step ``(b, h)`` walks KV-head block ``h`` of
    sequence ``b`` from its first live page to the page of its context, a
    tile of ``pages`` pages an iteration of an in-kernel loop, and fetches
    those pages itself: one DMA a (page, pool) from the block table in SMEM
    into the tile's rows of a double-buffered ``[2, heads, pages * bs, hd]``
    scratch. The NEXT tile - this walk's, or the first of the next grid
    step's - is started before this one is waited for; which half of the
    scratch holds the tile in flight is carried in SMEM from grid step to
    grid step (the grid is sequential). ``vd``: one pool, and a token's
    values are the first ``vd`` lanes of its key row. ``selected``: the walk
    of ``paged_sparse_decode`` - one more mask, ``selected(scores, positions,
    tau, cut)`` (``paged_sparse_attention.selected``): the row's threshold is
    two more prefetched scalars a sequence, and a tile's slice of the call's
    index scores ``[B, rows, S]`` (row 0 the query token's) one more DMA a
    tile, after the pools' in the operands, the scratch and the semaphores."""
    n_pools, selects = 1 if vd else 2, selected is not None
    n_src = n_pools + int(selects)
    tables_ref, ctx_ref, layer_ref = refs[:3]
    wnd_ref = refs[3] if has_window else None
    refs = refs[3 + int(has_window):]
    if selects:
        (tau_ref, cut_ref), refs = refs[:2], refs[2:]
    q_ref, hbm, o_ref = refs[0], refs[1:1 + n_src], refs[1 + n_src]
    bufs = refs[2 + n_src:2 + 2 * n_src]
    sems, slot_ref, m_scr, l_scr, acc_scr = refs[2 + 2 * n_src:]
    b, h = pl.program_id(0), pl.program_id(1)
    kv = pages * bs
    add, mul, div = jax.lax.add, jax.lax.mul, jax.lax.div
    lo, hi = jax.lax.max, jax.lax.min

    def span(b):
        """(first, last) live page of sequence ``b``: its window's first
        page, or page 0, to the page of the current token."""
        last = hi(div(ctx_ref[b], bs), max_blocks - 1)
        if not has_window:
            return 0, last
        return hi(div(lo(add(ctx_ref[b], add(1, -wnd_ref[0])), 0), bs),
                  last), last

    def tile_copies(b, h, pg0, last, slot, fetch):
        """The page copies of the tile of (sequence, head block) that begins
        at table entry ``pg0`` - of its pages up to the sequence's ``last``
        alone - started (``fetch``) or waited for; a wait takes the copy's
        shape and semaphore, and no source."""
        def page(p, _):
            blk = hi(lo(tables_ref[b, add(pg0, p)], 0), nblocks - 1) \
                if fetch else 0
            rows = pl.ds(pl.multiple_of(mul(p, bs), bs), bs)
            for i, (pool, buf) in enumerate(zip(hbm[:n_pools], bufs)):
                src = pool.at[layer_ref[0], blk] if layered else pool.at[blk]
                copy = pltpu.make_async_copy(
                    src.at[pl.ds(mul(h, heads), heads)],
                    buf.at[slot, :, rows], sems.at[i, slot])
                copy.start() if fetch else copy.wait()
            return _
        jax.lax.fori_loop(0, hi(pages, add(add(last, 1), -pg0)), page, 0)
        if selects:     # the tile's index scores: whole, whatever is live
            copy = pltpu.make_async_copy(
                hbm[n_pools].at[b, :, pl.ds(pl.multiple_of(mul(pg0, bs), kv),
                                            kv)],
                bufs[n_pools].at[slot], sems.at[n_pools, slot])
            copy.start() if fetch else copy.wait()

    first, last = span(b)

    @pl.when(jnp.logical_and(b == 0, h == 0))
    def _prime():
        # a tile's rows past its sequence's last page are never fetched and
        # always masked, so what the values' scratch holds there has to be
        # finite: every earlier tile's rows are, fresh VMEM need not be
        bufs[n_pools - 1][...] = jnp.zeros_like(bufs[n_pools - 1])
        slot_ref[0] = 0
        tile_copies(b, h, first, last, 0, True)

    _flash_init(0, m_scr, l_scr, acc_scr)
    ctx = ctx_ref[b]
    n = add(div(add(last, -first), pages), 1)

    def tile(j, _):
        slot = slot_ref[0]
        pg0 = add(first, mul(j, pages))
        more = j + 1 < n
        step = jnp.logical_and(jnp.logical_not(more),
                               h + 1 == pl.num_programs(1))
        b_next = jnp.where(step, b + 1, b)
        h_next = jnp.where(more, h, jnp.where(step, 0, h + 1))

        @pl.when(b_next < pl.num_programs(0))
        def _fetch_next():
            first_next, last_next = span(b_next)
            tile_copies(b_next, h_next,
                        jnp.where(more, add(pg0, pages), first_next),
                        last_next, 1 - slot, True)

        tile_copies(b, h, pg0, last, slot, False)
        q = q_ref[...]                             # [heads, gpad, hd]
        k = bufs[0][slot]                          # [heads, kv, hd]
        v = bufs[1][slot] if vd is None else k[..., :vd]
        s = _mxu_dot(q, k, _contract(q.ndim, -1),
                     preferred_element_type=jnp.float32) * scale
        pos = mul(pg0, bs) + jax.lax.broadcasted_iota(jnp.int32, (1, kv), 1)
        valid = pos <= ctx                          # the current token too
        if has_window:
            valid = jnp.logical_and(valid, pos > ctx - wnd_ref[0])
        if selects:     # scores past ``ctx`` may be anything: ``valid`` wins
            valid = jnp.logical_and(valid, selected(
                bufs[n_pools][slot, 0:1], pos, tau_ref[b], cut_ref[b]))
        _flash_update(jnp.where(valid, s, NEG_INF), v, m_scr, l_scr, acc_scr)
        slot_ref[0] = 1 - slot
        return _

    jax.lax.fori_loop(0, n, tile, 0)
    _flash_finish(True, o_ref, l_scr, acc_scr)


def _page_walk(qg, pools, block_tables, context_lens, layer, window, *,
               scale, pages, heads, vd=None, selected=None, selection=()):
    """The kernel, grid and arguments of one walk that fetches its own pages
    (:func:`_decode_kernel`) over layer ``layer`` of ``pools`` (K and V, or
    one latent pool with ``vd``), which reach it where they lie. ``qg``
    ``[B, nkv, gpad, hd]``. ``selected`` with ``selection``: one more mask
    (``selected(scores, positions, tau, cut)``) and its ``(idx, tau, cut)`` -
    the call's index scores ``[B, rows, S]`` (``S`` whole KV tiles; left in
    HBM like the pools) and each sequence's threshold ``[B]``."""
    B, nkv, gpad, hd = qg.shape
    nblocks, bs = pools[0].shape[-4], pools[0].shape[-2]
    od = vd or hd                       # the output's (values') width
    scores, taus = list(selection[:1]), list(selection[1:])
    hbm = pools + scores
    kernel = functools.partial(
        _decode_kernel, bs=bs, pages=pages, heads=heads, scale=scale,
        max_blocks=block_tables.shape[1], nblocks=nblocks,
        has_window=window is not None, vd=vd, layered=pools[0].ndim == 5,
        selected=selected)

    def qmap(b, h, *_):
        return (b, h, 0, 0)

    tiles = [(heads, pages * bs, hd)] * len(pools) \
        + [(idx.shape[1], pages * bs) for idx in scores]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3 + int(window is not None) + len(taus),
        grid=(B, nkv // heads),
        in_specs=[pl.BlockSpec((None, heads, gpad, hd), qmap)]
        + [pl.BlockSpec(memory_space=pl.ANY)] * len(hbm),
        out_specs=pl.BlockSpec((None, heads, gpad, od), qmap),
        scratch_shapes=[pltpu.VMEM((2,) + tile, p.dtype)
                        for tile, p in zip(tiles, hbm)] + [
            pltpu.SemaphoreType.DMA((len(hbm), 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((heads, gpad, 128), jnp.float32),
            pltpu.VMEM((heads, gpad, 128), jnp.float32),
            pltpu.VMEM((heads, gpad, od), jnp.float32),
        ],
    )
    args = [block_tables.astype(jnp.int32),
            context_lens.astype(jnp.int32), layer] \
        + ([] if window is None else [window.reshape(1)]) + taus \
        + [qg] + hbm
    return kernel, grid_spec, args


# in order: the tile in flight belongs to the NEXT grid step
_PAGE_WALK_GRID = _dim_semantics("arbitrary", "arbitrary")


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                           context_lens: jnp.ndarray, *,
                           scale: float = None,
                           window=None, k_scale=None,
                           v_scale=None, layer=None,
                           value_width: int = None) -> jnp.ndarray:
    """See module docstring. Returns [B, nh, hd]. ``v_pool`` None with
    ``value_width``: a latent pool - a token's values are the first
    ``value_width`` lanes of its key row, q is as wide as that row and the
    result ``[B, nh, value_width]``. ``layer``: the layer of
    ``[L, num_blocks, nkv, bs, hd]`` pools to attend over (int or traced
    scalar - the layer scan's index); a 4-D pool is one layer's. ``window``: optional
    sliding-window length (int or traced scalar — exaone4 scans per-layer
    windows): only the last ``window`` positions are attended, and the walk
    begins at the window's first page. ``k_scale``/``v_scale``:
    per-block-per-group fp32 scale pools ``[num_blocks, nkv, bs, ngroups]``
    for int8 code pools — the quantized-KV mode with dequant fused into the
    flash loop (both or neither must be given)."""
    B, nh, hd = q.shape
    assert (k_scale is None) == (v_scale is None), \
        "k_scale and v_scale must be given together"
    quant, vd = k_scale is not None, value_width
    assert (v_pool is None) == (vd is not None) and not (vd and quant), \
        "values come from a V pool or from the key page's lanes"
    layer = _layer_scalar(layer, k_pool, v_pool, k_scale, v_scale)
    nkv, bs = k_pool.shape[-3:-1]
    max_blocks = block_tables.shape[1]
    g = nh // nkv
    gpad = _group_rows(g)
    if window is not None:
        window = _checked_window(window)
    pages, heads, n_kv = _decode_tiles(
        nkv, g, hd, bs, max_blocks, k_pool.dtype.itemsize, quant,
        1 if v_pool is None else 2)
    scale = float(hd ** -0.5 if scale is None else scale)
    od = vd or hd                       # the output's (values') width
    # [B, nkv, gpad, hd] query groups
    qg = jnp.pad(q.reshape(B, nkv, g, hd),
                 ((0, 0), (0, 0), (0, gpad - g), (0, 0)))
    if not _fetches_pages(hd, quant):
        # the multi-token op's walk of BlockSpec pages, one token a sequence:
        # its grid ends with the longest context's last tile (the current
        # token included)
        n_live = jnp.clip(jnp.max(context_lens) // (pages * bs) + 1, 1, n_kv)
        kernel, grid_spec, args = _table_walk(
            qg, k_pool, v_pool, block_tables, context_lens,
            jnp.ones((B,), jnp.int32), layer, window, k_scale, v_scale,
            scale=scale, rows=gpad, tq=1, pages=pages, heads=heads,
            n_kv=n_live.astype(jnp.int32), vd=vd)
        order = _WALK_GRID
    else:
        kernel, grid_spec, args = _page_walk(
            qg, [k_pool] + ([] if v_pool is None else [v_pool]),
            block_tables, context_lens, layer, window, scale=scale,
            pages=pages, heads=heads, vd=vd)
        order = _PAGE_WALK_GRID
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qg.shape[:-1] + (od,), q.dtype),
        compiler_params=order,
        interpret=_interpret(),
        name="paged_decode",
    )(*args)
    return out[:, :, :g].reshape(B, nh, od)


def paged_decode_attention_xla(q: jnp.ndarray, k_pool: jnp.ndarray,
                               v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                               context_lens: jnp.ndarray, *,
                               scale: float = None,
                               window=None, k_scale=None,
                               v_scale=None, layer=None,
                               value_width: int = None) -> jnp.ndarray:
    """Dense-gather fallback with identical semantics (compiled XLA — the
    right choice off-TPU, where the Pallas path runs interpreted).
    ``k_scale``/``v_scale``: the quantized-KV reference path — int8 code
    pools dequantize on the gathered view (the convert rides the gather
    consumer, matching the fused-kernel semantics bit-for-bit in fp32)."""
    from ..attention import attention_xla

    B, nh, hd = q.shape
    layer = _layer_scalar(layer, k_pool, v_pool, k_scale, v_scale)
    nkv, bs = k_pool.shape[-3:-1]
    max_blocks = block_tables.shape[1]
    S = max_blocks * bs
    kg = _gathered_view(k_pool, block_tables, layer)
    vg = _latent_values(kg, value_width) if v_pool is None \
        else _gathered_view(v_pool, block_tables, layer)
    if window is not None:
        window = _checked_window(window)
    if k_scale is not None and k_scale.shape[-1] == 1:
        # one scale per (block, head, token) — the default group_size >= hd
        # config. Fold the scales into SCORE space instead of dequantizing
        # the [B, S, nkv, hd] gathered views: s_pos = (q · codes_pos) ·
        # k_scale_pos and out = (p · v_scale) @ v_codes, so the per-step
        # dequant work drops from O(S · hd) multiplies per head to O(S)
        sc = hd ** -0.5 if scale is None else scale
        g = nh // nkv
        qg = q.reshape(B, nkv, g, hd).astype(jnp.float32)
        ksg = _gathered_view(k_scale, block_tables, layer)[..., 0]
        vsg = _gathered_view(v_scale, block_tables, layer)[..., 0]
        s = jnp.einsum("bngh,bsnh->bngs", qg, kg.astype(jnp.float32)) * sc
        s = s * ksg.transpose(0, 2, 1)[:, :, None, :]       # [B, nkv, g, S]
        kv_pos = jnp.arange(S)[None, None, None, :]
        cl = context_lens[:, None, None, None]
        mask = kv_pos <= cl
        if window is not None:
            mask = mask & (kv_pos > cl - window)
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        p = p * vsg.transpose(0, 2, 1)[:, :, None, :]
        out = jnp.einsum("bngs,bsnh->bngh", p, vg.astype(jnp.float32))
        return out.reshape(B, nh, hd).astype(q.dtype)
    if k_scale is not None:
        from ..quantization import kv_dequantize_int8

        kg = kv_dequantize_int8(
            kg, _gathered_view(k_scale, block_tables, layer), q.dtype)
        vg = kv_dequantize_int8(
            vg, _gathered_view(v_scale, block_tables, layer), q.dtype)
    kv_pos = jnp.arange(S)[None, None, None, :]
    cl = context_lens[:, None, None, None]
    mask = kv_pos <= cl
    if window is not None:
        mask = mask & (kv_pos > cl - window)
    out = attention_xla(q[:, None], kg, vg, causal=False, mask=mask,
                        scale=scale)
    return out[:, 0]


def paged_prefill_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                            v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                            context_lens: jnp.ndarray, lengths=None, *,
                            scale: float = None, window=None, k_scale=None,
                            v_scale=None, layer=None,
                            value_width: int = None) -> jnp.ndarray:
    """Multi-token attention over the paged pools, flash over the block
    table: every ``t > 1`` call of ``models/_paged.paged_attention_step`` — a
    SplitFuse prefill chunk at a context offset, a batched prefill, a
    prefix-cache suffix, the speculative verify window ``[last_token,
    draft_1..k]``.

    q ``[B, t, nh, hd]`` — row ti of sequence b sits at absolute position
    ``context_lens[b] + ti``; this step's K/V must already be scattered into
    the pool (like the decode kernel's current token), and row ti attends
    positions ``<= context_lens[b] + ti``. ``lengths`` ``[B]`` (default: all
    ``t``) counts each sequence's REAL rows: rows past it are padding whose
    output is unspecified (callers discard it), and they neither extend the
    live range nor reach table entries past the sequence's blocks — a
    zero-length dummy row of a batched prefill computes nothing.
    ``window``/``k_scale``/``v_scale``/``layer``/``value_width`` as in
    :func:`paged_decode_attention`.
    Returns ``[B, t, nh, hd]`` (``value_width`` for ``hd`` over a latent
    pool). The walk: one KV head a grid step, query tiles of ``g * tq``
    rows, over pages it fetches itself (:func:`_own_pages_walk`) or, for
    int8 pools and heads under 128 lanes, over ``BlockSpec`` pages
    (:func:`_table_walk`)."""
    B, t, nh, hd = q.shape
    assert (k_scale is None) == (v_scale is None), \
        "k_scale and v_scale must be given together"
    layer = _layer_scalar(layer, k_pool, v_pool, k_scale, v_scale)
    nkv, bs = k_pool.shape[-3:-1]
    max_blocks = block_tables.shape[1]
    g = nh // nkv
    tq, n_qt, pages = _prefill_tiles(t, g, hd, bs, max_blocks)
    rows = g * tq
    if window is not None:
        window = _checked_window(window)
    if lengths is None:
        lengths = jnp.full((B,), t, jnp.int32)

    # [B, nkv, n_qt * rows, hd]: tile-major, then g-major/t-minor rows
    # (head h = kv * g + gi)
    qg = jnp.pad(q, ((0, 0), (0, n_qt * tq - t), (0, 0), (0, 0)))
    qg = qg.reshape(B, n_qt, tq, nkv, g, hd).transpose(0, 3, 1, 4, 2, 5) \
        .reshape(B, nkv, n_qt * rows, hd)
    od = value_width or hd
    bound = jnp.max(context_lens + lengths)

    def walk(pages):
        # the grid ends with the last tile any sequence's real rows reach (at
        # least one step: a call of dummies alone still initialises and
        # writes)
        n_live = jnp.clip(-(-bound // (pages * bs)), 1,
                          -(-max_blocks // pages))
        kernel, grid_spec, args = _table_walk(
            qg, k_pool, v_pool, block_tables, context_lens, lengths, layer,
            window, k_scale, v_scale,
            scale=hd ** -0.5 if scale is None else scale,
            rows=rows, tq=tq, pages=pages, heads=None,
            n_kv=n_live.astype(jnp.int32), vd=value_width)
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(qg.shape[:-1] + (od,), q.dtype),
            compiler_params=_WALK_GRID,
            interpret=_interpret(),
            name="paged_prefill",
        )(*args)

    wide = _wide_pages(rows, hd, bs, max_blocks, pages,
                       k_pool.dtype.itemsize, k_scale is not None,
                       1 if v_pool is None else 2)
    reach = int(context_lens.max()) + t \
        if isinstance(context_lens, np.ndarray) else max_blocks * bs
    if _fetches_pages(hd, k_scale is not None):
        # the walk that fetches its own pages takes the wide tile whatever
        # its length: what a step pays whatever its width is its rows'
        # flash rescale, and a page it does not fetch costs it nothing
        out = _own_pages_walk(
            qg, (k_pool,) + (() if v_pool is None else (v_pool,)),
            block_tables, context_lens, lengths, layer, window,
            scale=float(hd ** -0.5 if scale is None else scale), rows=rows,
            tq=tq, pages=wide, vd=value_width, interpret=_interpret())
    elif wide == pages or not _takes_wide(reach, wide, bs):
        out = walk(pages)
    else:
        # the grid of BlockSpec pages, two tile widths: which a call takes is
        # its own bound's to say (a short walk's one or two narrow steps cost
        # less than a wide one). Under a context that is a constant of the
        # program (a one-shot prefill's zeros) the most the bound can be is
        # one too, and a walk that cannot be long has no wide form to trace,
        # lower and compile
        out = jax.lax.cond(_takes_wide(bound, wide, bs), lambda: walk(wide),
                           lambda: walk(pages))
    return out.reshape(B, nkv, n_qt, g, tq, od).transpose(0, 2, 4, 1, 3, 5) \
        .reshape(B, n_qt * tq, nh, od)[:, :t]


def paged_prefill_attention_xla(q: jnp.ndarray, k_pool: jnp.ndarray,
                                v_pool: jnp.ndarray,
                                block_tables: jnp.ndarray,
                                context_lens: jnp.ndarray, lengths=None, *,
                                scale: float = None, window=None,
                                k_scale=None, v_scale=None,
                                layer=None,
                                value_width: int = None) -> jnp.ndarray:
    """The reference with identical semantics on every real row: gather the
    table's whole width, mask, soft-max in f32 (the right choice off-TPU,
    where the Pallas path runs interpreted; what every multi-token paged
    program computed before the kernel). ``lengths`` is not needed here:
    a padded row attends whatever lies under its mask and is discarded."""
    from ..attention import attention_xla
    from ..quantization import kv_dequantize_int8

    del lengths
    t = q.shape[1]
    layer = _layer_scalar(layer, k_pool, v_pool, k_scale, v_scale)
    kg = _gathered_view(k_pool, block_tables, layer)
    vg = _latent_values(kg, value_width) if v_pool is None \
        else _gathered_view(v_pool, block_tables, layer)
    if k_scale is not None:
        kg = kv_dequantize_int8(
            kg, _gathered_view(k_scale, block_tables, layer), q.dtype)
        vg = kv_dequantize_int8(
            vg, _gathered_view(v_scale, block_tables, layer), q.dtype)
    kv_pos = jnp.arange(kg.shape[1])[None, None, None, :]
    q_abs = (context_lens[:, None] + jnp.arange(t)[None, :])[:, None, :, None]
    mask = kv_pos <= q_abs
    if window is not None:
        mask = mask & (q_abs - kv_pos < _checked_window(window))
    return attention_xla(q, kg, vg, causal=False, mask=mask, scale=scale)


# --------------------------------------------------------------------------- #
# the write: a step's K/V rows into the pages they belong to, in place
# --------------------------------------------------------------------------- #
def _stored_rows(k, v, k_pool, k_scale):
    """This step's rows as the pools store them, pool by pool: ``(k, v)`` in
    the pool's dtype, or int8 codes and their fp32 per-(token, head, group)
    scales ``(qk, qv, sk, sv)`` - fill-time quantisation, in the same step
    as the write."""
    if v is None:                       # one pool: a latent row a token
        return (k.astype(k_pool.dtype),)
    if k_scale is None:
        return k.astype(k_pool.dtype), v.astype(k_pool.dtype)
    from ..quantization import kv_quantize_int8

    group = k.shape[-1] // k_scale.shape[-1]
    (qk, sk), (qv, sv) = kv_quantize_int8(k, group), kv_quantize_int8(v, group)
    return qk, qv, sk, sv


def _four(outs, v_pool) -> Tuple:
    """A write's results as ``(k_pool, v_pool, k_scale, v_scale)``."""
    outs = tuple(outs)
    if v_pool is None:
        outs = outs[:1] + (None,)
    return outs + (None,) * (4 - len(outs))


def _write_pages(t: int, bs: int) -> int:
    """Pages a sequence's ``t`` rows can touch from any offset in a page."""
    return 1 if t == 1 else -(-t // bs) + 1


def _kv_write_kernel(*refs, bs, n):
    """Overlay the step's rows on their page: slot ``o`` of the sequence's
    ``j``-th touched page holds its row ``j * bs + o - ctx % bs``, where that
    is one of its ``lengths`` real rows; every other slot keeps the pool's."""
    ctx_ref, len_ref = refs[1], refs[2]    # after the tables, before the layer
    rows, pages, outs = refs[4:4 + n], refs[4 + n:4 + 2 * n], refs[4 + 2 * n:]
    b, j = pl.program_id(0), pl.program_id(1)
    r = j * bs - ctx_ref[b] % bs \
        + jax.lax.broadcasted_iota(jnp.int32, (1, bs, 1), 1)
    mine = jnp.logical_and(r >= 0, r < len_ref[b])
    for row, page, out in zip(rows, pages, outs):
        out[...] = jnp.where(mine, row[...], page[...])


def paged_kv_write(k: jnp.ndarray, v: jnp.ndarray, k_pool: jnp.ndarray,
                   v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                   context_lens: jnp.ndarray, lengths: jnp.ndarray, *,
                   layer=None, k_scale=None, v_scale=None) -> Tuple:
    """Write a step's K/V ``[B, t, nkv, hd]`` into layer ``layer`` of the
    ``[L, num_blocks, nkv, bs, hd]`` pools IN PLACE: row ti of sequence b
    goes to position ``context_lens[b] + ti`` of its block table, for its
    first ``lengths[b]`` rows; a padded row and a zero-length dummy sequence
    write nothing. With ``k_scale``/``v_scale`` the pools hold int8 codes and
    the same call writes codes and scales. ``v`` and ``v_pool`` None: ONE
    pool, one row a token (a latent pool; ``k [B, t, 1, W]``). Returns
    ``(k_pool, v_pool, k_scale, v_scale)``, None what there is none of.

    One Mosaic call whose pools are aliased to its results, grid (sequence,
    page the step can touch): a grid step reads one page of every KV head
    through the block table, overlays the rows that fall on it and writes
    it back, so the call moves the pages a step touches (9 for a 256-token
    chunk, one a sequence for a decode) and nothing else of the pool. The
    step's rows reach it page-aligned - a gather over the step's own rows
    in XLA - so no slice in the kernel is unaligned. A grid step past its
    sequence's last touched page aims at block 0 with nothing to overlay,
    and rewrites what it read. No two grid steps of a call write one live
    page (copy-on-write keeps two sequences off one page; a sequence's
    pages are distinct table entries), so a page fetched ahead of an
    earlier step's write-back is never one that step writes; the grid runs
    in order."""
    B, t = k.shape[:2]
    layer = _layer_scalar(layer, k_pool, v_pool, k_scale, v_scale)
    nblocks, nkv, bs = k_pool.shape[-4:-1]
    max_blocks = block_tables.shape[1]
    n_pages = _write_pages(t, bs)
    pools = [p for p in (k_pool, v_pool, k_scale, v_scale) if p is not None]

    # slot o of page j <- row j * bs + o - ctx % bs (clipped: the kernel's
    # mask drops what is not the sequence's own)
    src = jnp.clip(jnp.arange(n_pages * bs)[None, :]
                   - (context_lens % bs)[:, None], 0, t - 1)

    def page_aligned(x):                # [B, t, nkv, w] -> [B, j, nkv, bs, w]
        x = jnp.take_along_axis(x, src[:, :, None, None], axis=1)
        return x.reshape(B, n_pages, bs, nkv, -1).swapaxes(2, 3)

    def row_map(b, j, *_):              # the rows' page j of sequence b
        return (b, j, 0, 0, 0)

    rows = [page_aligned(r) for r in _stored_rows(k, v, k_pool, k_scale)]

    def page_map(b, j, tables, ctx, lens, layer):
        pg = ctx[b] // bs + j
        live = jnp.logical_and(
            lens[b] > 0, pg <= jnp.minimum((ctx[b] + lens[b] - 1) // bs,
                                           max_blocks - 1))
        blk = jnp.where(live, tables[b, jnp.minimum(pg, max_blocks - 1)], 0)
        return (layer[0], jnp.clip(blk, 0, nblocks - 1), 0, 0, 0)

    def specs(index_map):
        return [_page_spec(p, nkv, index_map) for p in pools]

    outs = pl.pallas_call(
        functools.partial(_kv_write_kernel, bs=bs, n=len(pools)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, n_pages),
            in_specs=[_page_spec(r, nkv, row_map) for r in rows]
            + specs(page_map),
            out_specs=specs(page_map)),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # operands: 4 prefetched scalars, the rows, then the pools
        input_output_aliases={4 + len(pools) + i: i
                              for i in range(len(pools))},
        compiler_params=_dim_semantics("arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="paged_kv_write",
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      lengths.astype(jnp.int32), layer, *rows, *pools)
    return _four(outs, v_pool)


def paged_kv_write_xla(k: jnp.ndarray, v: jnp.ndarray, k_pool: jnp.ndarray,
                       v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                       context_lens: jnp.ndarray, lengths: jnp.ndarray, *,
                       layer=None, k_scale=None, v_scale=None) -> Tuple:
    """The reference with identical semantics: one scatter a pool, on the
    layer's index (off a TPU nothing has a layout to disagree with; on one
    the scatter's layout preference copies the whole pool - PERF.md
    Findings, PR 29). Rows past ``lengths`` are dropped."""
    t = k.shape[1]
    layer = _layer_scalar(layer, k_pool, v_pool, k_scale, v_scale)
    nblocks, bs = k_pool.shape[-4], k_pool.shape[-2]
    positions = context_lens[:, None] + jnp.arange(t)[None, :]
    blk = jnp.take_along_axis(
        block_tables, jnp.minimum(positions // bs, block_tables.shape[1] - 1),
        axis=1)
    blk = jnp.where(jnp.arange(t)[None, :] < lengths[:, None], blk, nblocks)
    # advanced indices (layer, blk, off) straddle the kv-head slice, so the
    # result dims land in front: [b, t, nkv, *] - exactly the rows' layout
    off = positions % bs
    outs = tuple(
        (p.at[blk, :, off] if p.ndim == 4 else p.at[layer[0], blk, :, off])
        .set(r, mode="drop")
        for p, r in zip((p for p in (k_pool, v_pool, k_scale, v_scale)
                         if p is not None),
                        _stored_rows(k, v, k_pool, k_scale)))
    return _four(outs, v_pool)


# --------------------------------------------------------------------------- #
# the multi-token walk that fetches its own pages
# --------------------------------------------------------------------------- #
def _prefill_kernel(*refs, bs, pages, scale, tq, max_blocks, nblocks,
                    has_window, vd, layered, selected=None):
    """``paged_prefill`` where the walk fetches its own pages
    (:func:`_fetches_pages`): grid step ``(b, h, qi)`` is the WHOLE walk of
    query tile ``qi`` of sequence ``b`` over KV head ``h`` - an in-kernel
    loop from the tile of its first row's window (tile 0 without one) to the
    tile of its last REAL row, the tiles :func:`_paged_kernel` computes on
    the grid of ``BlockSpec`` pages and at the same boundaries (tile ``j`` is
    table entries ``[j * pages, (j + 1) * pages)``), so the flash sums are
    the same sums in the same order. Each live page of a tile is one DMA a
    pool, ``pool[layer, tables[b, pg], h]`` into the page's ``bs`` rows of a
    double-buffered ``[2, pages * bs, hd]`` scratch - the matmul's layout,
    no join - and pages below the window's first or past the last real
    row's are not fetched: their rows keep an earlier tile's and are masked.
    The NEXT tile - this walk's, or the first of the next grid step's - is
    started before this one is waited for; which half of the scratch holds
    the tile in flight is carried in SMEM (the grid is sequential). A query
    tile with no real row (padding, a zero-length dummy) takes one tile of
    NO page: it fetches and computes nothing, and writes zeros. ``vd``: one
    pool, and a token's values are the first ``vd`` lanes of its key row.
    ``selected``: the walk of ``paged_sparse_prefill`` - one more mask,
    ``selected(scores, positions, tau, cut)``
    (``paged_sparse_attention.selected``; :func:`_selected_chunk_scores`):
    the rows' thresholds are two ``[tq, 1]`` blocks of the query tile after
    q, and a tile's ``[tq, pages * bs]`` slice of the call's index scores
    ``[B, query tiles * tq, S]`` one more DMA a tile that has a live page,
    after the pools' in the operands, the scratch and the semaphores."""
    n_pools, selects = 1 if vd else 2, selected is not None
    n_src = n_pools + int(selects)
    tables_ref, ctx_ref, len_ref, layer_ref = refs[:4]
    wnd_ref = refs[4] if has_window else None
    q_ref, refs = refs[4 + int(has_window)], refs[5 + int(has_window):]
    if selects:
        (tau_ref, cut_ref), refs = refs[:2], refs[2:]
    hbm, o_ref, bufs = refs[:n_src], refs[n_src], refs[1 + n_src:1 + 2 * n_src]
    sems, slot_ref, m_scr, l_scr, acc_scr = refs[1 + 2 * n_src:]
    b, h, qi = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kv = pages * bs
    add, mul, div = jax.lax.add, jax.lax.mul, jax.lax.div
    lo, hi = jax.lax.max, jax.lax.min

    def span(b, qi):
        """(first tile, tiles, first live page, last live page) of query
        tile ``qi`` of sequence ``b`` - :func:`_live_page`'s bounds; a tile
        with no real row: one tile, and a last page below its first."""
        ctx, n = ctx_ref[b], len_ref[b]
        q_lo = mul(qi, tq)
        last = add(add(ctx, hi(add(q_lo, tq), n)), -1)
        hi_pg = hi(lo(div(last, bs), 0), max_blocks - 1)
        lo_pg = hi(div(lo(add(add(ctx, q_lo), add(1, -wnd_ref[0])), 0), bs),
                   hi_pg) if has_window else 0
        j0 = div(lo_pg, pages)
        real = q_lo < n
        return (j0, jnp.where(real, add(add(div(hi_pg, pages), -j0), 1), 1),
                lo_pg, jnp.where(real, hi_pg, add(lo_pg, -1)))

    def tile_copies(b, h, qi, j, lo_pg, hi_pg, slot, fetch):
        """The page copies of tile ``j`` of (sequence, KV head) - of its
        pages in ``[lo_pg, hi_pg]`` alone - started (``fetch``) or waited
        for; a wait takes the copy's shape and semaphore, and no source.
        Under a selection the query tile's index scores over the tile's
        positions too: whole, whatever is live of them."""
        pg0 = mul(j, pages)
        first = lo(pg0, lo_pg)
        n = add(add(hi(add(pg0, pages - 1), hi_pg), 1), -first)

        def page(p, _, skip):
            blk = hi(lo(tables_ref[b, add(first, p)], 0), nblocks - 1) \
                if fetch else 0
            rows = pl.ds(pl.multiple_of(mul(add(skip, p), bs), bs), bs)
            for i, (pool, buf) in enumerate(zip(hbm[:n_pools], bufs)):
                src = pool.at[layer_ref[0], blk] if layered else pool.at[blk]
                copy = pltpu.make_async_copy(src.at[h], buf.at[slot, rows],
                                             sems.at[i, slot])
                copy.start() if fetch else copy.wait()
            return _

        # a whole tile's copies in straight-line code at static rows, the
        # last (or a window's first) tile's in a counted loop. Unrolled
        # where the loop is LOWERED, not in Python: every traced copy costs
        # a TPU host ~18 ms (PERF.md section 6, PR 54)
        @pl.when(n == pages)
        def _whole_tile():
            jax.lax.fori_loop(0, pages, functools.partial(page, skip=0), 0,
                              unroll=True)

        @pl.when(n < pages)
        def _part_tile():
            jax.lax.fori_loop(
                0, n, functools.partial(page, skip=add(first, -pg0)), 0)

        if selects:
            @pl.when(n > 0)
            def _index_scores():
                copy = pltpu.make_async_copy(
                    hbm[n_pools].at[
                        b, pl.ds(pl.multiple_of(mul(qi, tq), tq), tq),
                        pl.ds(pl.multiple_of(mul(j, kv), kv), kv)],
                    bufs[n_pools].at[slot], sems.at[n_pools, slot])
                copy.start() if fetch else copy.wait()

    j0, tiles, lo_pg, hi_pg = span(b, qi)

    @pl.when(jnp.logical_and(jnp.logical_and(b == 0, h == 0), qi == 0))
    def _prime():
        # rows of a tile that are not fetched are always masked, so what the
        # values' scratch holds there has to be finite: every earlier tile's
        # rows are, fresh VMEM need not be (the keys' too where a selection's
        # mask is ADDED to the scores)
        for buf in bufs[:n_pools] if selects else bufs[-1:]:
            buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        tile_copies(b, h, qi, j0, lo_pg, hi_pg, 0, True)

    _flash_init(0, m_scr, l_scr, acc_scr)
    ctx, n = ctx_ref[b], len_ref[b]
    q_lo = mul(qi, tq)
    n_h, n_qt = pl.num_programs(1), pl.num_programs(2)

    def tile(i, _):
        slot = slot_ref[0]
        j = add(j0, i)
        more = i + 1 < tiles
        # the grid step after this one: query tiles innermost, then KV heads
        next_q = jnp.logical_not(more)
        next_h = jnp.logical_and(next_q, qi + 1 == n_qt)
        next_b = jnp.logical_and(next_h, h + 1 == n_h)
        qi_n = jnp.where(next_q, jnp.where(next_h, 0, qi + 1), qi)
        h_n = jnp.where(next_h, jnp.where(next_b, 0, h + 1), h)
        b_n = jnp.where(next_b, b + 1, b)

        @pl.when(b_n < pl.num_programs(0))
        def _fetch_next():
            j0_n, _, lo_n, hi_n = span(b_n, qi_n)
            tile_copies(b_n, h_n, qi_n, jnp.where(more, add(j, 1), j0_n),
                        lo_n, hi_n, 1 - slot, True)

        tile_copies(b, h, qi, j, lo_pg, hi_pg, slot, False)

        @pl.when(hi_pg >= lo_pg)
        def _compute():
            k = bufs[0][slot]                           # [kv, hd]
            v = bufs[1][slot] if vd is None else k[..., :vd]
            if selects:
                s = _selected_chunk_scores(
                    q_ref[...], k, bufs[n_pools][slot], tau_ref[...],
                    cut_ref[...], selected, j, kv, ctx, n, q_lo, tq, scale)
            else:
                s = _chunk_scores(q_ref[...], k, j, kv, ctx, n, q_lo, tq,
                                  wnd_ref, scale)
            _flash_update(s, v, m_scr, l_scr, acc_scr)

        slot_ref[0] = 1 - slot
        return _

    jax.lax.fori_loop(0, tiles, tile, 0)
    _flash_finish(True, o_ref, l_scr, acc_scr)


# in order: the tile in flight belongs to the NEXT grid step
_OWN_PAGES_GRID = _dim_semantics("arbitrary", "arbitrary", "arbitrary")


def _own_pages_parts(qg, pools, block_tables, context_lens, lengths, layer,
                     window, *, scale, rows, tq, pages, vd, selected=None,
                     selection=()):
    """The kernel, grid, result and arguments of one multi-token walk that
    fetches its own pages (:func:`_prefill_kernel`) over layer ``layer`` of
    ``pools`` (K and V, or one latent pool with ``vd``), which reach it where
    they lie. ``qg`` ``[B, nkv, query tiles * rows, hd]`` as
    :func:`_table_walk` takes it. ``selected`` with ``selection``: one more
    mask (``selected(scores, positions, tau, cut)``) and its ``(idx, tau,
    cut)`` - the call's index scores ``[B, query tiles * tq, S]`` (``S``
    whole KV tiles; left in HBM like the pools) and each row's threshold
    ``[B, query tiles * tq, 1]``."""
    B, nkv, _, hd = qg.shape
    nblocks, bs = pools[0].shape[-4], pools[0].shape[-2]
    od = vd or hd                       # the output's (values') width
    scores, taus = tuple(selection[:1]), tuple(selection[1:])
    hbm = tuple(pools) + scores
    kernel = functools.partial(
        _prefill_kernel, bs=bs, pages=pages, scale=scale, tq=tq,
        max_blocks=block_tables.shape[1], nblocks=nblocks,
        has_window=window is not None, vd=vd, layered=pools[0].ndim == 5,
        selected=selected)

    def qmap(b, h, qi, *_):
        return (b, h, qi, 0)

    tiles = [(pages * bs, hd)] * len(pools) + [(tq, pages * bs)] * len(scores)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4 + int(window is not None),
        grid=(B, nkv, qg.shape[2] // rows),
        in_specs=[pl.BlockSpec((None, None, rows, hd), qmap)]
        + [pl.BlockSpec((None, tq, 1), lambda b, h, qi, *_: (b, qi, 0))]
        * len(taus) + [pl.BlockSpec(memory_space=pl.ANY)] * len(hbm),
        out_specs=pl.BlockSpec((None, None, rows, od), qmap),
        scratch_shapes=[pltpu.VMEM((2,) + tile, p.dtype)
                        for tile, p in zip(tiles, hbm)] + [
            pltpu.SemaphoreType.DMA((len(hbm), 2)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, od), jnp.float32),
        ],
    )
    args = [block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
            lengths.astype(jnp.int32), layer] \
        + ([] if window is None else [window.reshape(1)]) + [qg, *taus, *hbm]
    return (kernel, grid_spec,
            jax.ShapeDtypeStruct(qg.shape[:-1] + (od,), qg.dtype), args)


# jitted: one trace a process and a shape, whatever holds the call (every
# layer body of every program of that shape) - set-up time otherwise
@functools.partial(jax.jit, static_argnames=("scale", "rows", "tq", "pages",
                                             "vd", "interpret"))
def _own_pages_walk(qg, pools, block_tables, context_lens, lengths, layer,
                    window, *, scale, rows, tq, pages, vd, interpret):
    """One ``paged_prefill`` call whose walk fetches its own pages
    (:func:`_own_pages_parts`)."""
    kernel, grid_spec, out_shape, args = _own_pages_parts(
        qg, pools, block_tables, context_lens, lengths, layer, window,
        scale=scale, rows=rows, tq=tq, pages=pages, vd=vd)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=_OWN_PAGES_GRID,
        interpret=interpret,
        name="paged_prefill",
    )(*args)


# speculative verification is the same computation at t = 1 + draft tokens:
# the op name the verify programs were written against resolves to the kernel
paged_spec_verify_attention = paged_prefill_attention
paged_spec_verify_attention_xla = paged_prefill_attention_xla


from ..registry import register  # noqa: E402

register("paged_decode_attention", backend="pallas")(paged_decode_attention)
register("paged_decode_attention", backend="xla")(paged_decode_attention_xla)
for _name in ("paged_prefill_attention", "paged_spec_verify_attention"):
    register(_name, backend="pallas")(paged_prefill_attention)
    register(_name, backend="xla")(paged_prefill_attention_xla)
register("paged_kv_write", backend="pallas")(paged_kv_write)
register("paged_kv_write", backend="xla")(paged_kv_write_xla)
