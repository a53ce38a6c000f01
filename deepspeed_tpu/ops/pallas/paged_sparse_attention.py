"""Learned token selection over the paged cache: the kernels of a sparse
attention whose INDEXER chooses, per query token, the ``topk`` cached tokens
attention may read (DeepSeek-Sparse-Attention's indexer on grouped-query
attention; ``models/mixtral.py`` ``SparseAttention``).

Per layer and query token ``t`` (``s <= t`` a cached token):

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])      the index score
    S_t     = the topk tokens of largest I[t, s]; ties: lower s first
    attention reads S_t alone

Five calls, each on the layer's index into ``[L, ...]`` pools, like
``ops/pallas/paged_attention.py``'s three:

``paged_index_write``   the step's index keys into the THIRD pool, in place.
``paged_index_scores``  ``I`` over a block table -> ``[B, rows, S]`` float32.
                        One token a sequence (the decode rows) takes
                        ``paged_decode``'s walk over the index pool
                        (``_index_walk_kernel``): each sequence to its own
                        end and an idle slot nowhere, page DMAs issued from
                        the block table, a tile and a sequence ahead. A
                        packed page is one whole ``[bs / pack, 128]`` tile
                        of the pool, so one DMA moves it, and behind a
                        ``BlockSpec`` each 4 KB page cost a grid step more
                        than its bytes did. A chunk's rows keep the grid of
                        table-indexed ``BlockSpec`` pages, as long as the
                        longest slot - as a decode row does where Mosaic
                        cannot slice the page (``_fetches_index_pages``).
``paged_sparse_select`` the exact threshold of each row: its ``topk``-th
                        largest score and, among the scores equal to it, the
                        last position taken. A bisection on the score's bit
                        pattern, at most 32 counting passes over the row in
                        VMEM (the tie rule: one more a position bit); no sort.
``paged_sparse_decode`` / ``paged_sparse_prefill``  the flash walks of
                        ``paged_attention`` with one more mask: a token
                        under its row's threshold is dropped. The decode
                        rows take ``paged_decode``'s walk, which fetches its
                        own pages (``_decode_kernel``: each sequence to its
                        own end, page DMAs issued from the block table, a
                        tile and a sequence ahead) and, a tile, one more DMA:
                        its slice of the row's index scores. The chunk's
                        rows take ``paged_prefill``'s walk, which fetches
                        its own pages too (``_prefill_kernel``: each
                        (sequence, KV head, query tile) to its last real
                        row, a tile and a grid step ahead) and, a tile, one
                        more DMA: the query tile's ``[tq, KV]`` slice of the
                        scores - the same tiles, the same flash sums as the
                        grid of table-indexed ``BlockSpec`` pages
                        (``_sparse_walk``) that both keep where Mosaic
                        cannot slice a page out of the pool (heads narrower
                        than a lane tile; the decode call at one token a
                        sequence).
                        (Both still visit the whole live context - the mask
                        form; a gather over the selected pages is what the
                        ``sparse_attn_roofline`` leaves room for.)

The index pool. One key head of ``d`` = 64 values a token is half a lane
tile, and a ``[.., bs, 64]`` pool has no row-major device layout
(``models/_paged.lane_pack_of``). So a page holds ``pack = 128 / d`` tokens a
row: ``[L, num_blocks, 1, bs / pack, pack * d]``, token ``o`` of a block at
row ``o % (bs / pack)``, lanes ``(o // (bs / pack)) * d ...``: the block's
first half beside its second. The scores kernel takes a page apart with lane
masks (rows of a page, zero outside one token's lanes, stacked token-major)
and contracts over all 128 lanes against the query repeated ``pack`` times,
so no lane is ever shifted; the same bytes as ``[bs, d]``, in whole tiles.

Scores past a row's own position are never read: the selection masks by
position, so the scores call skips dead tiles and leaves them unwritten (a
slot with no row: all of them).
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
from .paged_attention import (_MAX_PAGES, _OWN_PAGES_GRID, _PAGE_WALK_GRID,
                              _WALK_GRID, _contract, _decode_tiles,
                              _fetches_pages, _flash_finish, _flash_init,
                              _flash_update, _group_rows, _kv_tile,
                              _layer_scalar, _own_pages_parts, _page_spec,
                              _page_walk,
                              _prefill_tiles, _selected_chunk_scores,
                              _walk_vmem, _write_pages)

KEY_MIN = -2 ** 31          # the sort key of a position that is not the row's
_SELECT_ROWS = 8            # rows of one selection tile: a sublane tile
_SELECT_CHUNK = 2048        # score lanes of one counting step
_SCORE_ROWS = 64            # query tokens of one scores tile, at most
_INDEX_PAGES = 64           # index-pool pages of one tile of a decode row's
                            # scores (a page of one 64-wide key head is
                            # 4 KB, a tile 2 048 tokens): the walk that
                            # fetches its own pages, or the grid's step
                            # where it cannot. The walk is bound by its
                            # pages, ~25 ns each, and a tile's own cost is
                            # small: 8 slots of which 4 decode over 6-31 k
                            # tokens take 71-86 us a call (the grid of
                            # BlockSpec pages: 598-706), and 32 / 128 pages
                            # read 3 % over / 2 % under 64
                            # (scripts/sparse_kernel_bench.py --only index
                            # on the chip, PR 54); a sequence's last tile
                            # is computed whole, so the widest is not
                            # taken. A call of more rows takes _MAX_PAGES:
                            # its [heads x rows, KV] scores fill the VMEM
                            # sooner
_PREFILL_PAGES = 32         # K / V pages of one step of the multi-token
                            # masked walk: 1024 tokens. A step's fixed work
                            # (the flash rescale of a [rows, 128]
                            # accumulator, 64 page DMAs' issue) is what a
                            # 256-token step mostly was: the 512-row walk
                            # alone took 3.8 / 2.6 / 2.0 ms at 8 / 16 / 32
                            # pages (scripts/sparse_kernel_bench.py on the
                            # chip, PR 38). The decode rows' tile is
                            # ``paged_attention._decode_tiles``'.
_PREFILL_VMEM = 14 << 20    # what a step of that walk may keep of Mosaic's
                            # 16 MiB of VMEM: the plain walk's tiles and the
                            # scores' (12.5 MiB at 1 024 rows x 1 024 keys)


def index_pack(d: int, block_size: int) -> int:
    """Tokens that share one row of an index-pool page: as many as fill a
    128-lane tile and divide the block."""
    lanes = max(1, 128 // d)
    return max(p for p in range(1, lanes + 1) if block_size % p == 0)


def index_pool_shape(num_layers: int, num_blocks: int, block_size: int,
                     d: int) -> Tuple[int, ...]:
    pack = index_pack(d, block_size)
    return (num_layers, num_blocks, 1, block_size // pack, pack * d)


def _pack_pages(x, pack: int):
    """``[.., bs, d]`` index keys as pool pages ``[.., bs / pack, pack * d]``."""
    bs, d = x.shape[-2:]
    x = x.reshape(x.shape[:-2] + (pack, bs // pack, d))
    return jnp.swapaxes(x, -3, -2).reshape(x.shape[:-3]
                                           + (bs // pack, pack * d))


def _unpack_pages(x, pack: int):
    """The inverse of :func:`_pack_pages`."""
    rows, width = x.shape[-2:]
    x = x.reshape(x.shape[:-2] + (rows, pack, width // pack))
    return jnp.swapaxes(x, -3, -2).reshape(x.shape[:-3]
                                           + (rows * pack, width // pack))


def _gathered_keys(pool, block_tables, layer, d: int):
    """Dense ``[B, S, d]`` view of the index keys the tables reference (the
    XLA references' read; the kernels never build it)."""
    pack = pool.shape[-1] // d
    g = pool[layer[0], block_tables][:, :, 0]         # [b, mb, rows, pack*d]
    g = _unpack_pages(g, pack)                        # [b, mb, bs, d]
    return g.reshape(g.shape[0], -1, d)


def score_key(s):
    """A float32 score's sort key: an int32 that orders as the float does."""
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def index_scores_dense(q_idx, k_idx, w_idx):
    """``I [.., t, S]`` from index queries ``[.., t, H, d]``, index keys
    ``[.., S, d]`` and head weights ``[.., t, H]``, all keys at once: the
    gathered form (the XLA references, ``models/mixtral.py``'s dense paths).
    -0.0 and 0.0 are one score: one sort key."""
    s = jnp.einsum("...thd,...sd->...ths", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    s = jnp.sum(jnp.maximum(s, 0.0)
                * w_idx.astype(jnp.float32)[..., None], axis=-2)
    return jnp.where(s == 0.0, 0.0, s)


def selected(idx, pos, tau, cut):
    """Whether the token at ``pos`` with index score ``idx`` is one of its
    row's ``topk``, from the row's threshold (``paged_sparse_select``)."""
    key = score_key(idx)
    return jnp.logical_or(key > tau,
                          jnp.logical_and(key == tau, pos <= cut))


# --------------------------------------------------------------------------- #
# the index keys' write
# --------------------------------------------------------------------------- #
def _index_write_kernel(tables, ctx_ref, len_ref, layer, row, page, out, *,
                        bs, d):
    """``paged_attention._kv_write_kernel`` on a packed page: slot ``o`` of
    the page lies at row ``o % rows``, lane segment ``o // rows``."""
    del tables, layer
    b, j = pl.program_id(0), pl.program_id(1)
    rows, width = page.shape[-2:]
    sub = jax.lax.broadcasted_iota(jnp.int32, (1, rows, width), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, rows, width), 2)
    seg = sum((lane >= p * d).astype(jnp.int32)
              for p in range(1, width // d)) if width > d else 0
    r = j * bs - ctx_ref[b] % bs + sub + rows * seg
    mine = jnp.logical_and(r >= 0, r < len_ref[b])
    out[...] = jnp.where(mine, row[...], page[...])


def paged_index_write(k_idx, pool, block_tables, context_lens, lengths, *,
                      layer):
    """Write a step's index keys ``[B, t, d]`` into layer ``layer`` of the
    index pool IN PLACE, as ``paged_kv_write`` writes K and V: row ti of
    sequence b to position ``context_lens[b] + ti`` of its block table, for
    its first ``lengths[b]`` rows. Returns the pool."""
    B, t, d = k_idx.shape
    layer = _layer_scalar(layer, pool)
    nblocks, rows = pool.shape[1], pool.shape[-2]
    pack = pool.shape[-1] // d
    bs = rows * pack
    max_blocks = block_tables.shape[1]
    n_pages = _write_pages(t, bs)
    src = jnp.clip(jnp.arange(n_pages * bs)[None, :]
                   - (context_lens % bs)[:, None], 0, t - 1)
    x = jnp.take_along_axis(k_idx.astype(pool.dtype), src[:, :, None], axis=1)
    x = _pack_pages(x.reshape(B, n_pages, bs, d), pack)[:, :, None]

    def row_map(b, j, *_):
        return (b, j, 0, 0, 0)

    def page_map(b, j, tables, ctx, lens, layer):
        pg = ctx[b] // bs + j
        live = jnp.logical_and(
            lens[b] > 0, pg <= jnp.minimum((ctx[b] + lens[b] - 1) // bs,
                                           max_blocks - 1))
        blk = jnp.where(live, tables[b, jnp.minimum(pg, max_blocks - 1)], 0)
        return (layer[0], jnp.clip(blk, 0, nblocks - 1), 0, 0, 0)

    return pl.pallas_call(
        functools.partial(_index_write_kernel, bs=bs, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(B, n_pages),
            in_specs=[_page_spec(x, 1, row_map),
                      _page_spec(pool, 1, page_map)],
            out_specs=_page_spec(pool, 1, page_map)),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={5: 0},    # 4 prefetched scalars, rows, pool
        compiler_params=_dim_semantics("arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="paged_index_write",
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      lengths.astype(jnp.int32), layer, x, pool)


def paged_index_write_xla(k_idx, pool, block_tables, context_lens, lengths,
                          *, layer):
    """The reference with identical semantics: one scatter on the layer's
    index, into the packed page's rows and lanes."""
    B, t, d = k_idx.shape
    layer = _layer_scalar(layer, pool)
    nblocks, rows = pool.shape[1], pool.shape[-2]
    pack = pool.shape[-1] // d
    bs = rows * pack
    positions = context_lens[:, None] + jnp.arange(t)[None, :]
    blk = jnp.take_along_axis(
        block_tables, jnp.minimum(positions // bs, block_tables.shape[1] - 1),
        axis=1)
    blk = jnp.where(jnp.arange(t)[None, :] < lengths[:, None], blk, nblocks)
    off = positions % bs
    view = pool.reshape(pool.shape[:4] + (pack, d))
    view = view.at[layer[0], blk, 0, off % rows, off // rows].set(
        k_idx.astype(pool.dtype), mode="drop")
    return view.reshape(pool.shape)


# --------------------------------------------------------------------------- #
# the index scores
# --------------------------------------------------------------------------- #
def _pow2_pages(most: int, max_blocks: int) -> int:
    """Pages of one KV tile: a power of two, so that every call's tiles
    divide the scores' padded width."""
    return 1 << (max(1, min(most, max_blocks)).bit_length() - 1)


def _score_tiles(rows: int) -> int:
    """Query tokens of one scores tile: ``rows`` (the call's padded rows)
    halved until a tile's ``[heads * tq, KV]`` float32 scores are ~2 MB."""
    tq = rows
    while tq > _SCORE_ROWS and tq % 16 == 0:
        tq //= 2
    return tq


def _index_tile(page_refs, d: int):
    """One KV tile of index keys from its packed pages (refs, or the pages'
    values), token-major
    ``[pages * bs, 128]``: a token's row is its page row with every other
    token's lanes zeroed."""
    pages = [ref[...] for ref in page_refs]
    shape, width = pages[0].shape, pages[0].shape[-1]
    # every page has one shape: the tokens' lane masks are made once (a
    # traced equation is host time in every program that holds the kernel)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mine = [jnp.logical_and(lane >= p * d, lane < (p + 1) * d)
            for p in range(width // d)]
    zeros = jnp.zeros(shape, pages[0].dtype)
    out = [jnp.where(m, page, zeros) for page in pages for m in mine]
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=0)


def _tile_scores(q_ref, w_ref, page_refs, d: int, tq: int):
    """The scores ``[tq, KV]`` of one KV tile's pages for ``tq`` query
    tokens, their packed index queries ``[heads * tq, 128]`` head-major."""
    heads = q_ref.shape[0] // tq
    k = _index_tile(page_refs, d)                       # [kv, 128]
    s = _mxu_dot(q_ref[...], k, _contract(2, -1),
                 preferred_element_type=jnp.float32)         # [heads*tq, kv]
    s = jnp.maximum(s, 0.0) * w_ref[...]
    if tq == 1:     # one query token: the heads are the tile's rows
        acc = jnp.sum(s, axis=0, keepdims=True)
    else:
        acc = s[:tq]
        for h in range(1, heads):
            acc = acc + s[h * tq:(h + 1) * tq]
    # -0.0 and 0.0 are one score: one sort key
    return jnp.where(acc == 0.0, 0.0, acc)


def _index_scores_kernel(*refs, bs, d, pages, tq):
    ctx_ref, len_ref = refs[1], refs[2]
    q_ref, w_ref = refs[4], refs[5]
    k_refs, o_ref = refs[6:6 + pages], refs[6 + pages]
    b, qi, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    kv = pages * bs
    ctx, n = ctx_ref[b], len_ref[b]
    q_lo = qi * tq
    live = jnp.logical_and(q_lo < n,
                           j * kv < ctx + jnp.minimum(q_lo + tq, n))

    @pl.when(live)
    def _compute():
        o_ref[0:tq, :] = _tile_scores(q_ref, w_ref, k_refs, d, tq)


def _index_walk_kernel(tables_ref, ctx_ref, len_ref, layer_ref, q_ref, w_ref,
                       pool, o_ref, buf, sems, slot_ref, *, d, pages,
                       max_blocks, nblocks):
    """The scores of one query token a sequence, by the walk of
    ``paged_attention._decode_kernel`` over the packed index pool: grid step
    ``b`` walks sequence ``b`` from page 0 to the page of its own position,
    a tile of ``pages`` pages an iteration of an in-kernel loop, and fetches
    those pages itself - one DMA a page (``[bs / pack, 128]``: one whole
    tile of the pool) from the block table in SMEM into the page's rows of a
    double-buffered ``[2, pages * bs / pack, 128]`` scratch. The NEXT tile -
    this sequence's, or the first of the next sequence that decodes - is
    started before this one is waited for; which half holds the tile in
    flight is carried in SMEM from grid step to grid step (the grid is
    sequential). A sequence with no row (``lengths[b] == 0``) fetches and
    computes nothing, and its table row is never read."""
    b, n_seq = pl.program_id(0), len_ref.shape[0]
    prow = buf.shape[1] // pages
    bs = prow * (buf.shape[2] // d)
    kv = pages * bs
    add, mul, div = jax.lax.add, jax.lax.mul, jax.lax.div
    lo, hi = jax.lax.max, jax.lax.min

    def rows_of(p):
        return pl.ds(pl.multiple_of(mul(p, prow), prow), prow)

    def last_page(b):
        """The page of sequence ``b``'s own position."""
        return hi(div(ctx_ref[b], bs), max_blocks - 1)

    def tile_copies(b, pg0, slot, fetch):
        """The page copies of sequence ``b``'s tile that begins at table
        entry ``pg0`` - of its pages up to the sequence's last alone -
        started (``fetch``) or waited for; a wait takes the copy's shape
        and semaphore, and no source."""
        src, dst, sem = pool.at[layer_ref[0]], buf.at[slot], sems.at[slot]

        def page(p, _):
            blk = hi(lo(tables_ref[b, add(pg0, p)], 0), nblocks - 1) \
                if fetch else 0
            copy = pltpu.make_async_copy(src.at[blk, 0], dst.at[rows_of(p)],
                                         sem)
            copy.start() if fetch else copy.wait()
            return _

        n = hi(pages, add(add(last_page(b), 1), -pg0))

        # a whole tile's copies in straight-line code: the walk is bound by
        # its pages' issue and wait on the scalar core, and a loop with a
        # dynamic trip count costs each page half as much again. Unrolled
        # where the loop is LOWERED, not in Python: every traced copy costs
        # a TPU host ~18 ms (each static index of its indexers becomes a
        # device array), 64 pages x 3 sites x every program that holds one
        @pl.when(n == pages)
        def _whole_tile():
            jax.lax.fori_loop(0, pages, page, 0, unroll=True)

        @pl.when(n < pages)
        def _last_tile():
            jax.lax.fori_loop(0, n, page, 0)

    def decoding_after(b):
        """The first sequence after ``b`` that has a row; ``n_seq``: none."""
        def earlier(i, found):
            s = n_seq - 1 - i
            return jnp.where(jnp.logical_and(s > b, len_ref[s] > 0), s, found)
        return jax.lax.fori_loop(0, n_seq, earlier, n_seq)

    @pl.when(b == 0)
    def _prime():
        # a tile's rows past its sequence's last page are never fetched and
        # their scores never read, but they go through the matmul: what the
        # scratch holds there has to be finite, which every earlier tile's
        # rows are and fresh VMEM need not be
        buf[...] = jnp.zeros_like(buf)
        slot_ref[0] = 0
        first = decoding_after(-1)

        @pl.when(first < n_seq)
        def _fetch_first():
            tile_copies(first, 0, 0, True)

    @pl.when(len_ref[b] > 0)
    def _walk():
        n = add(div(last_page(b), pages), 1)
        b_after = decoding_after(b)

        def tile(j, _):
            slot = slot_ref[0]
            pg0 = mul(j, pages)
            more = j + 1 < n
            b_next = jnp.where(more, b, b_after)

            @pl.when(b_next < n_seq)
            def _fetch_next():
                tile_copies(b_next, jnp.where(more, add(pg0, pages), 0),
                            1 - slot, True)

            tile_copies(b, pg0, slot, False)
            keys = buf[slot]        # one load: a page's rows are a slice
            o_ref[0:1, pl.ds(pl.multiple_of(mul(pg0, bs), kv), kv)] = \
                _tile_scores(q_ref, w_ref,
                             [keys[p * prow:(p + 1) * prow]
                              for p in range(pages)], d, 1)
            slot_ref[0] = 1 - slot
            return _

        jax.lax.fori_loop(0, n, tile, 0)


def _fetches_index_pages(pool_shape) -> bool:
    """Whether a decode row's scores fetch their index pages themselves
    (``paged_attention._fetches_pages``' rule on the packed page): Mosaic
    slices a page out of the pool, and the page's rows out of the scratch,
    for a DMA only in whole ``(8, 128)`` tiles - an index head width that
    divides 128, blocks of eight pool rows or more. Any other pool keeps the
    grid of ``BlockSpec`` pages."""
    return pool_shape[-1] % 128 == 0 and pool_shape[-2] % 8 == 0


def _index_pages(t: int, max_blocks: int) -> int:
    """Pool pages of one KV tile of a scores call of ``t`` tokens a
    sequence."""
    return _pow2_pages(_INDEX_PAGES if t == 1 else _MAX_PAGES, max_blocks)


def index_tile_counts(context_lens, lengths, pool_shape, block_size: int,
                      max_blocks: int) -> Tuple[int, int]:
    """(live, taken) KV tiles of ONE ``paged_index_scores`` call of one
    token a sequence over slots at ``context_lens`` (host integers), of
    which those with ``lengths`` > 0 decode: the tiles that hold context a
    decoding slot scores, and the tiles the call takes - the same tiles
    where it fetches its own pages (:func:`_fetches_index_pages`), every
    slot as far as the longest where it is the grid of ``BlockSpec`` pages.
    What the serving engine puts on its ``decode_step`` span, as
    ``paged_attention.decode_tile_counts`` for the decode walk."""
    pages = _index_pages(1, max_blocks)
    kv, n_kv = pages * block_size, -(-max_blocks // pages)
    ctx, n = np.asarray(context_lens), np.asarray(lengths)
    live = int(np.minimum(ctx[n > 0] // kv + 1, n_kv).sum())
    if _fetches_index_pages(pool_shape):
        return live, live
    longest = min(max(-(-int((ctx + n).max()) // kv), 1), n_kv)
    return live, longest * ctx.size


def _packed_queries(q_idx, w_idx, pool, tq: int, n_qt: int):
    """A call's index queries and head weights as the kernels take them:
    ``[B, n_qt * H * tq, pack * d]`` (the query repeated over a page row's
    ``pack`` tokens) and ``[B, n_qt * H * tq, 1]`` float32, head-major
    inside each of the ``n_qt`` query tiles of ``tq`` tokens."""
    B, t, H, d = q_idx.shape

    def tiled(x):       # [B, t, H, w] -> [B, n_qt * H * tq, w]
        x = jnp.pad(x, ((0, 0), (0, n_qt * tq - t), (0, 0), (0, 0)))
        return x.reshape(B, n_qt, tq, H, -1).swapaxes(2, 3) \
            .reshape(B, n_qt * H * tq, -1)

    return (jnp.tile(tiled(q_idx).astype(pool.dtype),
                     (1, 1, pool.shape[-1] // d)),
            tiled(w_idx[..., None].astype(jnp.float32)))


def paged_index_scores(q_idx, w_idx, pool, block_tables, context_lens,
                       lengths, *, layer, rows: int = None):
    """Index scores of a step's rows over their block tables.

    ``q_idx [B, t, H, d]`` (roped), ``w_idx [B, t, H]``; the step's index
    keys must already be in the pool. Returns ``[B, rows, S]`` float32
    (``rows``: ``t`` padded to the attention call's tiles, ``S`` the table's
    width in tokens, padded to whole KV tiles), entry ``[b, ti, s]`` the
    score of cached token ``s`` for row ti - for ``s`` up to the row's own
    position ``context_lens[b] + ti`` and a real row; everything else is
    unspecified (dead tiles are not even written) and the selection never
    reads it. One token a sequence (a decode row) walks each sequence's own
    pages (:func:`_index_walk_kernel`) where Mosaic can slice a page out of
    the pool (:func:`_fetches_index_pages`); every other call is the grid of
    table-indexed ``BlockSpec`` pages."""
    t = q_idx.shape[1]
    return _index_scores(
        _index_walk if t == 1 and _fetches_index_pages(pool.shape)
        else _index_grid, q_idx, w_idx, pool, block_tables, context_lens,
        lengths, layer=layer, rows=rows or -(-t // 8) * 8)


def _index_scores(walk, q_idx, w_idx, pool, block_tables, context_lens,
                  lengths, *, layer, rows: int):
    """The scores call of one ``walk`` (:func:`_index_walk`,
    :func:`_index_grid`)."""
    kernel, grid_spec, operands, width, order = walk(
        q_idx, w_idx, pool, block_tables.shape[1],
        jnp.max(context_lens + lengths), rows)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((q_idx.shape[0], rows, width),
                                       jnp.float32),
        compiler_params=order,
        interpret=_interpret(),
        name="paged_index_scores",
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      lengths.astype(jnp.int32), _layer_scalar(layer, pool), *operands)


def _index_walk(q_idx, w_idx, pool, max_blocks: int, bound, rows: int):
    """One token a sequence, each sequence's own pages
    (:func:`_index_walk_kernel`) as ``(kernel, grid, operands, S, grid
    order)``: grid ``(B,)``, sequential - the tile in flight belongs to the
    NEXT grid step; the pool stays in HBM and a sequence's ``[rows, S]``
    scores are one output block, row 0 the query token's."""
    del bound           # each sequence walks to its own end
    B, _, H, d = q_idx.shape
    nblocks, prow, width = pool.shape[1], pool.shape[-2], pool.shape[-1]
    pages = _index_pages(1, max_blocks)
    S = -(-max_blocks // pages) * pages * prow * (width // d)

    def row_map(b, *_):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(B,),
        in_specs=[pl.BlockSpec((None, H, width), row_map),
                  pl.BlockSpec((None, H, 1), row_map),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, rows, S), row_map),
        scratch_shapes=[pltpu.VMEM((2, pages * prow, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SMEM((1,), jnp.int32)])
    kernel = functools.partial(_index_walk_kernel, d=d, pages=pages,
                               max_blocks=max_blocks, nblocks=nblocks)
    return (kernel, grid_spec,
            _packed_queries(q_idx, w_idx, pool, 1, 1) + (pool,), S,
            _dim_semantics("arbitrary"))


def _index_grid(q_idx, w_idx, pool, max_blocks: int, bound, rows: int):
    """The grid ``(B, query tiles, KV tiles)`` of table-indexed ``BlockSpec``
    pages, its last dimension DYNAMIC - the tiles up to ``bound``, the
    longest slot's last real row - as ``(kernel, grid, operands, S, grid
    order)``: the chunk's rows, and a decode row's where the walk cannot
    slice its pages."""
    B, t, H, d = q_idx.shape
    nblocks, prow = pool.shape[1], pool.shape[-2]
    pack = pool.shape[-1] // d
    bs = prow * pack
    pages = _index_pages(t, max_blocks)
    kv = pages * bs
    n_kv = -(-max_blocks // pages)
    # one query token a sequence (a decode row): its index heads are the
    # tile's rows, and the result is row 0 of an 8-row block
    tq = 1 if t == 1 else _score_tiles(rows)
    n_qt = 1 if t == 1 else rows // tq

    def qmap(b, qi, j, *_):
        return (b, qi, 0)

    def page_map(p):
        def kvmap(b, qi, j, tables, ctx, lens, layer):
            last = ctx[b] + jnp.minimum(qi * tq + tq, lens[b]) - 1
            hi_pg = jnp.clip(last // bs, 0, max_blocks - 1)
            j_eff = jnp.minimum(j, hi_pg // pages)
            pg = jnp.minimum(j_eff * pages + p, hi_pg)
            return (layer[0], jnp.clip(tables[b, pg], 0, nblocks - 1), 0,
                    0, 0)
        return kvmap

    n_live = jnp.clip(-(-bound // kv), 1, n_kv)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4, grid=(B, n_qt, n_live.astype(jnp.int32)),
        in_specs=[pl.BlockSpec((None, H * tq, pack * d), qmap),
                  pl.BlockSpec((None, H * tq, 1), qmap)]
        + [_page_spec(pool, None, page_map(p)) for p in range(pages)],
        out_specs=pl.BlockSpec((None, rows // n_qt, kv),
                               lambda b, qi, j, *_: (b, qi, j)))
    kernel = functools.partial(_index_scores_kernel, bs=bs, d=d, pages=pages,
                               tq=tq)
    return (kernel, grid_spec,
            _packed_queries(q_idx, w_idx, pool, tq, n_qt)
            + (pool,) * pages, n_kv * kv,
            _dim_semantics("parallel", "parallel", "arbitrary"))


def paged_index_scores_xla(q_idx, w_idx, pool, block_tables, context_lens,
                           lengths, *, layer, rows: int = None):
    """The reference with identical semantics on every entry the selection
    reads: gather the table's whole width, one einsum a head."""
    del context_lens, lengths
    B, t, H, d = q_idx.shape
    layer = _layer_scalar(layer, pool)
    keys = _gathered_keys(pool, block_tables, layer, d)         # [B, S, d]
    s = index_scores_dense(q_idx.astype(pool.dtype), keys, w_idx)
    rows = rows or t
    return jnp.pad(s, ((0, 0), (0, rows - t), (0, 0)))


# --------------------------------------------------------------------------- #
# the selection: each row's exact threshold
# --------------------------------------------------------------------------- #
def _select_kernel(hi_ref, s_ref, q_ref, tau_ref, cut_ref, key_scr, *,
                   topk, chunk, nbits):
    """Rows ``[tr, S]`` of scores -> each row's ``tau`` and ``cut``:
    :func:`selected` with them takes the ``topk`` positions ``<= q_abs`` of
    largest score. The row's sort keys stay in VMEM and every pass counts
    over the live chunks alone, into a chunk-wide accumulator (lanes add
    independently; one cross-lane sum a pass).

    The threshold is built bit by bit from the top, in the order-preserving
    unsigned image of the key (``key ^ KEY_MIN``): a bit is kept where
    ``topk`` keys still reach the candidate. A row is DONE as soon as
    exactly ``topk`` keys reach its threshold - the set is then known,
    whatever the lower bits - or fewer do (a context under ``topk``: its
    threshold stays at the bottom and takes everything), and the passes end
    when every row of the tile is. Only a row that runs out of bits with
    more than ``topk`` keys at its threshold has equal scores there: of
    those the first by position are taken, and the position bisection runs
    for that tile alone."""
    tr = s_ref.shape[0]
    n_ch = -(-hi_ref[pl.program_id(0)] // chunk)
    q_abs = q_ref[...]                                       # [tr, 1]
    key_min = jnp.int32(KEY_MIN)

    def lanes(c):
        off = pl.multiple_of(c * chunk, chunk)
        return off, pl.ds(off, chunk)

    def positions(off):
        return off + jax.lax.broadcasted_iota(jnp.int32, (tr, chunk), 1)

    def fill(c, _):
        off, at = lanes(c)
        key_scr[:, at] = jnp.where(positions(off) <= q_abs,
                                   score_key(s_ref[:, at]), key_min)
        return 0

    jax.lax.fori_loop(0, n_ch, fill, 0)

    def count(pred, with_pos=False):
        """Per row, the positions where ``pred`` holds (a float32 count is
        exact far beyond any table's width)."""
        def body(c, acc):
            off, at = lanes(c)
            key = key_scr[:, at]
            hit = pred(key, positions(off)) if with_pos else pred(key)
            return acc + jnp.where(hit, 1.0, 0.0)
        acc = jax.lax.fori_loop(0, n_ch, body,
                                jnp.zeros((tr, chunk), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def undecided(reach):
        return jnp.max(reach) > topk

    def refine(state):
        bit, t_u, reach = state
        cand_u = t_u | jax.lax.shift_left(jnp.int32(1), bit)
        cand = cand_u ^ key_min
        r = count(lambda key: key >= cand)
        keep = r >= topk
        return (bit - 1, jnp.where(keep, cand_u, t_u),
                jnp.where(keep, r, reach))

    everything = (n_ch * chunk).astype(jnp.float32)     # keys >= KEY_MIN
    _, t_u, reach = jax.lax.while_loop(
        lambda st: jnp.logical_and(st[0] >= 0, undecided(st[2])), refine,
        (jnp.int32(31), jnp.zeros((tr, 1), jnp.int32),
         jnp.full((tr, 1), everything, jnp.float32)))
    tau = t_u ^ key_min
    tau_ref[...] = tau
    cut_ref[...] = jnp.full((tr, 1), 2 ** 31 - 1, jnp.int32)

    @pl.when(undecided(reach))
    def _equal_scores():
        # of the keys equal to tau the first ``need`` by position are taken:
        # the largest P with fewer than ``need`` of them before it is the
        # last one taken (a decided row needs them all: P runs to the top)
        need = topk - count(lambda key: key > tau)
        cut = jnp.zeros((tr, 1), jnp.int32)
        for bit in range(nbits - 1, -1, -1):
            cand = cut | jnp.int32(1 << bit)
            before = count(lambda key, pos, cand=cand: jnp.logical_and(
                key == tau, pos < cand), with_pos=True)
            cut = jnp.where(before < need, cand, cut)
        cut_ref[...] = jnp.where(reach > topk, cut, 2 ** 31 - 1)


def paged_sparse_select(scores, q_abs, *, topk: int):
    """``scores [N, S]`` float32 and each row's own position ``q_abs [N]``
    (-1: a padded row, whose result is unspecified) -> ``(tau, cut)`` ``[N]``
    int32: :func:`selected` with them is true for exactly the
    ``min(q_abs + 1, topk)`` positions ``<= q_abs`` of largest score, ties to
    the lower position."""
    N, S = scores.shape
    tr = _SELECT_ROWS
    n_pad = -(-N // tr) * tr
    width = -(-S // 128) * 128
    chunk = max(c for c in (128, 256, 512, 1024, _SELECT_CHUNK)
                if width % c == 0)
    scores = jnp.pad(scores, ((0, 0), (0, width - S)))
    S = width
    scores = jnp.pad(scores, ((0, n_pad - N), (0, 0)))
    q_abs = jnp.pad(q_abs.astype(jnp.int32), (0, n_pad - N),
                    constant_values=-1)
    # positions a tile's rows can reach: the counting loops' bound
    hi = jnp.clip(jnp.max(q_abs.reshape(-1, tr), axis=1) + 1, 0, S)
    tau, cut = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, chunk=chunk,
                          nbits=max(1, (S - 1).bit_length())),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(n_pad // tr,),
            in_specs=[pl.BlockSpec((tr, S), lambda i, *_: (i, 0)),
                      pl.BlockSpec((tr, 1), lambda i, *_: (i, 0))],
            out_specs=[pl.BlockSpec((tr, 1), lambda i, *_: (i, 0))] * 2,
            scratch_shapes=[pltpu.VMEM((tr, S), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct((n_pad, 1), jnp.int32)] * 2,
        compiler_params=_dim_semantics("parallel"),
        interpret=_interpret(),
        name="paged_sparse_select",
    )(hi.astype(jnp.int32), scores, q_abs[:, None])
    return tau[:N, 0], cut[:N, 0]


def paged_sparse_select_xla(scores, q_abs, *, topk: int):
    """The reference with an identical selection: ``lax.top_k`` (equal
    values: the lower index first) over the row's own positions."""
    N, S = scores.shape
    pos = jnp.arange(S)[None, :]
    mine = pos <= q_abs[:, None]
    key = jnp.where(mine, score_key(scores), KEY_MIN)
    if topk >= S:
        return (jnp.full((N,), KEY_MIN, jnp.int32),
                jnp.full((N,), S, jnp.int32))
    top, at = jax.lax.top_k(key, topk)
    tau = top[:, -1]
    cut = jnp.max(jnp.where(top == tau[:, None], at, -1), axis=1)
    return tau, cut.astype(jnp.int32)


# --------------------------------------------------------------------------- #
# attention over the selected tokens: the flash walk with one more mask
# --------------------------------------------------------------------------- #
def _sparse_kernel(*refs, bs, pages, scale, tq, g):
    """``paged_attention._paged_kernel`` (no window, no int8 pools) that
    drops the tokens under each row's threshold: ``g * tq`` rows of one KV
    head, g-major, the thresholds ``[tq, 1]`` blocks."""
    ctx_ref, len_ref = refs[1], refs[2]
    refs = refs[4:]
    q_ref, refs = refs[0], refs[1:]
    k_refs, v_refs = refs[:pages], refs[pages:2 * pages]
    idx_ref, tau_ref, cut_ref = refs[2 * pages:2 * pages + 3]
    o_ref, m_scr, l_scr, acc_scr = refs[-4:]
    b, qi, j = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    kv = pages * bs

    _flash_init(j, m_scr, l_scr, acc_scr)
    ctx, n = ctx_ref[b], len_ref[b]
    q_lo = qi * tq
    live = jnp.logical_and(q_lo < n,
                           j * kv < ctx + jnp.minimum(q_lo + tq, n))

    @pl.when(live)
    def _compute():
        q = q_ref[...]
        none = (None,) * pages
        k = _kv_tile(k_refs, none, q.dtype)
        v = _kv_tile(v_refs, none, q.dtype)
        s = _selected_chunk_scores(q, k, idx_ref[...], tau_ref[...],
                                   cut_ref[...], selected, j, kv, ctx, n,
                                   q_lo, tq, scale)
        _flash_update(s, v, m_scr, l_scr, acc_scr)

    _flash_finish(j == pl.num_programs(3) - 1, o_ref, l_scr, acc_scr)


def _sparse_walk(qg, k_pool, v_pool, idx, tau, cut, block_tables,
                 context_lens, lengths, layer, *, scale, rows, tq, g, pages):
    """The kernel, grid and arguments of one walk over layer ``layer`` of the
    K and V pools
    (``paged_attention._table_walk``), the index scores' tile and the rows'
    thresholds beside each KV tile. The grid's last dimension is DYNAMIC:
    the tiles of the longest live context."""
    B, nkv, _, hd = qg.shape
    nblocks, bs = k_pool.shape[-4], k_pool.shape[-2]
    max_blocks = block_tables.shape[1]
    kv = pages * bs

    def qmap(b, h, qi, j, *_):
        return (b, h, qi, 0)

    def last_tile(b, qi, ctx, lens):
        last = ctx[b] + jnp.minimum(qi * tq + tq, lens[b]) - 1
        return jnp.clip(last // bs, 0, max_blocks - 1)

    def page_map(p):
        def kvmap(b, h, qi, j, tables, ctx, lens, layer, *_):
            hi_pg = last_tile(b, qi, ctx, lens)
            j_eff = jnp.minimum(j, hi_pg // pages)
            pg = jnp.minimum(j_eff * pages + p, hi_pg)
            return (layer[0], jnp.clip(tables[b, pg], 0, nblocks - 1), h,
                    0, 0)
        return kvmap

    def idx_map(b, h, qi, j, tables, ctx, lens, layer, *_):
        return (b, qi, jnp.minimum(j, last_tile(b, qi, ctx, lens) // pages))

    in_specs = [pl.BlockSpec((None, None, rows, hd), qmap)] + [
        _page_spec(pool, None, page_map(p))
        for pool in (k_pool, v_pool) for p in range(pages)] + [
        pl.BlockSpec((None, tq, kv), idx_map)] + [
        pl.BlockSpec((None, tq, 1), lambda b, h, qi, j, *_: (b, qi, 0))] * 2
    operands = [qg] + [pool for pool in (k_pool, v_pool)
                       for _ in range(pages)] + [idx, tau, cut]
    prefetch = [block_tables.astype(jnp.int32),
                context_lens.astype(jnp.int32), lengths.astype(jnp.int32),
                layer]
    n_kv = -(-max_blocks // pages)
    n_live = jnp.clip(-(-jnp.max(context_lens + lengths) // kv), 1, n_kv)
    kernel = functools.partial(_sparse_kernel, bs=bs, pages=pages,
                               scale=float(scale), tq=tq, g=g)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, nkv, qg.shape[2] // rows, n_live.astype(jnp.int32)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, rows, hd), qmap),
        scratch_shapes=[
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, 128), jnp.float32),
            pltpu.VMEM((rows, hd), jnp.float32)])
    return kernel, grid_spec, prefetch + operands


def prefill_rows(t: int, nh: int, nkv: int, hd: int, bs: int,
                 max_blocks: int) -> int:
    """Rows the scores of a ``t``-token call are padded to: whole query
    tiles of :func:`paged_sparse_prefill_attention`."""
    tq, n_qt, _ = _prefill_tiles(t, nh // nkv, hd, bs, max_blocks)
    return tq * n_qt


def prefill_pages(t: int, nh: int, pool_shape, max_blocks: int,
                  itemsize: int = 2) -> int:
    """Pages of the KV tile of the multi-token masked walk of ``t`` rows
    over pools of ``pool_shape`` (``[.., nkv, bs, hd]``), whichever way it
    reaches them: ``_PREFILL_PAGES``, a power of two the table holds, halved
    until a step - the plain walk's tiles and the query tile's index scores,
    double-buffered (``paged_attention._walk_vmem``) - fits
    ``_PREFILL_VMEM``. From the shapes alone
    (``prefill_tile_counts(.., pages=)`` counts its tiles on the host)."""
    nkv, bs, hd = pool_shape[-3:]
    tq = _prefill_tiles(t, nh // nkv, hd, bs, max_blocks)[0]
    pages = _pow2_pages(_PREFILL_PAGES, max_blocks)
    while pages > 1 and _walk_vmem(nh // nkv * tq, hd, pages * bs, itemsize,
                                   mask_rows=tq) > _PREFILL_VMEM:
        pages //= 2
    return pages


def _masked_tiles(q, k_pool, idx, max_blocks):
    """``q [B, t, nh, hd]`` as the multi-token walks take it - ``[B, nkv,
    query tiles * g * tq, hd]``, tile-major then g-major - with ``(g, tq,
    pages a KV tile)`` of the masked walk over scores ``idx`` and the
    inverse, a result as ``[B, t, nh, hd]``."""
    B, t, nh, hd = q.shape
    nkv, bs = k_pool.shape[-3:-1]
    g = nh // nkv
    tq, n_qt, _ = _prefill_tiles(t, g, hd, bs, max_blocks)
    assert idx.shape[1] == n_qt * tq, (idx.shape, n_qt, tq)
    pages = prefill_pages(t, nh, k_pool.shape, max_blocks,
                          k_pool.dtype.itemsize)
    qg = jnp.pad(q, ((0, 0), (0, n_qt * tq - t), (0, 0), (0, 0)))
    qg = qg.reshape(B, n_qt, tq, nkv, g, hd).transpose(0, 3, 1, 4, 2, 5) \
        .reshape(B, nkv, n_qt * g * tq, hd)

    def token_major(out):
        return out.reshape(B, nkv, n_qt, g, tq, hd) \
            .transpose(0, 2, 4, 1, 3, 5).reshape(B, n_qt * tq, nh, hd)[:, :t]

    return qg, (g, tq, pages), token_major


def _selected_table_walk(q, k_pool, v_pool, idx, tau, cut, block_tables,
                         context_lens, lengths, scale, layer):
    """The multi-token masked walk of ``q [B, t, nh, hd]`` over the grid of
    ``BlockSpec`` pages as ``(kernel, grid, arguments, result's shape, the
    result as [B, t, nh, hd])``: query tiles of ``g * tq`` rows, one KV head
    a step (:func:`_sparse_walk`)."""
    hd = q.shape[-1]
    qg, (g, tq, pages), token_major = _masked_tiles(q, k_pool, idx,
                                                    block_tables.shape[1])
    return _sparse_walk(
        qg, k_pool, v_pool, idx, tau[..., None], cut[..., None],
        block_tables, context_lens, lengths,
        _layer_scalar(layer, k_pool, v_pool),
        scale=hd ** -0.5 if scale is None else scale, rows=g * tq, tq=tq,
        g=g, pages=pages) + (qg.shape, token_major)


def _whole_tiles(idx, width: int):
    """The scores ``[B, rows, S]`` at least ``width`` wide - the KV tiles a
    walk that fetches its own pages takes of them: where the table is no
    multiple of the walk's tile and the scores no wider than it, the last
    tile's DMA stays inside them."""
    return jnp.pad(idx, ((0, 0), (0, 0), (0, max(0, width - idx.shape[2]))))


def paged_sparse_decode_attention(q, k_pool, v_pool, idx, tau, cut,
                                  block_tables, context_lens, *,
                                  scale: float = None, layer=None):
    """``paged_decode_attention`` over the selected tokens alone. ``idx [B,
    rows, S]``: the call's index scores, row 0 the query token's; ``tau``,
    ``cut`` ``[B]``: its threshold. Returns ``[B, nh, hd]``. The walk is
    ``paged_decode``'s (``paged_attention._page_walk``) with the selection
    as one more operand; where that walk cannot fetch its own pages
    (``_fetches_pages``: heads narrower than a lane tile) the multi-token
    walk serves, at one token a sequence."""
    B, nh, hd = q.shape
    nkv, bs = k_pool.shape[-3:-1]
    max_blocks = block_tables.shape[1]
    g = nh // nkv
    if _fetches_pages(hd, False):
        gpad = _group_rows(g)
        pages, heads, n_kv = _decode_tiles(nkv, g, hd, bs, max_blocks,
                                           k_pool.dtype.itemsize, False)
        idx = _whole_tiles(idx, n_kv * pages * bs)
        qg = jnp.pad(q.reshape(B, nkv, g, hd),
                     ((0, 0), (0, 0), (0, gpad - g), (0, 0)))
        kernel, grid_spec, args = _page_walk(
            qg, [k_pool, v_pool], block_tables, context_lens,
            _layer_scalar(layer, k_pool, v_pool), None,
            scale=float(hd ** -0.5 if scale is None else scale), pages=pages,
            heads=heads, selected=selected, selection=(idx, tau, cut))
        out_shape, order = qg.shape, _PAGE_WALK_GRID

        def rows_of(out):
            return out[:, :, :g].reshape(B, nh, hd)
    else:
        rows = prefill_rows(1, nh, nkv, hd, bs, max_blocks)

        def row_0(x):                   # [B, ..] -> [B, rows, ..], row 0 real
            return jnp.pad(x[:, None], ((0, 0), (0, rows - 1))
                           + ((0, 0),) * (x.ndim - 1))

        kernel, grid_spec, args, out_shape, token_major = \
            _selected_table_walk(
                q[:, None], k_pool, v_pool, row_0(idx[:, 0]), row_0(tau),
                row_0(cut), block_tables, context_lens,
                jnp.ones((B,), jnp.int32), scale, layer)
        order = _WALK_GRID

        def rows_of(out):
            return token_major(out)[:, 0]
    return rows_of(pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=order,
        interpret=_interpret(),
        name="paged_sparse_decode",
    )(*args))


# jitted: one trace a process and a shape, as ``_own_pages_walk`` is
@functools.partial(jax.jit, static_argnames=("scale", "rows", "tq", "pages",
                                             "interpret"))
def _own_pages_masked_walk(qg, pools, selection, block_tables, context_lens,
                           lengths, layer, *, scale, rows, tq, pages,
                           interpret):
    """One ``paged_sparse_prefill`` call whose walk fetches its own pages:
    ``paged_attention._own_pages_walk`` with ``selection`` (the scores and
    the rows' thresholds) as three more operands."""
    kernel, grid_spec, out_shape, args = _own_pages_parts(
        qg, pools, block_tables, context_lens, lengths, layer, None,
        scale=scale, rows=rows, tq=tq, pages=pages, vd=None,
        selected=selected, selection=selection)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        compiler_params=_OWN_PAGES_GRID,
        interpret=interpret,
        name="paged_sparse_prefill",
    )(*args)


def paged_sparse_prefill_attention(q, k_pool, v_pool, idx, tau, cut,
                                   block_tables, context_lens, lengths, *,
                                   scale: float = None, layer=None):
    """``paged_prefill_attention`` over the selected tokens alone. ``idx
    [B, rows, S]`` with ``rows`` = :func:`prefill_rows`; ``tau``, ``cut``
    ``[B, rows]``. Returns ``[B, t, nh, hd]``. The walk is
    ``paged_prefill``'s that fetches its own pages
    (``paged_attention._own_pages_parts``) with the selection as one more
    operand, at the same tiles as the grid of ``BlockSpec`` pages
    (:func:`_sparse_walk`) that serves where it cannot (``_fetches_pages``:
    heads narrower than a lane tile) - the same sums in the same order."""
    hd, bs, max_blocks = q.shape[-1], k_pool.shape[-2], block_tables.shape[1]
    if _fetches_pages(hd, False):
        qg, (g, tq, pages), token_major = _masked_tiles(q, k_pool, idx,
                                                        max_blocks)
        idx = _whole_tiles(idx, -(-max_blocks // pages) * pages * bs)
        return token_major(_own_pages_masked_walk(
            qg, (k_pool, v_pool), (idx, tau[..., None], cut[..., None]),
            block_tables, context_lens, lengths,
            _layer_scalar(layer, k_pool, v_pool),
            scale=float(hd ** -0.5 if scale is None else scale),
            rows=g * tq, tq=tq, pages=pages, interpret=_interpret()))
    kernel, grid_spec, args, out_shape, token_major = _selected_table_walk(
        q, k_pool, v_pool, idx, tau, cut, block_tables, context_lens,
        lengths, scale, layer)
    return token_major(pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        compiler_params=_WALK_GRID,
        interpret=_interpret(),
        name="paged_sparse_prefill",
    )(*args))


def _sparse_attention_xla(q, k_pool, v_pool, idx, tau, cut, block_tables,
                          context_lens, scale, layer):
    """Both references: gather the table's whole width, mask by position and
    by the rows' thresholds, soft-max in float32."""
    from ..attention import attention_xla
    from .paged_attention import _gathered_view

    t = q.shape[1]
    layer = _layer_scalar(layer, k_pool, v_pool)
    kg = _gathered_view(k_pool, block_tables, layer)
    vg = _gathered_view(v_pool, block_tables, layer)
    S = kg.shape[1]
    pos = jnp.arange(S)[None, None, :]
    q_abs = (context_lens[:, None] + jnp.arange(t)[None, :])[..., None]
    keep = selected(idx[:, :t, :S], pos, tau[:, :t, None], cut[:, :t, None])
    mask = jnp.logical_and(pos <= q_abs, keep)[:, None]     # [B, 1, t, S]
    return attention_xla(q, kg, vg, causal=False, mask=mask, scale=scale)


def paged_sparse_decode_attention_xla(q, k_pool, v_pool, idx, tau, cut,
                                      block_tables, context_lens, *,
                                      scale: float = None, layer=None):
    return _sparse_attention_xla(q[:, None], k_pool, v_pool, idx,
                                 tau[:, None], cut[:, None], block_tables,
                                 context_lens, scale, layer)[:, 0]


def paged_sparse_prefill_attention_xla(q, k_pool, v_pool, idx, tau, cut,
                                       block_tables, context_lens, lengths,
                                       *, scale: float = None, layer=None):
    del lengths
    return _sparse_attention_xla(q, k_pool, v_pool, idx, tau, cut,
                                 block_tables, context_lens, scale, layer)


from ..registry import register  # noqa: E402

for _name, _pallas, _xla in (
        ("paged_index_write", paged_index_write, paged_index_write_xla),
        ("paged_index_scores", paged_index_scores, paged_index_scores_xla),
        ("paged_sparse_select", paged_sparse_select, paged_sparse_select_xla),
        ("paged_sparse_decode_attention", paged_sparse_decode_attention,
         paged_sparse_decode_attention_xla),
        ("paged_sparse_prefill_attention", paged_sparse_prefill_attention,
         paged_sparse_prefill_attention_xla)):
    register(_name, backend="pallas")(_pallas)
    register(_name, backend="xla")(_xla)
