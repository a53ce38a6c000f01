"""The Mosaic kernel of a state-space layer's MULTI-TOKEN segment on the
state pool (``ops/ssm.py`` has the recurrence, the pool and the XLA form;
``ops/pallas/ssm.py`` the pool's other kernels and its contract).

``ssm_chunk_scan``  many tokens of every row: the row's state, the first
    ``N`` sublanes of ``[layer, rows[i]]``, is read where it lies (zeros
    where ``fresh``) and advanced over the row's tokens in fast memory;
    ``y`` comes out ``[b, t, H * P]`` and the rows' new state ``[b, N, H *
    P]``, in the pool's own layout, for ``state_rows_write``. The state
    keeps that layout throughout - ``B^T [N, q] x (to_end * x) [q, lanes]``
    lands an update on its own lanes and ``C [q, N] x H [N, lanes]`` reads
    it out -, so nothing is transposed and the ``[q, q]`` masks a head never
    leave VMEM. Its floor is ``x``, ``B``, ``C`` in, ``y`` out and one read
    and one write of the rows' state. (It does not write the pool itself:
    a kernel that reads AND writes the pool between the tail's write and
    the decode rows' made XLA copy Nemotron's KV pools four times an
    attention layer - ``tests/test_chip_compile.py`` holds the mixed
    program to no pool copy; the read, the tail's write, the state's write
    is the order the compiler is known to leave in place.)

Grid (row, lane block, tile of ``TOKENS`` tokens), the tiles in order: the
lane block's ``[N, lanes]`` state is the resident block of the result from
a row's first tile to its last. A tile takes the running sum ``cs`` of
``dt A`` of the block's heads (one lower-triangular product), ``C B^T [q,
q]`` a GROUP, and then walks the block 128 lanes - ``128 / P`` heads - at a
time: a head's mask ``exp(cs_t - cs_s) dt_s`` for ``s <= t`` (every
exponent <= 0, as the XLA form's), the heads' masked ``C B^T`` side by side
against their ``x`` block-diagonally (one product, all 128 output lanes
used), the state's read-out and the state's update. Per-head scalars meet
the tokens-on-sublanes tiles as lane gathers of ``cs [q, heads]`` (a
column) and sublane reads of its transpose (a row).

Precision, as the XLA form's at default precision, operand for operand: the
products that make ``y`` see what it feeds the MXU - the masked ``C B^T``
and the state read out rounded to ``x``'s type -, the state's update sees
``to_end * x`` in float32 (:func:`_pieces`); every sum and the state itself
are float32.

Shapes the kernel does not tile (:func:`takes`) run the XLA form. Loaded by
the family that has such layers (``models/granite_hybrid.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import ssm as _ssm
from ..registry import register
from ._common import interpret as _interpret
from .ssm import _lane_block, _scalars

F32 = jnp.float32
TOKENS = 128       # a tile's tokens: its [q, q] masks are one lane tile wide
_TILE = 128        # lanes the walk takes at a time
_VMEM = 32 * 2 ** 20    # of v5e's 128 MiB (a 2048-lane block: its state in
#                         and out, x, y and their second buffers are 9 MB)
_NT = (((1,), (1,)), ((), ()))     # contract both operands' lanes


def takes(state: int, heads: int, head_dim: int, groups: int, dtype) -> bool:
    """Whether the kernel tiles a layer of these sizes in a pool of
    ``dtype``: a float32 state of whole 128-lane tiles of ``B`` and ``C``
    (they are sliced a group at a time), heads that share a 128-lane tile
    whole (``P`` 32, 64 or 128), groups of whole tiles, and lane blocks
    that nest with them."""
    width = heads * head_dim
    if (jnp.dtype(dtype) != F32 or state % 128 or head_dim not in (32, 64, 128)
            or width % (groups * _TILE)):
        return False
    lanes, group = _lane_block(width), width // groups
    return lanes % group == 0 or group % lanes == 0


def _pieces(a, mx):
    """A float32 operand as what the MXU is fed of it beside an operand of
    type ``mx``: itself beside float32, and beside bfloat16 its three
    bfloat16 pieces (8 + 8 + 8 bits of mantissa: they sum to it exactly, and
    a bfloat16 value times each is an exact float32 product) - what the XLA
    form's state update comes to at default precision (measured on the
    chip: its state lies 2e-5 from the token-by-token recurrence's where
    one piece lies 1e-2, PERF.md section 6, PR 58)."""
    if mx != jnp.bfloat16:
        return [a]
    high = a.astype(mx)
    rest = a - high.astype(F32)
    mid = rest.astype(mx)
    return [high, mid, (rest - mid.astype(F32)).astype(mx)]


def _kernel(layer, rows, fresh, x_ref, dt_ref, a_ref, dt_t, a_col, b_ref,
            b_t, c_ref, h_in, h_out, y_ref, cs_t, cb, fed, kept, *, trash,
            head_dim, spans, group_tiles):
    """``spans``: the groups of B and C this lane block spans side by side
    (``b_ref``, ``c_ref`` ``[q, spans * N]``, ``b_t [spans, N, q]``; 1: the
    block lies inside one group); ``group_tiles``: the 128-lane tiles of a
    group. ``dt_ref [q, 128]``: the block's heads' steps on the first lanes,
    0 past them, with ``A`` a row (``a_ref``); ``dt_t [128, q]``, ``a_col``:
    the same, a head a sublane - the wrapper's transposes, as ``b_t`` is: a
    ``[128, 128]`` transpose in here costs more than the tile's matmuls.
    Scratch: ``cs_t [heads, q]`` (a head's running sum as a row), ``cb
    [spans, q, q]``, ``fed [q, lanes]`` (``to_end * x``: what the tile
    feeds the state) and ``kept [1, lanes]`` (what the tile keeps of
    it)."""
    del layer
    i, k = pl.program_id(0), pl.program_id(2)
    q, lanes = x_ref.shape
    n = h_out.shape[0]
    mx = x_ref.dtype                # what the MXU is fed, as the XLA form's
    per = _TILE // head_dim         # heads a 128-lane tile
    width = min(lanes, group_tiles * _TILE)     # a group's lanes in the block

    @pl.when(k == 0)
    def _enter():
        h_out[...] = jnp.where(fresh[i] > 0, 0.0, h_in[...])

    # a row aimed at the trash row computes nothing: its state comes out as
    # it was read and its y is zeros
    @pl.when(rows[i] == trash)
    def _idle():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(rows[i] != trash)
    def _tile():
        row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
        causal = col <= row
        exact = dict(precision=jax.lax.Precision.HIGHEST,
                     preferred_element_type=F32)
        dt = dt_ref[...]
        cs = jax.lax.dot(causal.astype(F32), dt * a_ref[...], **exact)
        cs_t[...] = jax.lax.dot(dt_t[...] * a_col[...],
                                (row <= col).astype(F32), **exact)
        to_end = jnp.exp(cs[q - 1:q] - cs) * dt             # [q, heads]

        def group_in(g, carry):
            """A group's ``C B^T``, and its lanes' read-out of the state
            that ENTERS the tile into ``y`` (each lane's ``exp(cs_t)``
            waits for the walk)."""
            at = pl.ds(pl.multiple_of(g * n, n), n)
            of = pl.ds(pl.multiple_of(g * width, _TILE), width)
            cb[g] = jax.lax.dot_general(c_ref[:, at], b_ref[:, at], _NT,
                                        preferred_element_type=F32)
            y_ref[:, of] = jnp.dot(c_ref[:, at], h_out[:, of].astype(mx),
                                   preferred_element_type=F32)
            return carry

        jax.lax.fori_loop(0, spans, group_in, 0)
        head_of_lane = jax.lax.broadcasted_iota(
            jnp.int32, (q, _TILE), 1) // head_dim

        def tile(p, carry):
            at = pl.ds(pl.multiple_of(p * _TILE, _TILE), _TILE)
            g = p // group_tiles if spans > 1 else 0
            x = x_ref[:, at]                         # [q, 128]
            masks, xs, cs_l, te_l = [], [], None, None
            for r in range(per):
                head = p * per + r
                pick = jnp.full((q, _TILE), head, jnp.int32)
                at_t = jnp.take_along_axis(cs, pick, axis=1)  # cs_t, a lane
                te = jnp.take_along_axis(to_end, pick, axis=1)   # each key
                seg = jnp.where(causal, at_t - cs_t[pl.ds(head, 1), :],
                                -jnp.inf)
                masks.append((cb[g] * jnp.exp(seg)
                              * dt_t[pl.ds(head, 1), :]).astype(mx))
                mine = head_of_lane == r
                xs.append(x if per == 1 else
                          jnp.where(mine, x, jnp.zeros_like(x)))
                cs_l = at_t if r == 0 else jnp.where(mine, at_t, cs_l)
                te_l = te if r == 0 else jnp.where(mine, te, te_l)
            # the tile's heads' masked [q, q] side by side against their x
            # block-diagonally: every output lane is a head's own
            y_ref[:, at] = jnp.exp(cs_l) * y_ref[:, at] + jnp.dot(
                jnp.concatenate(masks, axis=1), jnp.concatenate(xs, axis=0),
                preferred_element_type=F32)
            fed[:, at] = te_l * x
            kept[:, at] = jnp.exp(cs_l[q - 1:q])
            return carry

        jax.lax.fori_loop(0, lanes // _TILE, tile, 0)

        def group_out(g, carry):
            of = pl.ds(pl.multiple_of(g * width, _TILE), width)
            h_out[:, of] = kept[:, of] * h_out[:, of] + sum(
                jnp.dot(b_t[g], piece, preferred_element_type=F32)
                for piece in _pieces(fed[:, of], mx))
            return carry

        jax.lax.fori_loop(0, spans, group_out, 0)


def ssm_chunk_scan(pool, layer, rows, fresh, x, dt, A, B, C, chunk):
    """``t`` tokens of ``b`` rows from the first ``N`` sublanes of their
    rows of the state pool ``[L, S + 1, >= N, H * P]``
    (``ops/ssm.ssm_chunk_scan_xla`` is the contract; ``chunk`` is how THAT
    form blocks its scan and no part of the result: the kernel's tile is
    ``TOKENS``). ``x [b, t, H, P]``; ``dt [b, t, H]`` after its softplus, 0
    on padding; ``A [H]``; ``B``, ``C`` ``[b, t, N]`` or ``[b, t, G, N]``.
    Returns ``(y [b, t, H * P] float32, the rows' new state [b, N, H * P]
    float32)``. ``t`` is padded to whole tiles with ``dt = 0`` tokens,
    which neither decay nor feed a state."""
    heads, head_dim = x.shape[2:]
    groups = 1 if B.ndim == 3 else B.shape[2]
    if not takes(B.shape[-1], heads, head_dim, groups, pool.dtype):
        return _ssm.ssm_chunk_scan_xla(pool, layer, rows, fresh, x, dt, A, B,
                                       C, chunk)
    return _tiled(pool, layer, rows, fresh, x, dt, A, B, C,
                  interpret=_interpret())


# jitted: one trace and one lowering a program, whatever its layer bodies -
# the kernel's body is ~200 equations, a stack traces it a dozen times and
# each is 0.1 s of the chip's host (set-up time)
@functools.partial(jax.jit, static_argnames="interpret")
def _tiled(pool, layer, rows, fresh, x, dt, A, B, C, *, interpret):
    b, t, heads, head_dim = x.shape
    n = B.shape[-1]
    groups = 1 if B.ndim == 3 else B.shape[2]
    width = heads * head_dim
    lanes = _lane_block(width)      # as ``ssm_decode_update``'s: 2048 of 4096
    blocks, block_heads = width // lanes, lanes // head_dim
    spans = max(1, lanes * groups // width)

    def tokens(a):      # [b, t, ..] as [b, whole tiles, flat]
        a = a.reshape(b, t, -1)
        return jnp.pad(a, ((0, 0), (0, -t % TOKENS), (0, 0)))

    def by_block(a):    # [.., H]: a lane block's heads on a 128-lane row
        a = a.astype(F32).reshape(a.shape[:-1] + (blocks, block_heads))
        return jnp.pad(a, ((0, 0),) * (a.ndim - 1)
                       + ((0, _TILE - block_heads),))

    x, B, C = tokens(x), tokens(B), tokens(C)
    dt, A = by_block(tokens(dt)).swapaxes(1, 2), by_block(A)
    tiles = x.shape[1] // TOKENS
    if spans > 1:       # the block's groups, else the one group it lies in
        group = lambda i, j, k, *_: (i, k, j)
        group_t = lambda i, j, k, *_: (i, j, 0, k)
    else:
        group = lambda i, j, k, *_: (i, k, j * groups // blocks)
        group_t = lambda i, j, k, *_: (i, j * groups // blocks, 0, k)
    grouped = pl.BlockSpec((None, TOKENS, spans * n), group)
    by_lane = pl.BlockSpec((None, TOKENS, lanes),
                           lambda i, j, k, *_: (i, k, j))
    head_row = pl.BlockSpec((None, 1, _TILE), lambda i, j, k, *_: (j, 0, 0))
    head_col = pl.BlockSpec((None, _TILE, 1), lambda i, j, k, *_: (j, 0, 0))
    new, y = pl.pallas_call(
        functools.partial(_kernel, trash=pool.shape[1] - 1,
                          head_dim=head_dim, spans=spans,
                          group_tiles=width // groups // _TILE),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, blocks, tiles),
            in_specs=[by_lane,
                      pl.BlockSpec((None, None, TOKENS, _TILE),
                                   lambda i, j, k, *_: (i, j, k, 0)),
                      head_row,
                      pl.BlockSpec((None, None, _TILE, TOKENS),
                                   lambda i, j, k, *_: (i, j, 0, k)),
                      head_col, grouped,
                      pl.BlockSpec((None, spans, n, TOKENS), group_t),
                      grouped,
                      pl.BlockSpec((None, None, n, lanes),
                                   lambda i, j, k, layer, rows, fresh:
                                   (layer[0], rows[i], 0, j))],
            out_specs=[pl.BlockSpec((None, n, lanes),
                                    lambda i, j, k, *_: (i, 0, j)), by_lane],
            scratch_shapes=[pltpu.VMEM((_TILE, TOKENS), F32),
                            pltpu.VMEM((spans, TOKENS, TOKENS), F32),
                            pltpu.VMEM((TOKENS, lanes), F32),
                            pltpu.VMEM((1, lanes), F32)]),
        out_shape=[jax.ShapeDtypeStruct((b, n, width), F32),
                   jax.ShapeDtypeStruct(x.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="ssm_chunk_scan",
    )(*_scalars(layer, rows, fresh), x, dt, A[:, None], dt.swapaxes(2, 3),
      A[:, :, None], B,
      B.reshape(b, -1, groups, n).transpose(0, 2, 3, 1), C, pool)
    return y[:, :t], new


register("ssm_chunk_scan", backend="pallas")(ssm_chunk_scan)
