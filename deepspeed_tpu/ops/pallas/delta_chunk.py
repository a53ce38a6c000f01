"""The Mosaic kernel of a gated delta-rule layer's MULTI-TOKEN segment on the
state pool (``ops/delta.py`` has the mathematics, the layout and the XLA
form; ``ops/pallas/delta.py`` the single-token kernel and the op).

``delta_chunk_tiled``  many tokens of every row: the row's state, the first
    ``dk`` sublanes of ``[layer, rows[i]]``, is read where it lies through
    the row table (zeros where ``fresh``), carried in VMEM - in the pool's
    own layout, ``[dk, heads * dv]``, a head a lane tile - through the row's
    tiles of ``TOKENS`` tokens, and handed back ``[b, dk, heads * dv]`` for
    ONE ``state_rows_write`` (the kernel does not write the pool: a kernel
    that read AND wrote it between the tail's write and the decode rows'
    changed the order of a segment's pool operations, and XLA answered
    Nemotron's with four copies of a KV pool - ``ops/pallas/ssm_scan.py``).
    The token operands arrive as the family makes them, ``[b, t, heads,
    d]`` in the types they have (``q``, ``k``, ``log_a`` float32, ``v`` the
    compute type): nothing is widened, transposed or tiled in HBM, and
    nothing of the chunked form is left to XLA but ``beta`` laid out twice
    (64 K numbers).

Grid (row, block of ``_HEADS`` heads, tile), the tiles in order; the block's
``[dk, lanes]`` state is the resident block of the result from a row's first
tile to its last. A tile is the chunked (WY / UT) form of ``ops/delta.py``
in two passes. Inside SUB-blocks of 16 tokens (:func:`_sub_blocks`, every
head of the block at once): the decayed products from the tokens' own
decays, a token at a time, and the sub-block's unit-lower system by forward
substitution. Then, ``_GROUP`` heads in flight (:func:`_kernel`): the
running sum ``g`` of ``log_a``; the decayed products BETWEEN sub-blocks
level by level - halves of 16, 32, 64 tokens, both factors relative to the
boundary between the halves -; the sub-blocks' inverses merged up the same
levels (``T <- T - T L21 T``); ``W``, ``U`` and the state's products. Every
exponent is <= 0 and no power of ``L`` is formed, as ``ops/delta.py``
demands. ``beta`` scales ``A``'s rows for the substitution and the
sub-blocks' inverses' COLUMNS after it (``T Diag(beta)``, which the merges
keep), and enters no later product.

Precision, as the XLA form's operand for operand: every matrix product is a
float32 ``dot`` at the trace's matmul precision (``jax.
default_matmul_precision``: the tests' ``highest`` is exact, the served
default is what the XLA form's ``einsum``s run at - one bfloat16 pass,
measured: ``scripts/delta_kernel_bench.py``), what the XLA form computes
elementwise (the sub-blocks' products, the substitution, the running sum)
is float32 elementwise here, the state is float32 in the pool and in VMEM.

Shapes the kernel does not tile (:func:`takes`) run the XLA form between
the row-table kernels. Loaded by ``ops/pallas/delta.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import delta as _delta
from ._common import interpret as _interpret
from .ssm import _scalars

F32 = jnp.float32
SUB = _delta.SUB
# a tile's tokens: its [tokens, tokens] matrices are whole 128-lane tiles
# (the XLA form's 64 halves them into shapes Mosaic pads)
TOKENS = 128
_HEADS = 8          # heads a grid step takes: their sub-block rows, 16 lanes
#                     a head, fill one 128-lane tile
_GROUP = 8          # of them in flight at a time in the walk (``_kernel``):
#                     1 / 2 / 4 / 8 read 452 / 270 / 224 / 212 us a 512-token
#                     call's walk on the chip (PERF.md section 6, PR 60)
_VMEM = 32 * 2 ** 20    # of v5e's 128 MiB (a step's blocks and their second
#                         buffers are 8 MB at 8 heads of 128 x 128)


def takes(dk: int, dv: int, heads: int, t: int, dtype) -> bool:
    """Whether the kernel tiles a call of these sizes on a pool of
    ``dtype``: a float32 state, heads of whole 128-lane tiles (a head's
    keys, values and state are sliced off a block by lane tiles), any
    number of tokens (the last tile is padded with tokens that neither
    decay nor write)."""
    del heads, t
    return jnp.dtype(dtype) == F32 and dk % 128 == 0 and dv % 128 == 0


def _head_block(heads: int) -> int:
    return max(n for n in range(1, _HEADS + 1) if heads % n == 0)


def _sub_blocks(q_ref, k_ref, a_ref, b_ref, by_ref, p_ref, t_ref):
    """Inside SUB-blocks of 16 tokens, for every head of the block at once
    and in the layout the operands arrive in - a token a ``[heads, dk]``
    slab, its heads on sublanes, so the token ``d`` before it is the slab
    ``d`` before it and nothing is rotated: ``p_ref[i, h, 16 n + s]`` takes
    token ``16 n + i``'s decayed product of ``q`` with the key of token ``16
    n + s``, ``s <= i``, and ``t_ref[i, h, 16 n + s]`` row ``i`` of ``(I +
    Diag(beta) A)^-1 Diag(beta)`` of sub-block ``n`` (``A`` the keys'
    decayed products below the diagonal).

    The decay between two tokens of a sub-block is the PRODUCT of the
    tokens' own decays between them, built a token at a time (``a_u =
    exp(log_a_u) <= 1``: no exponent is positive, nothing overflows, and 15
    float32 products keep more of a weight than the difference of two
    running sums does); a product over the channels is a lane reduction a
    slab. The unit-lower system is solved by forward substitution a COLUMN
    at a time: once row ``s`` stands, every later row takes its multiple of
    it - 15 steps over whole arrays, no power of ``L`` formed."""
    c, heads, dk = k_ref.shape
    n = c // SUB
    slabs = lambda ref: ref[...].reshape(n, SUB, heads, dk)
    q, k, a = slabs(q_ref), slabs(k_ref), jnp.exp(slabs(a_ref))
    shape = (n, SUB, heads, 128)
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 3)
    own = lane == jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    over = lambda x: jnp.sum(x, axis=-1, keepdims=True)
    P = jnp.where(own, over(q * k), 0.0)
    A = jnp.zeros(shape, F32)
    decay = None
    for d in range(1, SUB):     # token i against the token d before it
        decay = a[:, 1:] if d == 1 else decay[:, 1:] * a[:, 1:SUB + 1 - d]
        other = decay * k[:, :SUB - d]
        at = own[:, :SUB - d]       # rows d.., lane i - d
        P = jnp.concatenate([P[:, :d], jnp.where(
            at, over(q[:, d:] * other), P[:, d:])], axis=1)
        A = jnp.concatenate([A[:, :d], jnp.where(
            at, over(k[:, d:] * other), A[:, d:])], axis=1)
    # beta a token as a column of its slab: picked off the diagonal of
    # [heads, heads] by a lane reduction
    pick = jax.lax.broadcasted_iota(jnp.int32, (c, heads, heads), 1) \
        == jax.lax.broadcasted_iota(jnp.int32, (c, heads, heads), 2)
    beta = over(jnp.where(pick, b_ref[...][:, None, :], 0.0))
    L = beta.reshape(n, SUB, heads, 1) * A
    # the tile's sub-blocks side by side on the lanes, 16 a sub-block (lanes
    # past 15 are 0: a rotation a sub-block and a sum) - the substitution
    # then runs on an eighth of the registers, and the walk repeats a
    # head's [16, 128] rows down the tile where it would gather lanes
    packed = lambda x: sum(
        (pltpu.roll(x[j], SUB * j, 2) for j in range(1, n)), x[0])
    p_ref[...] = packed(P)
    L = packed(L)                                       # [SUB, heads, 128]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (SUB, heads, 128), 2)
    T = (lanes % SUB == jax.lax.broadcasted_iota(
        jnp.int32, (SUB, heads, 128), 0)).astype(F32)
    for s in range(SUB - 1):
        below = (SUB - 1 - s) * heads
        # L[i, s] of each sub-block over its 16 lanes: a lane gather
        factor = jnp.take_along_axis(
            L[s + 1:].reshape(below, 128),
            (lanes[s + 1:] // SUB * SUB + s).reshape(below, 128), axis=1)
        T = jnp.concatenate([T[:s + 1], T[s + 1:] - factor.reshape(
            SUB - 1 - s, heads, 128) * T[s:s + 1]], axis=0)
    t_ref[...] = T * by_ref[...]


def _kernel(layer, rows, fresh, q_ref, k_ref, v_ref, a_ref, b_ref, by_ref,
            s_in, s_out, o_ref, g_ref, v32, p_ref, t_ref, *, heads, group, dk,
            dv):
    """A tile of ``c`` tokens of a block of ``heads`` heads. ``q_ref``,
    ``k_ref``, ``a_ref`` (``log_a``) ``[c, heads, dk]``, ``v_ref``, ``o_ref``
    ``[c, heads, dv]``: blocks of the operands as they lie, a token's heads
    on sublanes; ``b_ref [c, heads]``: ``beta``, a token a row; ``by_ref
    [heads, c]``: ``beta`` again, the tile's tokens along a head's lanes;
    the state ``[dk, heads * dv]``, a head a lane tile. Scratch: ``g_ref
    [group, c, dk]``, the running sum of ``log_a`` inside the tile (its
    boundary rows are read back by index); ``v32``, the values widened once
    a step; ``p_ref``, ``t_ref`` ``[16, heads, c]``: the sub-blocks' rows
    (:func:`_sub_blocks`).

    Two passes a step. The sub-blocks first, ALL the block's heads at once
    in the layout the operands arrive in (:func:`_sub_blocks`). Then the
    walk, ``group`` heads in flight at a time, a head's ``[c, d]`` a
    sublane-strided read: a head's products are a CHAIN (three merges of
    ``T``, then ``W``, ``R``, ``o``: a dozen ``[128, 128]`` products each
    waiting on the last), so the walk takes ``group`` heads a step as
    ``[group, c, d]`` arrays - one traced operation whatever the group, a
    product a head - and the matrix units work on one head's while
    another's drains."""
    del layer, rows
    c = q_ref.shape[0]
    dot = functools.partial(jax.lax.dot_general, preferred_element_type=F32)
    heads_ = ((0,), (0,))       # a head a batch: a product a head, one op
    mm = lambda a, b: dot(a, b, (((2,), (1,)), heads_))
    nt = lambda a, b: dot(a, b, (((2,), (2,)), heads_))     # contract lanes
    tn = lambda a, b: dot(a, b, (((1,), (1,)), heads_))     # and sublanes
    start = fresh[pl.program_id(0)] > 0

    @pl.when(pl.program_id(2) == 0)
    def _enter():
        s_out[...] = jnp.where(start, 0.0, s_in[...])

    v32[...] = v_ref[...].astype(F32)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    summed = (col <= row).astype(F32)
    token = jax.lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    channel = jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1)
    same_block = row // SUB == col // SUB
    halves = [h for h in (SUB << n for n in range(8)) if h < c]
    _sub_blocks(q_ref, k_ref, a_ref, b_ref, by_ref, p_ref, t_ref)

    def walk(n, carry):
        first = n * group
        of = lambda ref: jnp.stack(
            [ref[:, first + i, :] for i in range(group)])   # [group, c, d]
        own = lambda ref: jnp.stack([jnp.where(
            same_block, jnp.concatenate(
                [ref[:, first + i, :]] * (c // SUB), axis=0), 0.0)
            for i in range(group)])     # a head's sub-block rows [16, 128]
        #   repeated down the tile, each kept in its own sub-block's
        #   columns: the block diagonals of [group, c, c]
        out = pl.ds(pl.multiple_of(first * dv, 128), group * dv)
        k, q = of(k_ref), of(q_ref)
        # the running sum of log_a down the tile: a lower-triangular product
        # at full precision - the matrix unit adds a row's tokens in order,
        # so tokens that do not decay (a row's padding) leave the sum bit
        # for bit what it was, which a sum by doubling does not (an
        # exponent off by the last place of -700 is 6e-5 of a weight). The
        # matrix units have the room: the same sum by sublane rotations
        # cost 33 us a call more (scripts/delta_kernel_bench.py)
        g = dot(jnp.broadcast_to(summed, (group, c, c)), of(a_ref),
                (((2,), (1,)), heads_), precision=jax.lax.Precision.HIGHEST)
        g_ref[...] = g
        last = g_ref[:, c - 1:c, :]                         # [group, 1, dk]
        into = jnp.exp(g)
        P, T = own(p_ref), own(t_ref)
        for half in halves:
            # between the halves of each pair of ``half``-token blocks, both
            # factors relative to the left half's last token (``mid``): g_t
            # - mid <= 0 on the right, mid - g_s <= 0 on the left. Only the
            # right halves' ROWS are products' rows, only the left halves'
            # columns their columns: the products run on the right rows
            # alone (``c / 2`` of them), and go back among zeros
            firsts = range(half, c, 2 * half)
            right = lambda x: jnp.concatenate(
                [x[:, m:m + half] for m in firsts], axis=1)
            none = jnp.zeros((group, half, c), F32)
            among = lambda y: jnp.concatenate(sum(
                ([none, y[:, j * half:(j + 1) * half]]
                 for j in range(len(firsts))), []), axis=1)
            mids = [g_ref[:, m - 1:m, :] for m in firsts]
            to_right = jnp.exp(right(g) - jnp.concatenate(
                [jnp.broadcast_to(m, (group, half, dk)) for m in mids],
                axis=1))
            mid = jnp.concatenate(
                [jnp.broadcast_to(m, (group, 2 * half, dk)) for m in mids],
                axis=1)
            left = k * jnp.exp(jnp.where(token % (2 * half) >= half,
                                         -jnp.inf, mid - g))
            both = nt(jnp.concatenate(
                [right(q) * to_right, right(k) * to_right], axis=1), left)
            if 2 * half < c:        # [group, c, c]: q's right rows, then k's
                both = jnp.where(
                    row % (c // 2) // half == col // (2 * half), both, 0.0)
            P = P + among(both[:, :c // 2])
            T = T - among(mm(right(T), among(mm(both[:, c // 2:], T))))
        WU = mm(T, jnp.concatenate([k * into, of(v32)], axis=2))
        S = jnp.stack([s_out[:, pl.ds(pl.multiple_of((first + i) * dv, 128),
                                      dv)] for i in range(group)])
        WQ = mm(jnp.concatenate([WU[:, :, :dk], q * into], axis=1), S)
        R = WU[:, :, dk:] - WQ[:, :c]
        o = WQ[:, c:] + mm(P, R)
        # what the tile keeps of a channel's state, a column: the row picked
        # off the diagonal by a lane reduction
        kept = jnp.exp(jnp.sum(jnp.where(
            channel, jnp.broadcast_to(last, (group, dk, dk)), 0.0), axis=2,
            keepdims=True))
        S = kept * S + tn(k * jnp.exp(last - g), R)
        for i in range(group):
            o_ref[:, first + i, :] = o[i]
        s_out[:, out] = jnp.concatenate([S[i] for i in range(group)], axis=1)
        return carry

    jax.lax.fori_loop(0, heads // group, walk, 0)


_EXPORTED: dict = {}    # (shapes, types, matmul precision) -> the wrapper lowered


def delta_chunk_tiled(pool, layer, rows, fresh, q, k, v, log_a, beta):
    """``t`` tokens of ``b`` rows from the first ``dk`` sublanes of their rows
    of the state pool (``ops/delta.delta_chunk_xla`` is the contract; the
    caller has asked :func:`takes`). Returns ``(o [b, t, H, dv] float32, the
    rows' new state [b, dk, H * dv] float32)`` - the state in the pool's
    layout, for ``state_rows_write``. A row aimed at the trash row starts
    from zeros like a fresh one: nothing it reads is a number's source."""
    args = (pool, jnp.asarray(layer, jnp.int32).reshape(()), rows, fresh, q,
            k, v, log_a, beta)
    if _interpret():
        return _tiled(*args, interpret=True)
    # lowered ONCE a process and a shape: a program that calls it takes the
    # exported module as it stands (the body is ~1 700 traced equations, and
    # lowering them anew for each of a cell's programs put 5 s on its
    # set-up: PERF.md section 6, PR 60)
    key = tuple((a.shape, a.dtype.name) for a in args) \
        + (jax.config.jax_default_matmul_precision,)
    if key not in _EXPORTED:
        _EXPORTED[key] = jax.export.export(
            jax.jit(functools.partial(_tiled, interpret=False)),
            platforms=("tpu",))(
                *(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args))
    return _EXPORTED[key].call(*args)


# jitted: one trace a process, whatever the programs and their layer bodies
# (ops/pallas/ssm_scan.py found +6 % of a cell's set-up without it)
@functools.partial(jax.jit, static_argnames="interpret")
def _tiled(pool, layer, rows, fresh, q, k, v, log_a, beta, *, interpret):
    b, t, H, dk = k.shape
    dv = v.shape[-1]
    c = TOKENS
    pad = -t % c
    if pad:     # tokens that neither decay nor write: log_a = beta = 0
        q, k, v, log_a, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, log_a, beta))
    hb = _head_block(H)
    nj = H // hb
    group = max(g for g in range(1, _GROUP + 1) if hb % g == 0)
    # beta twice over, a block of heads apart: a token a row, and a
    # sub-block's 16 tokens along the lanes of its heads' rows
    beta = beta.astype(F32).reshape(b, t + pad, nj, hb)
    by_token = jnp.moveaxis(beta, 2, 1)                 # [b, nj, t, hb]
    tiles = (t + pad) // c
    by_lane = beta.reshape(b, tiles, c // SUB, SUB, nj, hb) \
        .transpose(0, 4, 1, 5, 2, 3).reshape(b, nj, tiles, hb, c)
    by_key = pl.BlockSpec((None, c, hb, dk),
                          lambda i, j, n, *_: (i, n, j, 0))
    by_value = pl.BlockSpec((None, c, hb, dv),
                            lambda i, j, n, *_: (i, n, j, 0))
    idle = rows == pool.shape[1] - 1
    new, o = pl.pallas_call(
        functools.partial(_kernel, heads=hb, group=group, dk=dk, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, nj, tiles),
            in_specs=[by_key, by_key, by_value, by_key,
                      pl.BlockSpec((None, None, c, hb),
                                   lambda i, j, n, *_: (i, j, n, 0)),
                      pl.BlockSpec((None, None, None, hb, c),
                                   lambda i, j, n, *_: (i, j, n, 0, 0)),
                      pl.BlockSpec((None, None, dk, hb * dv),
                                   lambda i, j, n, layer, rows, fresh:
                                   (layer[0], rows[i], 0, j))],
            out_specs=[pl.BlockSpec((None, dk, hb * dv),
                                    lambda i, j, n, *_: (i, 0, j)),
                       by_value],
            scratch_shapes=[pltpu.VMEM((group, c, dk), F32),
                            pltpu.VMEM((c, hb, dv), F32),
                            pltpu.VMEM((SUB, hb, c), F32),
                            pltpu.VMEM((SUB, hb, c), F32)]),
        out_shape=[jax.ShapeDtypeStruct((b, dk, H * dv), F32),
                   jax.ShapeDtypeStruct((b, t + pad, H, dv), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        interpret=interpret,
        name="delta_chunk",
    )(*_scalars(layer, rows, fresh | idle), q, k, v, log_a, by_token, by_lane,
      pool)
    return o[:, :t], new
