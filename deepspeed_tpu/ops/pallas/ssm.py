"""Mosaic kernels over the per-slot state pools of a state-space layer
(``ops/ssm.py`` has the contract and the XLA references).

Every kernel takes the WHOLE ``[L, S + 1, A, B]`` pool, the layer as a
prefetched scalar and each call row's pool row as a prefetched table, and
reads or writes the rows where they lie; the writers alias the pool
argument-to-result. Nothing is sliced out of a pool and nothing is stacked
back, so a pool costs a program the rows its calls touch. A ``part`` is
``(first sublane, sublanes, lanes)`` of a row, block-aligned (the first
sublane a multiple of the count):

``state_rows_read``    ``pool[layer, rows, part] -> [b, sublanes, lanes]``
``state_rows_write``   ``pool[layer, rows, part] <- new``, in place
``ssm_decode_update``  one token of every row: ``H <- decay * H + B dtx^T``
                       in place on the row's first ``N`` sublanes and ``y =
                       C^T H`` out; its floor is one read and one write of
                       the rows' state.

Loaded by the family that has such layers (``models/granite_hybrid.py``),
not by ``ops/pallas/__init__``: no other program pays for its import.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import ssm as _ssm   # noqa: F401 (registers the XLA references)
from ..registry import register
from ._common import dim_semantics as _dim_semantics
from ._common import interpret as _interpret

_LANES = 2048      # lanes of a row one grid step moves (f32 [128, 2048]: 1 MB)


def _lane_block(width: int) -> int:
    """The widest multiple of 128 lanes that divides ``width`` and is at
    most ``_LANES``; the whole width where 128 does not divide it (a block
    as wide as its array is always legal)."""
    if width % 128:
        return width
    return max(w for w in range(128, min(width, _LANES) + 1, 128)
               if width % w == 0)


def _scalars(layer, rows, *more):
    return (jnp.asarray(layer, jnp.int32).reshape(1),
            rows.astype(jnp.int32)) + tuple(m.astype(jnp.int32) for m in more)


def _pool_spec(first, sublanes, lanes):
    """The ``[sublanes, lanes]`` block of row ``rows[i]`` of layer ``layer``
    that starts at sublane ``first`` and lane block ``j``."""
    assert first % sublanes == 0, "a part starts on a block of its own size"
    return pl.BlockSpec(
        (None, None, sublanes, lanes),
        lambda i, j, layer, rows, *_: (layer[0], rows[i], first // sublanes,
                                       j))


def _row_spec(width, lanes):
    """Call row i's ``[A, lanes]`` block of a ``[b, A, B]`` array."""
    return pl.BlockSpec((None, width, lanes), lambda i, j, *_: (i, 0, j))


def _copy_kernel(layer, rows, src, out):
    del layer, rows
    out[...] = src[...].astype(out.dtype)


def state_rows_read(pool, layer, rows, part):
    """``part`` of ``pool[layer, rows]`` as ``[b, sublanes, lanes]``: a grid
    step a (row, lane block), the pool read through the row table."""
    b, (first, a, width) = rows.shape[0], part
    lanes = _lane_block(width)
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, width // lanes),
            in_specs=[_pool_spec(first, a, lanes)],
            out_specs=_row_spec(a, lanes)),
        out_shape=jax.ShapeDtypeStruct((b, a, width), pool.dtype),
        compiler_params=_dim_semantics("parallel", "parallel"),
        interpret=_interpret(),
        name="state_rows_read",
    )(*_scalars(layer, rows), pool)


def _write_kernel(layer, rows, new, pool, out):
    del layer, rows, pool
    out[...] = new[...].astype(out.dtype)


def state_rows_write(pool, layer, rows, new, part):
    """``pool`` with ``new [b, sublanes, lanes]`` at ``part`` of ``[layer,
    rows]``, IN PLACE: the pool stays in HBM (it is no block operand), is
    aliased to the result, and a grid step writes one (row, lane block).
    Rows that must write nothing arrive aimed at the trash row; the grid
    runs in order, so where several share it the last one stands."""
    b, (first, a, width) = rows.shape[0], part
    lanes = _lane_block(width)
    return pl.pallas_call(
        _write_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, width // lanes),
            in_specs=[_row_spec(a, lanes),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=_pool_spec(first, a, lanes)),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},     # 2 scalars, new, then the pool
        compiler_params=_dim_semantics("arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="state_rows_write",
    )(*_scalars(layer, rows), new, pool)


def _column(row_ref):
    """A ``[1, N]`` lane vector as the ``[N, 1]`` sublane vector that
    broadcasts over a ``[N, lanes]`` tile: its values picked off the
    diagonal by a lane reduction."""
    n = row_ref.shape[-1]
    diagonal = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(diagonal, row_ref[...].astype(jnp.float32), 0.0),
                   axis=1, keepdims=True)


def _decode_kernel(layer, rows, fresh, order, decay, dtx, b_ref, c_ref, h_ref,
                   h_out, y_out, *, groups, spans):
    """``groups``: the groups of B and C (``b_ref``, ``c_ref`` ``[groups,
    N]``); ``spans``: how many of them this ``[N, lanes]`` block spans side
    by side (1: the block lies inside one group - with one group, all of
    it)."""
    del layer, rows
    i = order[pl.program_id(1)]
    width = h_ref.shape[-1] // spans
    for k in range(spans):
        at = (slice(None), slice(k * width, (k + 1) * width)) if spans > 1 \
            else Ellipsis
        if groups == 1:
            b_row, c_row = b_ref, c_ref
        else:
            # the block's first group: blocks before it times their span,
            # or several blocks a group
            g = pl.program_id(0) * spans + k if spans > 1 else \
                pl.program_id(0) * groups // pl.num_programs(0)
            b_row, c_row = b_ref.at[pl.ds(g, 1)], c_ref.at[pl.ds(g, 1)]
        h = jnp.where(fresh[i] > 0, 0.0, h_ref[at])        # [N, width]
        h = h * decay[at] + _column(b_row) * dtx[at]
        h_out[at] = h.astype(h_out.dtype)
        y_out[at] = jnp.sum(h * _column(c_row), axis=0, keepdims=True)


def ssm_decode_update(pool, layer, rows, fresh, decay, dtx, B, C):
    """One token of ``b`` rows on the first ``N`` sublanes of their rows of
    the state pool ``[L, S + 1, >= N, HP]``, in place
    (``ops/ssm.ssm_decode_update_xla`` is the contract). Grid (lane block,
    row): a step reads one ``[N, lanes]`` block of the row's state through
    the row table, scales it by the row's per-lane decay, adds the outer
    product of ``B`` (down the sublanes) and ``dtx`` (along the lanes),
    writes it back to the aliased pool and reduces it against ``C`` over the
    sublanes into ``y``. ``decay`` and ``dtx`` ``[b, HP]`` float32 arrive
    per lane, so nothing a token brings moves between lanes and sublanes but
    the ``N`` values of ``B`` and ``C`` (:func:`_column`). ``B``, ``C``
    ``[b, G, N]``: ``G`` groups, each its own ``HP / G`` lanes' - a step
    takes the row's ``[G, N]`` whole (1 KB a vector) and gives each group
    its lanes of the block (a 2048-lane block of Nemotron-3's 4096 spans
    four 512-lane groups); nothing is expanded per lane in HBM.

    The rows are walked live ones first (``order``), the rows aimed at the
    trash row last and one after another: consecutive steps on one block
    fetch it once and write it back once, so an inactive slot costs no
    state traffic (at 56 of 64 slots live, an eighth of the call's)."""
    b, n, width = rows.shape[0], B.shape[-1], pool.shape[3]
    lanes = _lane_block(width)
    groups = 1 if B.ndim == 2 else B.shape[1]
    spans = max(1, lanes * groups // width)
    assert width % groups == 0 and (
        lanes % (width // groups) == 0 or (width // groups) % lanes == 0), \
        (width, groups, lanes)
    vec = lambda a: a.astype(jnp.float32).reshape(b, 1, -1)
    grouped = lambda a: a.astype(jnp.float32).reshape(b, groups, n)
    order = jnp.argsort(rows == pool.shape[1] - 1, stable=True)

    def row(j, i, layer, rows, fresh, order):     # a call row's vectors
        return (order[i], 0, j)

    def state(j, i, layer, rows, fresh, order):
        return (layer[0], rows[order[i]], 0, j)

    per_lane = pl.BlockSpec((None, 1, lanes), row)
    whole = pl.BlockSpec((None, groups, n), lambda j, i, *s: (s[3][i], 0, 0))
    block = pl.BlockSpec((None, None, n, lanes), state)
    pool, y = pl.pallas_call(
        functools.partial(_decode_kernel, groups=groups, spans=spans),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(width // lanes, b),
            in_specs=[per_lane, per_lane, whole, whole, block],
            out_specs=[block, per_lane]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, 1, width), jnp.float32)],
        input_output_aliases={8: 0},     # 4 scalars, 4 vectors, the pool
        compiler_params=_dim_semantics("arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="ssm_decode_update",
    )(*_scalars(layer, rows, fresh, order), vec(decay), vec(dtx),
      grouped(B), grouped(C), pool)
    return pool, y[:, 0]


register("state_rows_read", backend="pallas")(state_rows_read)
register("state_rows_write", backend="pallas")(state_rows_write)
register("ssm_decode_update", backend="pallas")(ssm_decode_update)
