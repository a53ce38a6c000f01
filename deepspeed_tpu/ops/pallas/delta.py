"""Mosaic kernels over the per-slot state pool of a gated delta-rule layer
(``ops/delta.py`` has the mathematics, the layout and the XLA references).

``delta_decode_update``  one token of every row, in place. Grid (row, block
    of heads): a step takes the ``[dk, 16 * dv]`` block of the row's state -
    sixteen heads' ``S`` side by side, 1 MB at 128 x 128 - through the row
    table and, a head at a time, decays it down the sublanes, reads it at
    the key, adds the rank-one correction and reads it at the query; the
    pool is aliased to the result. What a token brings per key channel (the
    decay, ``k``, ``beta k``, ``q``) arrives as COLUMNS, a lane each of one
    128-lane tile a block of heads (formed outside: 64 KB a block where the
    state is 1 MB), what it brings per value channel (``beta v``) and what
    leaves (``o``) as rows. Its floor is one read and one write of the live
    rows' state.

``delta_chunk``  many tokens of a row: ONE kernel (``delta_chunk.py``) reads
    the row's state through the row table, carries it in VMEM through the
    row's tiles and hands it back for one ``state_rows_write``; a shape it
    does not tile takes ``delta_chunk_between_rows``: ``state_rows_read``,
    the chunked form of ``ops/delta.py`` in XLA, ``state_rows_write``.

Loaded by the family that has such layers (``models/solar_open2.py``), not
by ``ops/pallas/__init__``: no other program pays for its import.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import delta as _delta
from ..registry import register
from ._common import dim_semantics as _dim_semantics
from ._common import interpret as _interpret
from .ssm import state_rows_read, state_rows_write

F32 = jnp.float32
_HEADS = 16         # heads of a row's state one decode step moves
_COLUMNS = 4        # decay, k, beta k, q: a lane each a head of the block


def _float32_state(pool) -> None:
    """Both ops stream a float32 state (the decode kernel's blocks are the
    pool's own tiles, and a state rewritten every token compounds a narrower
    type's rounding): a pool of another type is refused by name, and runs
    through the XLA forms where a control asks for one (``set_backend``)."""
    if pool.dtype != jnp.float32:
        raise NotImplementedError(
            f"the delta-rule kernels stream a float32 state, not "
            f"{pool.dtype.name}: run a narrower state_dtype through the XLA "
            f"forms (ops.registry.set_backend('delta_chunk', 'xla') and "
            f"'delta_decode_update')")


def _head_block(heads: int, dv: int) -> int:
    """Heads a decode step takes: the most, up to ``_HEADS``, that divide
    ``heads`` in whole 128-lane tiles; all of them where there is none (a
    block as wide as its array is always legal)."""
    fits = [n for n in range(1, min(heads, _HEADS) + 1)
            if heads % n == 0 and (n * dv) % 128 == 0]
    return max(fits) if fits else heads


def _decode_kernel(layer, rows, fresh, idle, cols_ref, v_ref, pool_ref,
                   pool_out, o_ref, *, heads, dv):
    del layer, rows, idle
    start = fresh[pl.program_id(0)] > 0
    for h in range(heads):
        at = slice(h * dv, (h + 1) * dv)
        col = lambda n, h=h: cols_ref[:, n * heads + h:n * heads + h + 1]
        S = col(0) * jnp.where(start, 0.0, pool_ref[:, at].astype(F32))
        r = v_ref[:, at] - jnp.sum(col(2) * S, axis=0, keepdims=True)
        S = S + col(1) * r
        pool_out[:, at] = S.astype(pool_out.dtype)
        o_ref[:, at] = jnp.sum(col(3) * S, axis=0, keepdims=True)


def delta_decode_update(pool, layer, rows, fresh, q, k, v, log_a, beta):
    """One token of ``b`` rows on the first ``dk`` sublanes of their rows of
    the state pool, in place (``ops/delta.delta_decode_update_xla`` is the
    contract). A row aimed at the trash row starts from zeros like a fresh
    one and takes ONE block of it for all its steps (the block index stands
    still, so it is fetched once and written once): an idle slot costs a
    quarter of a live one's traffic at four blocks a row, and nothing it
    reads is ever a number's source."""
    _float32_state(pool)
    b, H, dk = k.shape
    dv = v.shape[-1]
    width = pool.shape[3]
    assert width == H * dv, (pool.shape, H, dv)
    hb = _head_block(H, dv)
    nj, lanes = H // hb, hb * dv
    assert _COLUMNS * hb <= 128, hb
    idle = rows == pool.shape[1] - 1
    beta = beta.astype(F32)[..., None]
    k = k.astype(F32)
    # [b, nj, dk, 128]: a block of heads' columns, a lane each
    cols = jnp.stack([jnp.exp(log_a.astype(F32)), k, beta * k,
                      q.astype(F32)], axis=1)               # [b, 4, H, dk]
    cols = cols.reshape(b, _COLUMNS, nj, hb, dk).transpose(0, 2, 4, 1, 3) \
        .reshape(b, nj, dk, _COLUMNS * hb)
    cols = jnp.pad(cols, ((0, 0),) * 3 + ((0, 128 - _COLUMNS * hb),))
    vrow = (beta * v.astype(F32)).reshape(b, 1, width)

    def state(i, j, layer, rows, fresh, idle):
        return (layer[0], rows[i], 0, jnp.where(idle[i] > 0, 0, j))

    block = pl.BlockSpec((None, None, dk, lanes), state)
    row = pl.BlockSpec((None, 1, lanes), lambda i, j, *_: (i, 0, j))
    pool, o = pl.pallas_call(
        functools.partial(_decode_kernel, heads=hb, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, nj),
            in_specs=[pl.BlockSpec((None, None, dk, 128),
                                   lambda i, j, *_: (i, j, 0, 0)),
                      row, block],
            out_specs=[block, row]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, 1, width), F32)],
        input_output_aliases={6: 0},     # 4 scalars, 2 vectors, the pool
        compiler_params=_dim_semantics("arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="delta_decode_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32),
      (fresh | idle).astype(jnp.int32), idle.astype(jnp.int32), cols, vrow,
      pool)
    return pool, o.reshape(b, H, dv)


from . import delta_chunk as _tiles  # noqa: E402 (below the decode kernel:
#                 a line of it moved is a compile-cache miss of its program)


def delta_chunk_between_rows(pool, layer, rows, fresh, q, k, v, log_a, beta,
                             tile: Optional[int] = None):
    """:func:`delta_chunk` as the XLA form between the row-table kernels:
    the state read (``state_rows_read``), ``ops/delta.delta_chunked`` in
    tiles of ``tile``, the state written back (``state_rows_write``). What
    a shape the Mosaic kernel does not tile runs, and the twin
    ``scripts/delta_kernel_bench.py`` times beside it."""
    _float32_state(pool)
    H, dk = k.shape[2:]
    part = (0, dk, pool.shape[3])
    S0 = _delta.state_to_heads(state_rows_read(pool, layer, rows, part), H)
    o, S = _delta.delta_chunked(
        q, k, v, log_a, beta,
        jnp.where(fresh[:, None, None, None], 0.0, S0), tile)
    return state_rows_write(pool, layer, rows, _delta.state_from_heads(S),
                            part), o


def delta_chunk(pool, layer, rows, fresh, q, k, v, log_a, beta,
                tile: Optional[int] = None):
    """``t`` tokens of ``b`` rows on their rows of the state pool
    (``ops/delta.delta_chunk_xla`` is the contract): ONE Mosaic kernel over
    the row table (``ops/pallas/delta_chunk.py``) and one write of the
    rows' new state, where the kernel tiles the call's shapes
    (``delta_chunk.takes``: heads of whole 128-lane tiles - the published
    128 x 128); any other shape takes :func:`delta_chunk_between_rows`,
    whose tile ``tile`` is (the kernel's is its own)."""
    _float32_state(pool)
    H, dk = k.shape[2:]
    if not _tiles.takes(dk, v.shape[-1], H, k.shape[1], pool.dtype):
        return delta_chunk_between_rows(pool, layer, rows, fresh, q, k, v,
                                        log_a, beta, tile)
    o, new = _tiles.delta_chunk_tiled(pool, layer, rows, fresh, q, k, v,
                                      log_a, beta)
    return state_rows_write(pool, layer, rows, new,
                            (0, dk, pool.shape[3])), o


register("delta_decode_update", backend="pallas")(delta_decode_update)
register("delta_chunk", backend="pallas")(delta_chunk)
