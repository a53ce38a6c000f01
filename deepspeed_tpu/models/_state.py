"""What the families with a per-slot state pool share beside the pool's ops
(``ops/ssm.py`` has the pool's protocol): which pool row each call row
meets (:func:`state_call`), the short causal convolution that runs ahead of
a recurrence (:func:`short_conv`) and the place of its tail - the sequence's
previous ``K - 1`` rows - in a slot's row under the recurrent state
(:func:`tail_part`, :func:`pack_tail`, :func:`unpack_tail`,
:func:`next_tail`). Lifted from ``models/granite_hybrid.py`` (Mamba-2's
convolution over ``[x | B | C]``, with a bias) when a second kind of layer
took them (``models/solar_open2.py``: three convolutions without one): the
same operations in the same order, so the programs that had them are what
they were. ``models/zaya.py`` takes them a third time, for a tail that lies
BESIDE paged keys and values in every layer (a pool of tails alone) ahead
of attention, its convolution without the silu.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import ssm
from ._paged import MixedCall

F32 = jnp.float32


def state_call(pool, block_tables, context_lens, valid, slots):
    """``(state rows, fresh, call)`` of a paged forward over the state pool
    ``pool``: each call row's pool row (the trash row where it must write
    nothing) and whether it starts from zeros - one array each, or, in a
    mixed call (``block_tables`` a ``_paged.MixedCall``, returned as
    ``call``: decode row i is slot i and the chunk's rows are
    ``chunk_slot``'s), (the decode rows', the chunk's) pairs."""
    if not isinstance(block_tables, MixedCall):
        if slots is None:
            slots = jnp.arange(valid.shape[0], dtype=jnp.int32)
        return ssm.pool_rows(slots, valid[:, 0], pool), context_lens == 0, \
            None
    call = block_tables
    rows = (ssm.pool_rows(jnp.arange(call.slots), call.active, pool),
            ssm.pool_rows(call.chunk_slot[None],
                          (call.chunk_valid > 0)[None], pool))
    return rows, (call.lens == 0, (call.chunk_ctx == 0)[None]), call


def short_conv(x, tail, taps, bias=None, silu: bool = True):
    """The causal depthwise convolution of ``x [b, t, C]`` after the
    sequence's previous ``K - 1`` rows ``tail [b, K - 1, C]``, ``taps [K,
    C]`` (``bias [C]`` or None), in float32, and its silu (not ``silu``: the
    plain form, ``models/zaya.py``'s first convolution): ``(silu(conv +
    bias) [b, t, C] in x's type, the rows it ran over [b, K - 1 + t, C])``."""
    t = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    taps = taps.astype(F32)
    out = sum(ext[:, k:k + t].astype(F32) * taps[k]
              for k in range(taps.shape[0]))
    if bias is not None:
        out = out + bias.astype(F32)
    return (jax.nn.silu(out) if silu else out).astype(x.dtype), ext


def tail_part(first: int, flat: int, width: int) -> Tuple[int, int, int]:
    """The convolution tail's part of a slot's row, under a recurrent state
    of ``first`` sublanes in a row ``width`` lanes wide: its ``flat`` = ``(K
    - 1) * C`` numbers in whole sublanes of as few whole 128-lane tiles as
    hold them - ``(first sublane, sublanes, lanes)`` (8 x 1664 for Granite's
    3 x 4352, 8 x 2304 for Nemotron-3-Nano's 3 x 6144, 16 x 4608 for three
    convolutions of 8192 channels)."""
    sublanes = 8 * -(-flat // (8 * width))
    lanes = width if width % 128 else min(
        width, -(-flat // (sublanes * 128)) * 128)
    assert first % sublanes == 0, "the tail starts on a block of its own size"
    return first, sublanes, lanes


def pack_tail(tail, part):
    """``[b, K - 1, C]`` as its ``part`` of the pool's rows, in the pool's
    type: every value of the compute type is one of it."""
    b = tail.shape[0]
    _, sublanes, lanes = part
    flat = tail.reshape(b, -1)
    return jnp.pad(flat, ((0, 0), (0, sublanes * lanes - flat.shape[1]))) \
        .reshape(b, sublanes, lanes)


def unpack_tail(rows, k: int, c: int, dtype):
    """The inverse of :func:`pack_tail`: ``[b, k, c]`` in ``dtype``."""
    b = rows.shape[0]
    return rows.reshape(b, -1)[:, :k * c].reshape(b, k, c).astype(dtype)


def next_tail(ext, n_valid, k: int):
    """The last ``k`` rows of ``[tail | the row's n_valid real tokens]``."""
    return jax.vmap(lambda e, n: lax.dynamic_slice_in_dim(
        e, n, k, axis=0))(ext, n_valid)
