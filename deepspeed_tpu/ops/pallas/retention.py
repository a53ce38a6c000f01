"""Mosaic kernels over the per-slot state pool of a power-retention layer
(``ops/retention.py`` has the mathematics, the layout and the XLA
references).

Both take the WHOLE ``[L, S + 1, nkv * d + 8.., R]`` pool, the layer as a
prefetched scalar and each call row's pool row as a prefetched table, read
and write the rows where they lie and alias the pool argument-to-result: a
pool costs a program the rows its calls touch.

``retention_decode_update``  one token of every row. Grid (row, lane block):
    a step takes one ``[sublanes, 512]`` block of the row's state - every
    head's ``S`` and ``z`` over 512 of ``phi``'s entries -, decays it, adds
    the rank-one ``v phi(k)^T``, writes it back and multiplies it by the
    group's query heads' ``phi(q)`` (the MXU, one read of the state for all
    of them). ``phi(k)`` and ``phi(q)`` of ONE token a row are formed outside
    (35 KB a head a row where the state is 4.5 MB). Its floor is one read
    and one write of the live rows' state.

``retention_chunk``  many tokens of a row. Grid (row, key-value head, tile
    of tokens): the head's state is copied into VMEM at the row's first
    tile, carried through its tiles and copied back at the last. A tile does
    the quadratic form among its own tokens, and against the state
    ``S phi(Q)^T`` and ``(decayed V)^T phi(K)`` on the MXU, with ``phi^T
    [R, tokens]`` formed GROUP OF ROWS BY GROUP in VMEM - a broadcast row of
    ``q^T`` times a slab of ``q^T`` - and never in HBM.

Loaded by the family that has such layers (``models/brumby.py``), not by
``ops/pallas/__init__``: no other program pays for its import.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import retention as _ret
from ..registry import register
from ._common import interpret as _interpret

F32 = jnp.float32
_LANES = 512            # lanes of a row's state one decode step moves
_VMEM = 64 * 2 ** 20    # of v5e's 128 MiB (the chunk kernel holds a head's
#                         4.7 MB state, a 1.9 MB group of phi and their casts)
_NT = (((1,), (1,)), ((), ()))     # contract both operands' lanes


def _lane_block(width: int) -> int:
    """The widest multiple of 128 lanes that divides ``width`` and is at
    most ``_LANES``; the whole width where there is none."""
    fits = [w for w in range(128, min(width, _LANES) + 1, 128)
            if width % w == 0]
    return max(fits) if fits else width


def _float32_state(pool) -> None:
    """Both kernels stream a float32 state (the chunk kernel copies a head's
    rows straight into its float32 scratch, and a row's ``z`` sublanes are
    half a packed tile of a narrower type): a pool of another type is
    refused by name, and runs through the XLA forms where a control asks for
    one (``set_backend``)."""
    if pool.dtype != jnp.float32:
        raise NotImplementedError(
            f"the retention kernels stream a float32 state, not "
            f"{pool.dtype.name}: run a narrower state_dtype through the XLA "
            f"forms (ops.registry.set_backend('retention_chunk', 'xla') and "
            f"'retention_decode_update')")


def _params(*sem):
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=_VMEM)


# --------------------------------------------------------------------------- #
# one token of every row
# --------------------------------------------------------------------------- #
def _decode_kernel(layer, rows, fresh, idle, fk_ref, fq_ref, cols_ref,
                   pool_ref, pool_out, num_ref, den_ref, *, nkv, d):
    del layer, rows, idle
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    start = fresh[i] > 0
    old = lambda at: jnp.where(start, 0.0, pool_ref[at, :].astype(F32))
    for h in range(nkv):
        at = slice(h * d, (h + 1) * d)
        # [d, lanes]: decay down the sublanes' column, v down the sublanes
        # times phi(k) along the lanes
        new = cols_ref[at, 1:2] * old(at) \
            + cols_ref[at, 0:1] * fk_ref[h:h + 1, :]
        pool_out[at, :] = new.astype(pool_out.dtype)
        num_ref[h] += jax.lax.dot_general(
            fq_ref[h], new, _NT, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=F32)
    at = slice(nkv * d, nkv * d + nkv)
    z = cols_ref[at, 1:2] * old(at) + fk_ref[...]
    pool_out[at, :] = z.astype(pool_out.dtype)
    spare = pool_out.shape[0] - (nkv * d + nkv)
    if spare:
        pool_out[nkv * d + nkv:, :] = jnp.zeros(
            (spare, pool_out.shape[1]), pool_out.dtype)
    for h in range(nkv):
        den = jnp.sum(fq_ref[h] * z[h:h + 1, :], axis=1, keepdims=True)
        den_ref[h] += jnp.broadcast_to(den, den_ref.shape[1:])


def retention_decode_update(pool, layer, rows, fresh, q, k, v, log_g,
                            eps: float = _ret.EPS):
    """One token of ``b`` rows on their rows of the state pool, in place
    (``ops/retention.retention_decode_update_xla`` is the contract). A row
    aimed at the trash row starts from zeros like a fresh one and takes ONE
    block of it for all its steps (the block index stands still, so it is
    fetched once and written once): an idle slot costs a seventeenth of a
    live one's traffic, and nothing it reads is ever a number's source."""
    _float32_state(pool)
    b, nkv, d = k.shape
    g = q.shape[1] // nkv
    sublanes, width = pool.shape[2:]
    lanes = _lane_block(width)
    idle = rows == pool.shape[1] - 1
    fk = _ret.phi(k)                                            # [b, nkv, R]
    fq = _ret.phi(q.reshape(b, nkv, g, d))                   # [b, nkv, g, R]
    decay = jnp.exp(log_g.astype(F32))                          # [b, nkv]
    spare = sublanes - nkv * d - nkv
    column = lambda s, z: jnp.pad(jnp.concatenate(
        [s.reshape(b, nkv * d), z], axis=1), ((0, 0), (0, spare)))
    cols = jnp.stack(
        [column(v.astype(F32), jnp.ones((b, nkv), F32)),
         column(jnp.repeat(decay, d, axis=1), decay)], axis=-1)

    def state(i, j, layer, rows, fresh, idle):
        return (layer[0], rows[i], 0, jnp.where(idle[i] > 0, 0, j))

    block = pl.BlockSpec((None, None, sublanes, lanes), state)
    out = pl.BlockSpec((None, nkv, g, d), lambda i, j, *_: (i, 0, 0, 0))
    out_den = pl.BlockSpec((None, nkv, g, 128), lambda i, j, *_: (i, 0, 0, 0))
    pool, num, den = pl.pallas_call(
        functools.partial(_decode_kernel, nkv=nkv, d=d),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b, width // lanes),
            in_specs=[
                pl.BlockSpec((None, nkv, lanes), lambda i, j, *_: (i, 0, j)),
                pl.BlockSpec((None, nkv, g, lanes),
                             lambda i, j, *_: (i, 0, 0, j)),
                pl.BlockSpec((None, sublanes, 2), lambda i, j, *_: (i, 0, 0)),
                block],
            out_specs=[block, out, out_den]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, nkv, g, d), F32),
                   jax.ShapeDtypeStruct((b, nkv, g, 128), F32)],
        input_output_aliases={7: 0},     # 4 scalars, 3 vectors, the pool
        compiler_params=_params("arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="retention_decode_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32),
      (fresh | idle).astype(jnp.int32), idle.astype(jnp.int32), fk, fq,
      cols, pool)
    o = num / (den[..., :1] + eps)
    return pool, o.reshape(b, nkv * g, d)


# --------------------------------------------------------------------------- #
# many tokens of a row
# --------------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _groups(d: int):
    """``phi``'s rows cut where a row of the outer product starts on a whole
    128-lane tile of the state (3712 / 2688 / 1664 / 640 at ``d`` = 128):
    ``((first entry, entries, ((m, entry of its first product), ..)),
    ..)``."""
    starts, at = [], 0
    for m in range(d):
        starts.append(at)
        at += d - 8 * (m // 8)
    cuts = [m for m in range(0, d, 8) if starts[m] % 128 == 0] + [d]
    ends = starts + [at]
    return tuple((ends[a], ends[b] - ends[a],
                  tuple((m, starts[m] - ends[a]) for m in range(a, b)))
                 for a, b in zip(cuts, cuts[1:]))


def _phi_t(x_ref, head, out_ref, rows, d):
    """``phi^T`` of one group's rows into ``out_ref [entries, tokens]``:
    ``x_ref[head]`` (``head`` None: ``x_ref`` itself) is ``a^T [d, tokens]``
    float32; outer-product row ``m`` is ``a^T``'s row ``m``, broadcast down
    the sublanes, times its rows from ``8 (m // 8)`` on - the diagonal block
    as it is, the rest times ``sqrt(2)``."""
    at = (lambda s: x_ref[s, :]) if head is None \
        else (lambda s: x_ref[head, s, :])
    for m, entry in rows:
        first = 8 * (m // 8)
        row = at(pl.ds(m, 1))                               # [1, tokens]
        out_ref[entry:entry + 8, :] = row * at(pl.ds(first, 8))
        if first + 8 < d:
            out_ref[entry + 8:entry + d - first, :] = \
                (row * (2.0 ** 0.5)) * at(pl.ds(first + 8, d - first - 8))


def _chunk_kernel(layer, rows, fresh, qn_ref, qt_ref, kt_ref, vt_ref,
                  lanes_ref, lcol_ref, kept_ref, pool_in, pool_hbm, o_ref,
                  s_ref, z_ref, phi_ref, acc_ref, sem, *, nkv, d, g, eps, mm):
    """``qn [g, c, d]`` the group's queries, ``qt [g, d, c]`` and ``kt [d,
    c]`` float32 transposes, ``vt [d + 8, c]`` the values' transpose over a
    row of ones (the normaliser is that row's retention), ``lanes [8, c]``:
    the tile's running log-decay ``l``, ``exp(l)`` and ``exp(l_end - l)``
    along the lanes, ``lcol [c, 1]`` ``l`` down the sublanes, ``kept [d + 8,
    1]`` ``exp(l_end)``. ``s_ref [d + 8, R]``: the head's ``S`` over its
    ``z`` and seven rows of zeros, through the row's tiles."""
    del pool_in         # the aliased result IS the pool: read it there, so
    #                     that a head sees the z rows the head before wrote
    bi, h, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    row, lyr = rows[bi], layer[0]
    zrows = pl.ds(pl.multiple_of(nkv * d + (h // 8) * 8, 8), 8)
    mine = pl.ds(pl.multiple_of(h * d, 8), d)

    def copy(back: bool):
        """The head's ``S`` and the eight heads' ``z`` rows, both at once."""
        pairs = ((pool_hbm.at[lyr, row, mine, :], s_ref.at[pl.ds(0, d), :]),
                 (pool_hbm.at[lyr, row, zrows, :], z_ref))
        both = [pltpu.make_async_copy(*(p[::-1] if back else p), sem.at[n])
                for n, p in enumerate(pairs)]
        for cp in both:
            cp.start()
        for cp in both:
            cp.wait()

    @pl.when(c == 0)
    def _():
        copy(False)
        start = fresh[bi] > 0
        s_ref[0:d, :] = jnp.where(start, 0.0, s_ref[0:d, :])
        z = jnp.where(start, 0.0, z_ref[pl.ds(h % 8, 1), :])
        first = jax.lax.broadcasted_iota(jnp.int32, (8, z.shape[1]), 0) == 0
        s_ref[d:d + 8, :] = jnp.where(first, z, 0.0)

    l_row, into, to_end = (lanes_ref[n:n + 1, :] for n in range(3))
    tokens = l_row.shape[1]
    seg = lcol_ref[...] - l_row                                 # [t, s]
    causal = jax.lax.broadcasted_iota(jnp.int32, (tokens, tokens), 0) \
        >= jax.lax.broadcasted_iota(jnp.int32, (tokens, tokens), 1)
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    vt = vt_ref[...].astype(F32)
    kt = kt_ref[...].astype(mm)

    # against the state, a group of phi's entries at a time; then the group
    # takes the tile's own tokens
    for n, (lo, size, pieces) in enumerate(_groups(d)):
        s_now = s_ref[:, lo:lo + size].astype(mm)

        def head(i, carry, s_now=s_now, size=size, pieces=pieces, n=n):
            _phi_t(qt_ref, i, phi_ref, pieces, d)
            part = jnp.dot(s_now, phi_ref[0:size, :].astype(mm),
                           preferred_element_type=F32)
            acc_ref[i] = part if n == 0 else acc_ref[i] + part
            return carry

        jax.lax.fori_loop(0, g, head, 0)
        _phi_t(kt_ref, None, phi_ref, pieces, d)
        s_ref[:, lo:lo + size] = kept_ref[...] * s_ref[:, lo:lo + size] \
            + jax.lax.dot_general((vt * to_end).astype(mm),
                                  phi_ref[0:size, :].astype(mm), _NT,
                                  preferred_element_type=F32)

    def head(i, carry):
        qk = jnp.dot(qn_ref[i].astype(mm), kt, preferred_element_type=F32)
        a = (qk * qk * decay).astype(mm)                        # [t, s]
        both = acc_ref[i] * into + jax.lax.dot_general(
            vt.astype(mm), a, _NT, preferred_element_type=F32)
        o_ref[i] = (both[0:d, :] / (both[d:d + 1, :] + eps)) \
            .astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, g, head, 0)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        z_ref[pl.ds(h % 8, 1), :] = s_ref[d:d + 1, :]
        copy(True)


def retention_chunk(pool, layer, rows, fresh, q, k, v, log_g,
                    eps: float = _ret.EPS, tile: int = 128):
    """``t`` tokens of ``b`` rows on their rows of the state pool, in place
    (``ops/retention.retention_chunk_xla`` is the contract: a row's padding
    arrives with ``k = v = 0`` and ``log_g = 0``). The MXU's operands are in
    ``q``'s type (bfloat16 in a served engine, float32 where a test says
    so), every sum and the state in float32."""
    _float32_state(pool)
    b, t, nh, d = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    c = min(tile, -(-t // 8) * 8)
    pad = -t % c
    if pad:
        q, k, v, log_g = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                  * (a.ndim - 2)) for a in (q, k, v, log_g))
    nt = (t + pad) // c
    idle = rows == pool.shape[1] - 1
    # [b, nkv, nt, .., c, d]: a (row, head, tile) a block
    tiles = lambda a: a.reshape((b, nt, c) + a.shape[2:])
    qn = tiles(q).reshape(b, nt, c, nkv, g, d).transpose(0, 3, 1, 4, 2, 5)
    kn = tiles(k).transpose(0, 3, 1, 2, 4)                  # [b, j, nt, c, d]
    vn = tiles(v).transpose(0, 3, 1, 2, 4)
    l = jnp.cumsum(tiles(log_g.astype(F32)), axis=2) \
        .transpose(0, 3, 1, 2)                              # [b, j, nt, c]
    end = l[..., -1:]
    lanes = jnp.pad(jnp.stack([l, jnp.exp(l), jnp.exp(end - l)], axis=-2),
                    ((0, 0),) * 3 + ((0, 5), (0, 0)))
    vt = jnp.concatenate(
        [vn.swapaxes(-1, -2), jnp.ones((b, nkv, nt, 1, c), v.dtype),
         jnp.zeros((b, nkv, nt, 7, c), v.dtype)], axis=-2)
    kept = jnp.broadcast_to(jnp.exp(end)[..., None], (b, nkv, nt, d + 8, 1))
    width = pool.shape[3]
    biggest = max(size for _, size, _ in _groups(d))

    def spec(*shape):
        zeros = (0,) * len(shape)
        return pl.BlockSpec((None, None, None) + shape,
                            lambda bi, h, ci, *_: (bi, h, ci) + zeros)

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    pool, o = pl.pallas_call(
        functools.partial(_chunk_kernel, nkv=nkv, d=d, g=g, eps=eps,
                          mm=jnp.dtype(q.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(b, nkv, nt),
            in_specs=[spec(g, c, d), spec(g, d, c), spec(d, c),
                      spec(d + 8, c), spec(8, c), spec(c, 1),
                      spec(d + 8, 1), anywhere],
            out_specs=[anywhere, spec(g, d, c)],
            scratch_shapes=[pltpu.VMEM((d + 8, width), F32),
                            pltpu.VMEM((8, width), F32),
                            pltpu.VMEM((biggest, c), F32),
                            pltpu.VMEM((g, d + 8, c), F32),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((b, nkv, nt, g, d, c), F32)],
        input_output_aliases={10: 0},    # 3 scalars, 7 operands, the pool
        compiler_params=_params("arbitrary", "arbitrary", "arbitrary"),
        interpret=_interpret(),
        name="retention_chunk",
    )(jnp.asarray(layer, jnp.int32).reshape(1), rows.astype(jnp.int32),
      (fresh | idle).astype(jnp.int32), qn,
      qn.swapaxes(-1, -2).astype(F32), kn.swapaxes(-1, -2).astype(F32), vt,
      lanes, l[..., None], kept, pool)
    # [b, j, nt, i, d, c] -> [b, t, nh, d]
    o = o.transpose(0, 2, 5, 1, 3, 4).reshape(b, nt * c, nh, d)
    return pool, o[:, :t]


register("retention_decode_update", backend="pallas")(retention_decode_update)
register("retention_chunk", backend="pallas")(retention_chunk)
