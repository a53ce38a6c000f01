"""The gated delta rule with a per-channel decay (Kimi Delta Attention,
arXiv:2510.26692; the delta rule of arXiv:2406.06484 under the decay of a
gated linear attention), and the per-slot state pool it is served from.

A head with keys ``k_t``, queries ``q_t`` in R^dk, values ``v_t`` in R^dv, a
decay ``a_t = exp(log_a_t)`` in (0, 1]^dk - one number a CHANNEL of the key -
and a step ``beta_t`` in [0, 2] keeps a state ``S`` in R^(dk x dv):

    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_(t-1) + beta_t k_t v_t^T
    o_t = S_t^T q_t

The update is no rank-one ADD (``ops/ssm.py``'s and ``ops/retention.py``'s
are ``S <- g S + outer``): the decayed state is first read at ``k_t``, and
what is written is the value's distance from that reading. A token a time:

    S <- Diag(a) S;   r = beta (v - S^T k);   S <- S + k r^T;   o = S^T q

Many tokens at once, in tiles of ``C`` (the WY / UT transform): with ``g``
the running sum of ``log_a`` inside a tile and ``A_ts = sum_c k_t[c] k_s[c]
exp(g_t[c] - g_s[c])`` for ``s < t``, the tile's pseudo-values ``r`` solve
``(I + Diag(beta) A) R = Diag(beta) (V - (K * e^g) S_0)``, so with ``T = (I
+ Diag(beta) A)^-1``, ``W = T (beta K e^g)`` and ``U = T (beta V)``:

    R = U - W S_0          O = (Q e^g) S_0 + tril(P) R
    S_C = Diag(e^(g_C)) S_0 + (K e^(g_C - g))^T R

(``P`` is ``A`` with queries on its rows and the diagonal kept). ``A`` and
``P`` are NEVER formed as ``(K e^g)(K e^-g)^T``: random weights make the
decay strong (``log_a`` of -5 a token is common), ``e^-g`` over a tile is
past float32, and what is wanted is only ever a product with ``g_t - g_s <=
0``. :func:`decayed_products` takes the differences themselves inside
sub-blocks of 16 tokens and, between the two halves of a longer block, both
factors relative to the boundary between them - every exponent is <= 0.

Three forms live here, all float32 accumulation: :func:`delta_recurrence`
(a token at a time; the truth the other is tested against),
:func:`delta_chunked` (the tiles above), and the pool's two ops' XLA
references.

The pool. One row a SEQUENCE SLOT a layer with no block axis, ``[layers,
slots + 1, dk + tail, heads * dv]`` (``ops/ssm.py`` has the protocol: the
last row the trash row, :func:`~.ssm.pool_rows`, a ``part``): head ``h``'s
``S`` on the first ``dk`` sublanes of lanes ``[h dv, (h + 1) dv)`` - the
key's channel on sublanes, so the decay and the key are columns and the
value, the reading and the output rows of whole 128-lane tiles at the
published ``dv`` = 128 - and the family's convolution tail under them
(``models/_state.py tail_part``). 4.19 MB of state a slot a layer in float32
at 64 heads of 128 x 128. Only ``delta_decode_update`` and ``delta_chunk``
(and the tail's ``state_rows_read`` / ``state_rows_write``) touch a pool.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register

F32 = jnp.float32
SUB = 16        # tokens whose decay differences are taken one by one


# --------------------------------------------------------------------------- #
# the layout
# --------------------------------------------------------------------------- #
def state_to_heads(rows, heads: int):
    """Pool rows ``[b, dk, heads * dv]`` as ``S [b, heads, dk, dv]``."""
    b, dk, width = rows.shape
    return rows.reshape(b, dk, heads, width // heads).transpose(0, 2, 1, 3)


def state_from_heads(S):
    """``S [b, heads, dk, dv]`` as pool rows ``[b, dk, heads * dv]``."""
    b, heads, dk, dv = S.shape
    return S.transpose(0, 2, 1, 3).reshape(b, dk, heads * dv)


# --------------------------------------------------------------------------- #
# a token at a time
# --------------------------------------------------------------------------- #
def delta_step(S, q, k, v, log_a, beta):
    """ONE token of every row: ``S [b, H, dk, dv]`` float32, ``q``, ``k``,
    ``log_a`` ``[b, H, dk]``, ``v [b, H, dv]``, ``beta [b, H]``. Returns
    ``(S, o [b, H, dv])``."""
    q, k, v = (a.astype(F32) for a in (q, k, v))
    S = jnp.exp(log_a.astype(F32))[..., None] * S
    r = beta.astype(F32)[..., None] * (
        v - jnp.einsum("bhkv,bhk->bhv", S, k, preferred_element_type=F32))
    S = S + k[..., None] * r[:, :, None, :]
    return S, jnp.einsum("bhkv,bhk->bhv", S, q, preferred_element_type=F32)


def delta_recurrence(q, k, v, log_a, beta, S0) -> Tuple:
    """The recurrence a token at a time (``lax.scan`` over ``t``) from ``S0
    [b, H, dk, dv]``: ``q``, ``k``, ``log_a`` ``[b, t, H, dk]``, ``v [b, t,
    H, dv]``, ``beta [b, t, H]``. A token with ``beta = 0`` and ``log_a =
    0`` (a row's padding, as the family makes it) leaves the state as it
    was. Returns ``(o [b, t, H, dv] float32, S)``."""
    S, o = lax.scan(lambda S, token: delta_step(S, *token), S0.astype(F32),
                    tuple(a.swapaxes(0, 1) for a in (q, k, v, log_a, beta)))
    return o.swapaxes(0, 1), S


# --------------------------------------------------------------------------- #
# many tokens a call
# --------------------------------------------------------------------------- #
def decayed_products(x, k, g, sub: int = SUB):
    """``M_ts = sum_c x_t[c] k_s[c] exp(g_t[c] - g_s[c])`` for ``s <= t``
    and 0 above the diagonal: ``x``, ``k``, ``g`` ``[.., n, dk]`` float32,
    ``g`` non-increasing along ``n``. Blocks of at most ``sub`` tokens take
    each difference before its exponential; a longer block is its two halves
    and, between them, ``(x e^(g - m)) (k e^(m - g))^T`` with ``m`` the
    running sum at the left half's last token: ``g_t - m <= 0`` on the
    right, ``m - g_s <= 0`` on the left."""
    n = x.shape[-2]
    if n <= sub:
        lower = jnp.tril(jnp.ones((n, n), bool))[..., None]
        decay = jnp.exp(jnp.where(
            lower, g[..., :, None, :] - g[..., None, :, :], -jnp.inf))
        return jnp.einsum("...tc,...tsc,...sc->...ts", x, decay, k,
                          preferred_element_type=F32)
    h = n // 2
    mid = g[..., h - 1:h, :]
    top = decayed_products(x[..., :h, :], k[..., :h, :], g[..., :h, :], sub)
    bottom = decayed_products(x[..., h:, :], k[..., h:, :], g[..., h:, :],
                              sub)
    cross = jnp.einsum("...tc,...sc->...ts",
                       x[..., h:, :] * jnp.exp(g[..., h:, :] - mid),
                       k[..., :h, :] * jnp.exp(mid - g[..., :h, :]),
                       preferred_element_type=F32)
    return jnp.concatenate([
        jnp.concatenate([top, jnp.zeros(top.shape[:-1] + (n - h,), F32)], -1),
        jnp.concatenate([cross, bottom], -1)], -2)


def unit_lower_inverse(L, base: int = SUB):
    """``(I + L)^-1`` of strictly lower-triangular ``L [.., n, n]``: forward
    substitution row by row up to ``base`` rows (exact: no power of ``L`` is
    formed, and with ``beta`` up to 2 its powers grow), two halves and
    ``-B^-1 L_21 A^-1`` between them beyond."""
    n = L.shape[-1]
    if n <= base:
        eye = jnp.eye(n, dtype=L.dtype)
        rows = [jnp.broadcast_to(eye[0], L.shape[:-2] + (n,))]
        for i in range(1, n):
            rows.append(eye[i] - jnp.einsum(
                "...s,...sj->...j", L[..., i, :i], jnp.stack(rows, -2),
                preferred_element_type=F32))
        return jnp.stack(rows, -2)
    h = n // 2
    A, B = unit_lower_inverse(
        jnp.stack([L[..., :h, :h], L[..., h:, h:]]), base)
    mm = lambda a, b: jnp.einsum("...ij,...jk->...ik", a, b,
                                 preferred_element_type=F32)
    return jnp.concatenate([
        jnp.concatenate([A, jnp.zeros_like(A)], -1),
        jnp.concatenate([-mm(mm(B, L[..., h:, :h]), A), B], -1)], -2)


# the chunked form's tile, in tokens: how it is blocked, not a result. 64
# beats 128 at the served chunk of 512 (scripts/delta_kernel_bench.py on the
# chip); a shorter call takes a smaller one (``_tile``)
TILE = 64


def _tile(t: int, tile: int) -> int:
    """The tile a call of ``t`` tokens runs in: ``tile`` where the call is
    that long, else the least of 8, 16, 32, .. that holds it."""
    if t >= tile:
        return tile
    c = min(SUB, -(-t // 8) * 8)
    while c < t:
        c *= 2
    return min(c, tile)


def delta_chunked(q, k, v, log_a, beta, S0,
                  tile: Optional[int] = None) -> Tuple:
    """The chunked form in tiles of ``tile`` tokens (None: ``TILE``; a power
    of two times ``SUB``, or less than ``SUB``): what is the same for every
    tile of the call - ``T``, ``W``, ``U``, ``P`` - in one batch, then the
    state carried tile to tile (``lax.scan``). Shapes and the padding rule as
    :func:`delta_recurrence`. Returns ``(o [b, t, H, dv] float32, S)``."""
    b, t, H, dk = q.shape
    dv = v.shape[-1]
    c = _tile(t, tile or TILE)
    pad = -t % c
    if pad:
        q, k, v, log_a, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, log_a, beta))
    nt = (t + pad) // c
    # [nt, b, H, c, ..]: a (tile, row, head) a matrix
    tiles = lambda a: jnp.moveaxis(
        a.astype(F32).reshape((b, nt, c) + a.shape[2:]), 3, 2).swapaxes(0, 1)
    q, k, v, log_a = map(tiles, (q, k, v, log_a))
    beta = tiles(beta[..., None])                       # [nt, b, H, c, 1]
    g = jnp.cumsum(log_a, axis=-2)
    both = decayed_products(jnp.stack([q, k]), k, g)    # [2, nt, b, H, c, c]
    P, A = both[0], jnp.tril(both[1], -1)
    T = unit_lower_inverse(beta * A)
    mm = lambda a, b_: jnp.einsum("...ij,...jk->...ik", a, b_,
                                  preferred_element_type=F32)
    into = jnp.exp(g)
    W, U = mm(T, beta * k * into), mm(T, beta * v)
    last = g[..., -1:, :]
    to_end, kept = k * jnp.exp(last - g), jnp.exp(last)     # [.., 1, dk]

    def step(S, tile_):
        W_, U_, P_, Q_, K_, kept_ = tile_
        R = U_ - mm(W_, S)
        o = mm(Q_, S) + mm(P_, R)
        S = kept_.swapaxes(-1, -2) * S + jnp.einsum(
            "...ck,...cv->...kv", K_, R, preferred_element_type=F32)
        return S, o

    S, o = lax.scan(step, S0.astype(F32), (W, U, P, q * into, to_end, kept))
    # [nt, b, H, c, dv] -> [b, t, H, dv]
    o = jnp.moveaxis(o.swapaxes(0, 1), 2, 3).reshape(b, nt * c, H, dv)
    return o[:, :t], S


# --------------------------------------------------------------------------- #
# the pool's two ops: XLA references
# --------------------------------------------------------------------------- #
def _layer(layer):
    return jnp.asarray(layer, jnp.int32).reshape(())


def _read(pool, layer, rows, fresh, heads: int, dk: int):
    S = state_to_heads(pool[_layer(layer), rows, :dk].astype(F32), heads)
    return jnp.where(fresh[:, None, None, None], 0.0, S)


def _write(pool, layer, rows, S):
    dk = S.shape[2]
    return pool.at[_layer(layer), rows, :dk].set(
        state_from_heads(S).astype(pool.dtype))


def delta_decode_update_xla(pool, layer, rows, fresh, q, k, v, log_a, beta):
    """One token of ``b`` rows on the state pool: row i's state, the first
    ``dk`` sublanes of ``[layer, rows[i]]`` (zeros where ``fresh[i]``: a
    sequence's first token), takes :func:`delta_step`. ``q``, ``k``,
    ``log_a`` ``[b, H, dk]``, ``v [b, H, dv]``, ``beta [b, H]``. Rows that
    must write nothing arrive aimed at the trash row. Returns ``(pool, o [b,
    H, dv] float32)``."""
    H, dk = k.shape[1:]
    S, o = delta_step(_read(pool, layer, rows, fresh, H, dk), q, k, v, log_a,
                      beta)
    return _write(pool, layer, rows, S), o


def delta_chunk_xla(pool, layer, rows, fresh, q, k, v, log_a, beta,
                    tile: Optional[int] = None):
    """``t`` tokens of ``b`` rows on the state pool, in tiles
    (:func:`delta_chunked`); a row's padding arrives with ``beta = 0`` and
    ``log_a = 0``. Returns ``(pool, o [b, t, H, dv] float32)``."""
    H, dk = k.shape[2:]
    o, S = delta_chunked(q, k, v, log_a, beta,
                         _read(pool, layer, rows, fresh, H, dk), tile)
    return _write(pool, layer, rows, S), o


register("delta_decode_update", backend="xla")(delta_decode_update_xla)
register("delta_chunk", backend="xla")(delta_chunk_xla)
