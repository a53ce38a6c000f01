"""Power retention (Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239): a gated linear attention of degree 2, and the
per-slot state pool it is served from.

A key-value head with keys ``k_t``, values ``v_t`` in R^d and a scalar gate
``g_t = exp(log_g_t)`` in (0, 1], and each query head ``q_t`` of its group:

    a_ts = exp(sum_{r=s+1..t} log g_r) * (q_t . k_s)^2          s <= t
    o_t  = sum_s a_ts v_s / (sum_s a_ts + eps)

(``p`` = 2 is even, so every ``a_ts`` is non-negative and the quotient is a
weighted mean of values; a scale on ``q . k`` cancels in it.) The same
numbers as a recurrence over a FIXED state, which is what makes the layer
linear in the context: with ``phi`` a map for which ``phi(a) . phi(b) =
(a . b)^2``,

    S_t = g_t S_{t-1} + v_t phi(k_t)^T   [d, R]     z_t = g_t z_{t-1} + phi(k_t)
    o_t = S_t phi(q_t) / (z_t . phi(q_t) + eps)

:func:`phi` is the symmetric map in a TILED upper-triangular layout: the
products ``a_m a_n`` for ``n >= 8 * (m // 8)`` - row ``m`` of the outer
product from the start of its own block of 8 on -, weighted 1 inside the
diagonal block (both orders are there) and ``sqrt(2)`` beyond it (one stands
for two). ``R = sum_j 8 (d - 8 j)`` = 8704 at ``d`` = 128, where the least
is 8256 and the whole outer product 16384: the transposed map ``phi^T [R,
tokens]`` is then whole sublane tiles of ``a`` times one broadcast row,
which is how the chunk kernel forms it in VMEM.

Four forms live here, all float32 accumulation: :func:`retention_quadratic`
(the equations above, whole sequences, no state), :func:`retention_chunked`
(within a tile the quadratic form, across tiles the state),
:func:`retention_recurrence` (a token at a time; what the other two are
tested against), and the pool's two ops' XLA references.

The pool. One row a SEQUENCE SLOT a layer, ``[layers, slots + 1, nkv * d +
8 * ceil(nkv / 8), R]`` (``ops/ssm.py`` has the protocol: no block axis, the
last row the trash row, :func:`~.ssm.pool_rows`): head ``j``'s ``S`` on
sublanes ``[j d, (j + 1) d)`` - the value index on sublanes, ``phi``'s on
lanes -, and every head's ``z`` one sublane each under them. 35.9 MB a slot
a layer in float32 at 8 heads of 128. Only ``retention_decode_update`` and
``retention_chunk`` touch a pool (``ops/pallas/retention.py`` on a TPU).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .registry import register

F32 = jnp.float32
EPS = 1e-6         # the normaliser's (the package's ``sum_of_keys`` quotient)


# --------------------------------------------------------------------------- #
# the layout
# --------------------------------------------------------------------------- #
def phi_rows(d: int) -> int:
    """Entries of :func:`phi` of a ``d``-vector (``d`` a multiple of 8)."""
    assert d % 8 == 0, d
    return sum(8 * (d - 8 * j) for j in range(d // 8))


@functools.lru_cache(maxsize=None)
def phi_index(d: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(m, n, weight)``, each ``[R]``: entry ``r`` of :func:`phi` is
    ``weight[r] * a[m[r]] * a[n[r]]``. Row ``m``'s entries are contiguous,
    ``n`` from ``8 * (m // 8)`` up."""
    ms, ns, ws = [], [], []
    for m in range(d):
        first = 8 * (m // 8)
        n = np.arange(first, d)
        ms.append(np.full_like(n, m))
        ns.append(n)
        ws.append(np.where(n < first + 8, 1.0, np.sqrt(2.0)))
    return (np.concatenate(ms).astype(np.int32),
            np.concatenate(ns).astype(np.int32),
            np.concatenate(ws).astype(np.float32))


def phi(a) -> jnp.ndarray:
    """``a [.., d]`` -> ``[.., R]`` float32 with ``phi(a) . phi(b) = (a .
    b)^2`` (to float32 rounding): block ``j``'s 8 rows of the outer product
    from column ``8 j`` on, one block after another."""
    d = a.shape[-1]
    a = a.astype(F32)
    # (the weights go into each block's own product: one pass writes phi)
    beyond = lambda j: np.where(np.arange(8 * j, d) < 8 * j + 8, 1.0,
                                np.sqrt(2.0)).astype(np.float32)
    parts = [(a[..., 8 * j:8 * j + 8, None]
              * (a[..., None, 8 * j:] * beyond(j)))
             .reshape(a.shape[:-1] + (-1,)) for j in range(d // 8)]
    return jnp.concatenate(parts, axis=-1)


def state_sublanes(nkv: int, d: int) -> int:
    """Sublanes of a slot's row: ``nkv`` heads' ``S`` and their ``z``."""
    return nkv * d + 8 * -(-nkv // 8)


def state_shape(layers: int, slots: int, nkv: int, d: int) -> Tuple[int, ...]:
    """The pool of ``slots`` sequence slots and the trash row."""
    return (layers, slots + 1, state_sublanes(nkv, d), phi_rows(d))


def state_to_heads(rows, nkv: int, d: int):
    """Pool rows ``[b, A, R]`` as ``(S [b, nkv, d, R], z [b, nkv, R])``."""
    b, _, r = rows.shape
    return (rows[:, :nkv * d].reshape(b, nkv, d, r),
            rows[:, nkv * d:nkv * d + nkv])


def state_from_heads(S, z, sublanes: int):
    """The inverse of :func:`state_to_heads` (zeros on the spare sublanes)."""
    b, nkv, d, r = S.shape
    rows = jnp.concatenate([S.reshape(b, nkv * d, r), z], axis=1)
    return jnp.pad(rows, ((0, 0), (0, sublanes - rows.shape[1]), (0, 0)))


# --------------------------------------------------------------------------- #
# the three forms over whole rows of tokens
# --------------------------------------------------------------------------- #
def _grouped(q, nkv: int):
    """``q [b, t, nh, d]`` as ``[b, t, nkv, nh / nkv, d]``: query head ``j g
    + i`` is head ``i`` of key-value head ``j``'s group."""
    b, t, nh, d = q.shape
    return q.reshape(b, t, nkv, nh // nkv, d)


def retention_quadratic(q, k, v, log_g, eps: float = EPS, degree: int = 2):
    """The equations, whole sequences from an empty state: ``q [b, t, nh,
    d]``, ``k``, ``v`` ``[b, t, nkv, d]``, ``log_g [b, t, nkv]`` float32 ->
    ``o [b, t, nh, d]`` float32. ``[b, nh, t, t]`` of memory."""
    b, t, nh, d = q.shape
    nkv = k.shape[2]
    cum = jnp.cumsum(log_g.astype(F32), axis=1)                 # [b, t, nkv]
    seg = cum[:, :, None, :] - cum[:, None, :, :]               # [b, t, s, j]
    causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    qk = jnp.einsum("btjid,bsjd->btsji", _grouped(q, nkv).astype(F32),
                    k.astype(F32), preferred_element_type=F32)
    a = decay[..., None] * qk ** degree                     # [b, t, s, j, i]
    num = jnp.einsum("btsji,bsjd->btjid", a, v.astype(F32),
                     preferred_element_type=F32)
    den = jnp.sum(a, axis=2)[..., None] + eps
    return (num / den).reshape(b, t, nh, d)


def retention_recurrence(q, k, v, log_g, S0, z0, eps: float = EPS):
    """The recurrence a token at a time (``lax.scan``) from ``(S0 [b, nkv,
    d, R], z0 [b, nkv, R])``: ``(o [b, t, nh, d], S, z)``."""
    b, t, nh, d = q.shape
    nkv = k.shape[2]

    def step(carry, token):
        S, z = carry
        q_t, k_t, v_t, g_t = token
        fk = phi(k_t)                                           # [b, nkv, R]
        g = jnp.exp(g_t.astype(F32))
        S = g[..., None, None] * S + v_t.astype(F32)[..., None] \
            * fk[:, :, None, :]
        z = g[..., None] * z + fk
        fq = phi(q_t.reshape(b, nkv, nh // nkv, d))          # [b, nkv, G, R]
        num = jnp.einsum("bjir,bjdr->bjid", fq, S,
                         preferred_element_type=F32)
        den = jnp.einsum("bjir,bjr->bji", fq, z,
                         preferred_element_type=F32)[..., None] + eps
        return (S, z), (num / den).reshape(b, nh, d)

    (S, z), o = lax.scan(step, (S0.astype(F32), z0.astype(F32)), tuple(
        a.swapaxes(0, 1) for a in (q, k, v, log_g)))
    return o.swapaxes(0, 1), S, z


def retention_chunked(q, k, v, log_g, S0, z0, tile: int = 128,
                      eps: float = EPS):
    """The chunked form: tiles of ``tile`` tokens, inside a tile the
    quadratic form and between tiles the state - what the chunk kernel
    computes, in plain ``jax.numpy`` with ``phi`` of a tile's rows
    materialised. A token with ``k = v = 0`` and ``log_g = 0`` (a row's
    padding, as the family makes it) neither decays nor feeds the state.
    Returns ``(o [b, t, nh, d] float32, S, z)``."""
    b, t, nh, d = q.shape
    nkv = k.shape[2]
    c = min(tile, t)
    pad = -t % c
    if pad:
        q, k, v, log_g = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                                  * (a.ndim - 2)) for a in (q, k, v, log_g))
    nt = (t + pad) // c
    tiles = lambda a: a.reshape((b, nt, c) + a.shape[2:]).swapaxes(0, 1)
    causal = jnp.tril(jnp.ones((c, c), bool))[None, :, :, None]

    def step(carry, tile_):
        S, z = carry
        q_c, k_c, v_c, g_c = tile_
        q_c = _grouped(q_c, nkv).astype(F32)                # [b, c, j, i, d]
        k_c, v_c = k_c.astype(F32), v_c.astype(F32)
        l = jnp.cumsum(g_c.astype(F32), axis=1)                 # [b, c, j]
        decay = jnp.exp(jnp.where(causal, l[:, :, None] - l[:, None], -jnp.inf))
        qk = jnp.einsum("btjid,bsjd->btsji", q_c, k_c,
                        preferred_element_type=F32)
        a = decay[..., None] * qk * qk
        fq, fk = phi(q_c), phi(k_c)
        into = jnp.exp(l)[..., None, None]                      # [b, c, j, 1, 1]
        num = jnp.einsum("btsji,bsjd->btjid", a, v_c,
                         preferred_element_type=F32) \
            + into * jnp.einsum("btjir,bjdr->btjid", fq, S,
                                preferred_element_type=F32)
        den = jnp.sum(a, axis=2) + into[..., 0] * jnp.einsum(
            "btjir,bjr->btji", fq, z, preferred_element_type=F32)
        to_end = jnp.exp(l[:, -1:] - l)                         # [b, c, j]
        kept = jnp.exp(l[:, -1])                                # [b, j]
        S = kept[..., None, None] * S + jnp.einsum(
            "bsj,bsjd,bsjr->bjdr", to_end, v_c, fk,
            preferred_element_type=F32)
        z = kept[..., None] * z + jnp.einsum(
            "bsj,bsjr->bjr", to_end, fk, preferred_element_type=F32)
        return (S, z), num / (den[..., None] + eps)

    (S, z), o = lax.scan(step, (S0.astype(F32), z0.astype(F32)),
                         tuple(map(tiles, (q, k, v, log_g))))
    return o.swapaxes(0, 1).reshape(b, nt * c, nh, d)[:, :t], S, z


# --------------------------------------------------------------------------- #
# the pool's two ops: XLA references
# --------------------------------------------------------------------------- #
def _layer(layer):
    return jnp.asarray(layer, jnp.int32).reshape(())


def _read(pool, layer, rows, fresh, nkv: int, d: int):
    S, z = state_to_heads(pool[_layer(layer), rows].astype(F32), nkv, d)
    return (jnp.where(fresh[:, None, None, None], 0.0, S),
            jnp.where(fresh[:, None, None], 0.0, z))


def _write(pool, layer, rows, S, z):
    return pool.at[_layer(layer), rows].set(
        state_from_heads(S, z, pool.shape[2]).astype(pool.dtype))


def retention_decode_update_xla(pool, layer, rows, fresh, q, k, v, log_g,
                                eps: float = EPS):
    """One token of ``b`` rows on the state pool: row i's state at ``[layer,
    rows[i]]`` (zeros where ``fresh[i]``: a sequence's first token) is
    decayed by ``exp(log_g[i])``, takes ``v[i] phi(k[i])^T`` and is queried
    by the row's ``nh`` query heads. ``q [b, nh, d]``, ``k``, ``v`` ``[b,
    nkv, d]``, ``log_g [b, nkv]`` float32. Rows that must write nothing
    arrive aimed at the trash row. Returns ``(pool, o [b, nh, d]
    float32)``."""
    nkv, d = k.shape[1:]
    S, z = _read(pool, layer, rows, fresh, nkv, d)
    o, S, z = retention_recurrence(q[:, None], k[:, None], v[:, None],
                                   log_g[:, None], S, z, eps)
    return _write(pool, layer, rows, S, z), o[:, 0]


def retention_chunk_xla(pool, layer, rows, fresh, q, k, v, log_g,
                        eps: float = EPS, tile: int = 128):
    """``t`` tokens of ``b`` rows on the state pool, in tiles
    (:func:`retention_chunked`): ``q [b, t, nh, d]``, ``k``, ``v`` ``[b, t,
    nkv, d]``, ``log_g [b, t, nkv]``; a row's padding arrives with ``k = v =
    0`` and ``log_g = 0``. Returns ``(pool, o [b, t, nh, d] float32)``."""
    nkv, d = k.shape[2:]
    S, z = _read(pool, layer, rows, fresh, nkv, d)
    o, S, z = retention_chunked(q, k, v, log_g, S, z, tile, eps)
    return _write(pool, layer, rows, S, z), o


register("retention_decode_update", backend="xla")(
    retention_decode_update_xla)
register("retention_chunk", backend="xla")(retention_chunk_xla)
