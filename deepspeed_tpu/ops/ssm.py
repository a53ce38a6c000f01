"""State-space (Mamba-2 / SSD) ops: the recurrence a head runs over its
sequence, and the per-slot state pools it is served from.

A head with input ``x_t`` in R^P, state ``H`` in R^(P x N), one scalar
``A < 0`` and a per-token step ``dt_t > 0`` runs

    H_t = exp(dt_t A) H_(t-1) + dt_t x_t B_t^T        y_t = H_t C_t

(``B_t``, ``C_t`` in R^N are shared by the heads of a GROUP: ``G`` groups of
``H / G`` neighbouring heads each, head ``h`` reads group ``h // (H / G)``.
Every function here takes ``B`` and ``C`` with ONE group as ``[.., N]`` -
the program it traces is then what it was before groups were written - or
with ``G`` as ``[.., G, N]``. The skip ``D x_t`` is the caller's). Two forms
of it live here:

- :func:`ssd_chunked_scan`: many tokens a call, in blocks ("SSD",
  arXiv:2405.21060 section 6): inside a block the tokens meet through one
  masked ``[q, q]`` matrix a head, between blocks through the carried state.
  Plain ``jax.numpy``; float32 accumulation.
- the op ``ssm_decode_update``: one token of every row, on the state pool
  where it lies (``ops/pallas/ssm.py`` on a TPU, :func:`ssm_decode_update_xla`
  off it).

The pool. A serving engine keeps one fixed-size row a SEQUENCE SLOT a
layer: ``[layers, slots + 1, A, B]`` with no block axis; the last row is the
TRASH row, which rows that must write nothing are aimed at
(:func:`pool_rows`) - the state pool's block 0. The recurrent state is
stored ``[N, heads * P]`` (state dimension on sublanes, the flat
(head, channel) index on lanes: both minor dimensions are whole 128-tiles
at the published sizes, where ``[P, N]`` with ``P = 64`` would leave every
vector a token brings - ``x``, ``dt``, the decay - needing a lane-to-sublane
move in the kernel). A row may hold more under those ``N`` sublanes (the
family's convolution tail), addressed as a ``part``: ``(first sublane,
sublanes, lanes)``, block-aligned. ONE pool, because a pool that fits the
chip's fast memory (the 62 MB a pool of tails alone would be) is copied
there and back around every kernel that takes it - a whole-pool copy a
layer that no ``copy`` instruction shows (``copy-start``; PERF.md Findings,
PR 31). Only the four ops below touch a pool:
``state_rows_read``, ``state_rows_write``, ``ssm_decode_update`` and
``ssm_chunk_scan`` (many tokens of every row, read where they lie: off a
TPU the read and the blocked scan below; on one ``ops/pallas/ssm_scan.py``,
the row's state carried in fast memory through the segment's tokens; the
rows' new state comes back for ``state_rows_write``); an XLA
slice, gather or scatter on a carried ``[L, ...]`` pool can cost a copy of
the whole of it a layer (PERF.md Findings, PR 29).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .registry import get_op, register

F32 = jnp.float32


def pool_rows(slots, valid, pool) -> jnp.ndarray:
    """The pool row of each call row: its sequence's slot, or the trash row
    (the pool's last) where ``valid`` is False."""
    return jnp.where(valid, slots.astype(jnp.int32), pool.shape[1] - 1)


def state_to_heads(rows, heads: int):
    """Pool rows ``[b, N, heads * P]`` as the scan's ``[b, heads, P, N]``."""
    b, n, hp = rows.shape
    return rows.reshape(b, n, heads, hp // heads).transpose(0, 2, 3, 1)


def state_from_heads(h):
    """The scan's ``[b, heads, P, N]`` as pool rows ``[b, N, heads * P]``."""
    b, heads, p, n = h.shape
    return h.transpose(0, 3, 1, 2).reshape(b, n, heads * p)


# --------------------------------------------------------------------------- #
# many tokens a call
# --------------------------------------------------------------------------- #
def _by_group(scan, x, dt, A, B, C, h0, *more):
    """``scan`` (one group's: ``B``, ``C`` ``[b, t, N]``) over the ``G``
    groups of ``B``, ``C`` ``[b, t, G, N]``: the heads split ``[G, H / G]``
    and each group's run beside the others."""
    b, t, H, P = x.shape
    G = B.shape[2]
    assert H % G == 0, (H, G)
    y, h_t = jax.vmap(lambda *a: scan(*a, *more),
                      in_axes=(2, 2, 0, 2, 2, 1), out_axes=(2, 1))(
        x.reshape(b, t, G, H // G, P), dt.reshape(b, t, G, H // G),
        A.reshape(G, H // G), B, C,
        h0.reshape(b, G, H // G, P, h0.shape[-1]))
    return y.reshape(b, t, H, P), h_t.reshape(h0.shape)


def ssd_chunked_scan(x, dt, A, B, C, h0, chunk: int) -> Tuple:
    """The recurrence over ``t`` tokens of ``b`` rows in blocks of ``chunk``.

    ``x [b, t, H, P]``; ``dt [b, t, H]`` float32, after its softplus, and 0
    on a row's padding (a token with ``dt = 0`` neither decays nor feeds the
    state, so the state after the call is the state after the real tokens);
    ``A [H]`` float32, negative; ``B``, ``C`` ``[b, t, N]`` (one group) or
    ``[b, t, G, N]``; ``h0 [b, H, P, N]`` float32. Returns ``(y [b, t, H, P]
    float32, h_t [b, H, P, N])``.

    Per block, with ``cs`` the running sum of ``dt A`` inside it: token s
    reaches token t >= s decayed by ``exp(cs_t - cs_s)``, so ``y`` inside a
    block is ``((C B^T) * decay * dt) x`` - one masked ``[q, q]`` matrix a
    head -, the state entering the block adds ``exp(cs_t) H C_t``, and the
    block leaves ``exp(cs_q) H + sum_s exp(cs_q - cs_s) dt_s x_s B_s^T``.
    Every exponent is <= 0: nothing here can overflow."""
    if B.ndim == 4:
        return _by_group(ssd_chunked_scan, x, dt, A, B, C, h0, chunk)
    b, t, H, P = x.shape
    q = min(chunk, t)
    pad = -t % q
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (t + pad) // q
    x = x.reshape(b, nc, q, H, P)
    B, C = (a.reshape(b, nc, q, -1) for a in (B, C))
    dt = dt.astype(F32).reshape(b, nc, q, H)
    cs = jnp.cumsum(dt * A.astype(F32), axis=2)            # [b, nc, q, H]

    # inside the blocks
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]       # [b, nc, t, s, H]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bctn,bcsn->bcts", C, B, preferred_element_type=F32)
    mix = cb[..., None] * decay * dt[:, :, None, :, :]
    y = jnp.einsum("bctsh,bcshp->bcthp", mix, x, preferred_element_type=F32)

    # what each block adds to the state, and the state entering each block
    to_end = jnp.exp(cs[:, :, -1:, :] - cs) * dt            # [b, nc, q, H]
    local = jnp.einsum("bcsh,bcshp,bcsn->bchpn", to_end, x, B,
                       preferred_element_type=F32)
    block_decay = jnp.exp(cs[:, :, -1, :])                  # [b, nc, H]

    def carry(h, block):
        added, kept = block
        return kept[:, :, None, None] * h + added, h

    h_t, entering = lax.scan(
        carry, h0.astype(F32),
        (local.swapaxes(0, 1), block_decay.swapaxes(0, 1)))
    y = y + jnp.einsum("bctn,cbhpn,bcth->bcthp", C, entering, jnp.exp(cs),
                       preferred_element_type=F32)
    return y.reshape(b, nc * q, H, P)[:, :t], h_t


def ssm_recurrence(x, dt, A, B, C, h0) -> Tuple:
    """The same recurrence a token at a time (``lax.scan`` over ``t``):
    what :func:`ssd_chunked_scan` is tested against."""
    if B.ndim == 4:
        return _by_group(ssm_recurrence, x, dt, A, B, C, h0)
    A = A.astype(F32)

    def step(h, token):
        x_t, dt_t, b_t, c_t = token            # [b,H,P] [b,H] [b,N] [b,N]
        h = jnp.exp(dt_t * A)[:, :, None, None] * h + jnp.einsum(
            "bh,bhp,bn->bhpn", dt_t, x_t.astype(F32), b_t.astype(F32))
        return h, jnp.einsum("bhpn,bn->bhp", h, c_t.astype(F32))

    h_t, y = lax.scan(step, h0.astype(F32), tuple(
        a.swapaxes(0, 1) for a in (x, dt.astype(F32), B, C)))
    return y.swapaxes(0, 1), h_t


# --------------------------------------------------------------------------- #
# the pools' three ops: XLA references (off a TPU nothing has a layout to
# disagree with; the registry picks the Mosaic kernels on one)
# --------------------------------------------------------------------------- #
def _layer(layer):
    return jnp.asarray(layer, jnp.int32).reshape(())


def state_rows_read_xla(pool, layer, rows, part):
    """``part`` of ``pool[layer, rows]``: ``[b, sublanes, lanes]``."""
    first, sublanes, lanes = part
    return pool[_layer(layer), rows, first:first + sublanes, :lanes]


def state_rows_write_xla(pool, layer, rows, new, part):
    """``pool`` with ``new [b, sublanes, lanes]`` at ``part`` of ``[layer,
    rows]``. Rows that must write nothing arrive aimed at the trash row
    (:func:`pool_rows`), the one row several call rows may share."""
    first, sublanes, lanes = part
    return pool.at[_layer(layer), rows, first:first + sublanes, :lanes].set(
        new.astype(pool.dtype))


def _per_lane(vectors, h):
    """``B`` or ``C`` against the state ``h [b, N, HP]``: ``[b, N, 1]`` of
    one group, ``[b, N, HP]`` - each group's over its own lanes - of more."""
    vectors = vectors.astype(F32)
    if vectors.ndim == 2:
        return vectors[:, :, None]
    return jnp.repeat(vectors.swapaxes(1, 2),
                      h.shape[-1] // vectors.shape[1], axis=2)


def ssm_decode_update_xla(pool, layer, rows, fresh, decay, dtx, B, C):
    """One token of ``b`` rows on the state pool ``[L, S + 1, >= N, HP]``:
    row i's state, the first ``N`` sublanes of ``[layer, rows[i]]`` (zeros
    where ``fresh[i]``: a sequence's first token), becomes ``decay[i] * H + B[i] dtx[i]^T``
    (``decay``, ``dtx`` ``[b, HP]`` float32, per lane; ``B``, ``C`` ``[b,
    N]``, or ``[b, G, N]``: group ``g``'s over its ``HP / G`` lanes) and
    reads out ``y[i] = C[i]^T H``. Returns ``(pool, y [b, HP] float32)``."""
    layer, n = _layer(layer), B.shape[-1]
    h = jnp.where(fresh[:, None, None], 0.0, pool[layer, rows, :n])
    h = h * decay[:, None, :] + _per_lane(B, h) * dtx[:, None, :]
    y = jnp.sum(h * _per_lane(C, h), axis=1)
    return pool.at[layer, rows, :n].set(h.astype(pool.dtype)), y


def ssm_chunk_scan_xla(pool, layer, rows, fresh, x, dt, A, B, C, chunk):
    """Many tokens of ``b`` rows from the state pool: row i's state, the
    first ``N`` sublanes of ``[layer, rows[i]]`` (zeros where
    ``fresh[i]``), advanced over ``x [b, t, H, P]``, ``dt [b, t, H]``,
    ``B``, ``C`` (:func:`ssd_chunked_scan`'s arguments, blocked by
    ``chunk``). Returns ``(y [b, t, H * P] float32, the rows' new state [b,
    N, H * P] float32)``, the state in the pool's layout for
    ``state_rows_write``. The rows are read through the pool's own op and
    meet the scan head-major: two transposes of the state a call."""
    b, t, H, P = x.shape
    h0 = jnp.where(fresh[:, None, None, None], 0.0, state_to_heads(
        get_op("state_rows_read")(pool, layer, rows,
                                  (0, B.shape[-1], H * P)), H))
    y, h_t = ssd_chunked_scan(x, dt, A, B, C, h0, chunk)
    return y.reshape(b, t, H * P), state_from_heads(h_t)


register("state_rows_read", backend="xla")(state_rows_read_xla)
register("state_rows_write", backend="xla")(state_rows_write_xla)
register("ssm_decode_update", backend="xla")(ssm_decode_update_xla)
register("ssm_chunk_scan", backend="xla")(ssm_chunk_scan_xla)
