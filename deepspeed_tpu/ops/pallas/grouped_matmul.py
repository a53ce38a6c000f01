"""Grouped matmul of an expert bank: each row tile against ITS expert.

The rows a router sent to a bank of SwiGLU experts lie in an expert-major,
tile-aligned order (``moe/sharded_moe.py row_groups``): row tile ``i`` holds
rows of ONE expert, ``tile_expert[i]``, padded with zero rows to ``tile``,
and the first ``num_tiles`` tiles are in use, ``tile_rows[i]`` rows of each
(a prefix). The op computes, a tile at a time in sub-tiles of at most
``ROW_SUBTILE`` rows (an MXU pass; a sub-tile past the rows in use is
skipped), ``(silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]`` - bf16 operands,
float32 accumulation, every matmul's result rounded to the operands' dtype,
which is what ``xe @ w`` gives an expert's slab - over the tiles in use and
nothing else: the work follows the router's counts (prefetched scalars), not
the static size of the row buffer. Rows of a tile past ``num_tiles``, and of
a skipped sub-tile, are unspecified in the kernel's result (the reference
zeroes the first and computes the second: zeros in, zeros out).

The bank is read where it lies. ``w_*`` are the STACKED ``[L, E, ...]``
weights of every layer and ``layer`` one more prefetched scalar, the first
coordinate of every weight block (as ``paged_decode`` takes the pools): a
Mosaic operand is a whole buffer, and a layer's bank sliced out of the stack
for it would be copied first (PERF.md Findings, PR 41).

Grid ``(tiles, F blocks)``, F innermost: a step reads one ``[H, tf]`` block
of gate and of up and one ``[tf, H]`` block of down, and the tile's result
accumulates in VMEM over the F blocks. A tile past ``num_tiles`` skips its
compute and folds every index onto the last step in use, so Pallas elides
its DMAs: an expert's weights come once a tile of its rows.

A bank of TWO matrices (``w_gate`` None: ``moe/layer.py BANK``) computes
``relu(x @ w_up[e]) ** 2 @ w_down[e]`` the same way, one block of up and one
of down a step; the budgets count the matrices the bank has.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import interpret as _interpret, mxu_dot

ROW_SUBTILE = 128   # rows an MXU pass takes: what a larger tile is worked in

# VMEM a step's three weight blocks may take, double-buffered (the rest of
# the working set - a row tile in and out, the float32 accumulator, a step's
# gate and up - is a few MB at the widest cell). An expert's three matrices
# WHOLE where they fit the first: one contiguous read each and no pass over
# the accumulator (OLMoE 9.01 ms a tick against 9.35 in halves). Else blocks
# within the second: at Mixtral's widths 128 or 256 columns a step read the
# bank in 11.6 ms a tick, 512 in 12.2, and a 16-row call's 11.47 / 11.75 /
# 12.08 (my chip runs, PR 41; scripts/moe_dispatch_bench.py --forms
# --weight-vmem-mb=N). Two budgets fitted to the three cells' widths, no more.
_WHOLE_VMEM = 24 << 20
_BLOCK_VMEM = 6 << 20
_MATMUL = (((1,), (0,)), ((), ()))


def f_block(hidden: int, inter: int, itemsize: int = 2,
            matrices: int = 3) -> int:
    """Columns of gate / up (rows of down) a grid step reads: all of them
    where an expert's matrices fit ``_WHOLE_VMEM`` twice over (or ``inter``
    is no multiple of 128: a block is then the whole dimension), else the
    largest multiple of 128 that divides ``inter`` whose blocks (one a
    matrix of the bank: ``matrices``) fit ``_BLOCK_VMEM`` twice over (128 at
    the least)."""
    step = 2 * matrices * hidden * itemsize  # bytes a column, double-buffered
    if inter % 128 or inter * step <= _WHOLE_VMEM:
        return inter
    return max(tf for tf in range(128, inter, 128)
               if inter % tf == 0 and (tf * step <= _BLOCK_VMEM or tf == 128))


def _swiglu_rounded(g, u, dtype):
    """``silu(g) * u`` of two float32 accumulators with the roundings of the
    slab form: each matmul's result, the activation and the product are
    ``dtype`` values."""
    g, u = g.astype(dtype), u.astype(dtype)
    return (jax.nn.silu(g.astype(jnp.float32)).astype(dtype)
            * u).astype(dtype)


def _relu2_rounded(u, dtype):
    """``relu(u) ** 2`` of a float32 accumulator with the roundings of the
    slab form (``moe/layer.py relu2``): the matmul's result and the square
    are ``dtype`` values."""
    r = jax.nn.relu(u.astype(dtype))
    return r * r


def _kernel(tile_expert, tile_rows, num_tiles, layer, x_ref, *refs, nf, sub):
    """``refs``: the bank's weight blocks (gate, up, down - or up, down of a
    two-matrix bank), the result and the accumulator."""
    del tile_expert, layer          # the index maps read them
    *gate_up, wd_ref, o_ref, acc = refs
    t, f = pl.program_id(0), pl.program_id(1)

    @pl.when(t < num_tiles[0])
    def _compute():
        # the tile's rows in use are a prefix: a sub-tile past them is
        # padding, skipped (its rows of the result are never read)
        for r in range(x_ref.shape[0] // sub):
            @pl.when(r * sub < tile_rows[t])
            def _sub_tile(rows=slice(r * sub, (r + 1) * sub)):
                x = x_ref[rows, :]
                ups = [mxu_dot(x, w[...], _MATMUL) for w in gate_up]
                h = _swiglu_rounded(*ups, x.dtype) if len(ups) == 2 \
                    else _relu2_rounded(*ups, x.dtype)
                part = mxu_dot(h, wd_ref[...], _MATMUL)

                @pl.when(f == 0)
                def _first():
                    acc[rows, :] = part

                @pl.when(f > 0)
                def _more():
                    acc[rows, :] += part

        @pl.when(f == nf - 1)
        def _done():
            o_ref[...] = acc[...].astype(o_ref.dtype)


def moe_grouped_matmul(x: jnp.ndarray, w_gate: jnp.ndarray,
                       w_up: jnp.ndarray, w_down: jnp.ndarray,
                       tile_expert: jnp.ndarray, tile_rows: jnp.ndarray,
                       num_tiles: jnp.ndarray, layer, *,
                       tile: int) -> jnp.ndarray:
    """See the module docstring. ``x [tiles * tile, H]``; ``w_gate`` (None
    in a two-matrix bank), ``w_up`` ``[L, E, H, F]`` and ``w_down [L, E, F,
    H]``; ``tile_expert``, ``tile_rows`` ``[tiles]``, ``num_tiles`` and
    ``layer`` (scalars, int or traced) int32. Returns ``[tiles * tile, H]``
    in ``x``'s dtype."""
    places, hidden = x.shape
    n_exp, inter = w_up.shape[1], w_up.shape[3]
    tiles = places // tile
    assert tiles * tile == places and tiles > 0
    size = x.dtype.itemsize
    gate_up = [w_up] if w_gate is None else [w_gate, w_up]
    matrices = len(gate_up) + 1
    tf = f_block(hidden, inter, size, matrices)
    nf = inter // tf

    # index maps are called with one trailing arg per prefetched scalar
    def folded(t, f, num):
        """The step (tile, F block) whose blocks step (t, f) uses: its own
        while the tile is in use, the last one in use after."""
        live = t < num[0]
        return (jnp.minimum(t, jnp.maximum(num[0] - 1, 0)),
                jnp.where(live, f, nf - 1))

    def rows(t, f, experts, used, num, layer):
        return (folded(t, f, num)[0], 0)

    def weights(down):
        def block(t, f, experts, used, num, layer):
            t, f = folded(t, f, num)
            e = jnp.clip(experts[t], 0, n_exp - 1)
            return (layer[0], e, f, 0) if down else (layer[0], e, 0, f)
        return block

    need = (2 * matrices * hidden * tf * size   # the weight blocks, twice
            + 4 * tile * hidden * size      # the row tile in and out, twice
            + tile * hidden * 4             # the accumulator
            + 4 * tile * tf * 4)            # a step's gate, up and product
    return pl.pallas_call(
        functools.partial(_kernel, nf=nf, sub=min(tile, ROW_SUBTILE)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(tiles, nf),
            in_specs=[
                pl.BlockSpec((tile, hidden), rows),
                *(pl.BlockSpec((None, None, hidden, tf), weights(False))
                  for _ in gate_up),
                pl.BlockSpec((None, None, tf, hidden), weights(True))],
            out_specs=pl.BlockSpec((tile, hidden), rows),
            scratch_shapes=[pltpu.VMEM((tile, hidden), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=int(need * 1.25) + (4 << 20)),
        interpret=_interpret(),
        name="moe_grouped_matmul",
    )(tile_expert.astype(jnp.int32), tile_rows.astype(jnp.int32),
      jnp.asarray(num_tiles, jnp.int32).reshape(1),
      jnp.asarray(layer, jnp.int32).reshape(1), x, *gate_up, w_down)


def moe_grouped_matmul_xla(x: jnp.ndarray, w_gate: jnp.ndarray,
                           w_up: jnp.ndarray, w_down: jnp.ndarray,
                           tile_expert: jnp.ndarray, tile_rows: jnp.ndarray,
                           num_tiles: jnp.ndarray, layer, *,
                           tile: int) -> jnp.ndarray:
    """The ``jax.numpy`` reference: every tile against its expert's weights,
    gathered a tile at a time (``[tiles, H, F]``: for the CPU's sizes), the
    tiles not in use zero (``tile_rows`` only spares the kernel work)."""
    del tile_rows
    w_gate, w_up, w_down = (w if w is None else w[layer]
                            for w in (w_gate, w_up, w_down))
    tiles = x.shape[0] // tile
    xt = x.reshape(tiles, tile, x.shape[1])
    experts = jnp.clip(tile_expert, 0, w_up.shape[0] - 1)
    if w_gate is None:      # a two-matrix bank: relu(u) ** 2
        h = jnp.square(jax.nn.relu(
            jnp.einsum("tmh,thf->tmf", xt, w_up[experts])))
    else:
        g = jax.nn.silu(jnp.einsum("tmh,thf->tmf", xt, w_gate[experts]))
        h = g * jnp.einsum("tmh,thf->tmf", xt, w_up[experts])
    y = jnp.einsum("tmf,tfh->tmh", h, w_down[experts])
    live = jnp.arange(tiles) < jnp.asarray(num_tiles).reshape(())
    return jnp.where(live[:, None, None], y, 0).reshape(x.shape)


from ..registry import register  # noqa: E402

register("moe_grouped_matmul", backend="pallas")(moe_grouped_matmul)
register("moe_grouped_matmul", backend="xla")(moe_grouped_matmul_xla)
