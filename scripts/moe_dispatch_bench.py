#!/usr/bin/env python
"""MoE dispatch: dense one-hot einsum vs compacted gather/scatter.

VERDICT r3 item 6: SURVEY §2.4 lists the reference's dedicated MoE
dispatch/top-k kernels (``inference/v2/kernels/ragged_ops/top_k_gating``,
``moe_scatter``, ``moe_gather``) as native-equivalent targets. Our MOELayer
dispatches with dense einsums ([T,E,C]·[T,H] → [E,C,H]) — MXU-friendly but
O(T·E·C·H) flops. The compacted alternative (what a Pallas scatter kernel
would compute) builds the [E,C] token index table from the gating output and
uses gather / scatter-add — O(k·T·H) memory movement, no E·C blowup.

``--forms`` (PR 41) times the EXPERT BANK of a no-drop serving call at the
three MoE cells' shapes, kernels alone, in three forms - today's dense slabs
(every expert over a slab as long as the call), ``jax.lax.ragged_dot`` as XLA
compiles it over rows sorted by expert, and the Mosaic grouped matmul
(``ops/pallas/grouped_matmul.py``) - each through a layer scan over a stacked
bank, as the serving forward meets it; and the whole MoE layer around the
slab and the grouped form (gating, gather, bank, combine).

Without the flag this script times BOTH dispatch paths end-to-end (gating →
dispatch → 2-matmul expert FFN → combine) at realistic shapes and prints one
JSON line, so the einsum-vs-kernel question is answered with data
(PERF.md records the verdict: implement the Pallas kernel only if compact
wins and XLA's lowering of it leaves time on the table).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _probe_common import finalize  # noqa: E402

RESULT = {"metric": "moe_dispatch_best_impl", "value": 0.0,
          "unit": "einsum_over_compact_speedup", "vs_baseline": None,
          "detail": {}}


# the MoE cells' calls (PERF.md section 4): name -> (rows, router width,
# experts held, experts a token, hidden, expert width, layers of the cell)
BANK_SHAPES = {
    "mixtral_272": (272, 8, 8, 2, 4096, 14336, 3),
    "mixtral_16": (16, 8, 8, 2, 4096, 14336, 3),
    "olmoe_272": (272, 64, 64, 8, 2048, 1024, 8),
    "olmoe_16": (16, 64, 64, 8, 2048, 1024, 8),
    "keye_520": (520, 128, 16, 8, 2048, 768, 12),
    "keye_8": (8, 128, 16, 8, 2048, 768, 12),
}


def bank_forms(shapes=None, steps: int = 5, tiles=(), skew: float = 0.0):
    """ms a call's LAYERS (the cell's depth: a tick's) of each form at each
    of ``shapes`` (names of ``BANK_SHAPES``), on seeded weights and a random
    router's groups.
    ``tiles``: row tiles to time the grouped kernel at beside the layer's
    own choice (``sharded_moe.row_tile``). ``skew``: the spread of a bias
    on each expert's logit (0: a uniform router; the cells' routers, on
    hidden states that resemble each other, are not)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepspeed_tpu.moe.layer import BANK, MoELayer
    from deepspeed_tpu.moe.sharded_moe import (row_groups, row_tile,
                                               top_k_gating_compact)
    from deepspeed_tpu.ops import pallas as _pallas_ops  # noqa: F401
    from deepspeed_tpu.ops.registry import get_op

    on_tpu = jax.default_backend() == "tpu"
    dtype = jnp.bfloat16

    def timed(fn, *args):
        jf = jax.jit(fn)
        jax.block_until_ready(jf(*args))
        t0 = time.perf_counter()
        for _ in range(steps):
            out = jf(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / steps * 1e3

    def one_shape(name):
        T, E, held_n, k, H, F, L = BANK_SHAPES[name]
        if not on_tpu:      # the CPU run is a schema run at a toy size
            H, F, L = 64, 128, 2
        held = None if held_n == E else (E // 8, held_n)
        keys = jax.random.split(jax.random.PRNGKey(41), 6)
        bank = {
            "w_gate": jax.random.normal(keys[0], (L, held_n, H, F), dtype)
            * H ** -0.5,
            "w_up": jax.random.normal(keys[1], (L, held_n, H, F), dtype)
            * H ** -0.5,
            "w_down": jax.random.normal(keys[2], (L, held_n, F, H), dtype)
            * F ** -0.5,
            "router": jax.random.normal(keys[3], (L, H, E), dtype) * H ** -0.5,
        }
        x = jax.random.normal(keys[4], (T, H), dtype)
        cg = top_k_gating_compact(
            jax.random.normal(keys[5], (T, E), jnp.float32)
            + skew * jax.random.normal(keys[3], (E,), jnp.float32), k,
            drop_tokens=False)
        first, count = held or (0, E)
        sizes = cg.counts[first:first + count]
        row = {"largest_group": int(sizes.max())}

        def over_layers(one):
            """A layer scan as the serving forward's: ``one(x, layer's
            scanned part, index, *whole)`` a layer, the result fed on."""
            def fn(x, scanned, *whole):
                def step(x, sc):
                    part, i = sc
                    return (x + 0.01 * one(x, part, i, *whole)[:T]
                            ).astype(dtype), None
                return lax.scan(step, x, (scanned, jnp.arange(L)))[0]
            return fn

        # today's slabs: every expert over T rows (the slab's content does
        # not change its time)
        def slabs(x, w, i):
            xe = jnp.broadcast_to(x, (held_n,) + x.shape)
            g = jax.nn.silu(jnp.einsum("ech,ehf->ecf", xe, w["w_gate"]))
            u = jnp.einsum("ech,ehf->ecf", xe, w["w_up"])
            return jnp.einsum("ecf,efh->ech", g * u, w["w_down"])[0]

        banks = {n: bank[n] for n in BANK}
        row["dense_slabs"] = timed(over_layers(slabs), x, banks)

        # rows sorted by expert, no padding: XLA's own ragged_dot
        n_sorted = T * min(k, count)

        def ragged(x, w, i, sizes):
            xs = jnp.resize(x, (n_sorted, H))
            g = jax.nn.silu(lax.ragged_dot(xs, w["w_gate"], sizes))
            u = lax.ragged_dot(xs, w["w_up"], sizes)
            return lax.ragged_dot(g * u, w["w_down"], sizes)

        try:
            row["ragged_dot"] = timed(over_layers(ragged), x, banks, sizes)
        except Exception as e:
            row["ragged_dot"] = f"error: {str(e)[-200:]}"

        # the Mosaic kernel: the stacked bank whole, the layer an index
        own = row_tile(T, E, k, held_n, F)
        for tile in dict.fromkeys((own,) + tuple(tiles)):
            groups = row_groups(cg, tile, held)
            places = groups.source.shape[0]

            def grouped(x, _, i, wg, wu, wd, tile_expert, tile_rows,
                        num_tiles, tile=tile, places=places):
                xs = jnp.resize(x, (places, H))
                return get_op("moe_grouped_matmul")(
                    xs, wg, wu, wd, tile_expert, tile_rows, num_tiles, i,
                    tile=tile)

            label = f"grouped_tile{tile}" + ("_own" if tile == own else "")
            try:
                row[label] = timed(
                    over_layers(grouped), x, jnp.zeros((L,)),
                    *(bank[n] for n in BANK), groups.tile_expert,
                    groups.tile_rows, groups.num_tiles)
            except Exception as e:
                row[label] = f"error: {str(e)[-200:]}"
            row[f"tiles_in_use_{tile}"] = int(groups.num_tiles)

        # the whole MoE layer, both forms (router, gather, bank, combine)
        for form in ("slab", "grouped"):
            layer = MoELayer(E, k, drop_tokens=False, held=held)

            # one layer's bank (the scanned part): slabs; the stack: grouped
            def whole(x, part, i, *stack, layer=layer):
                params = {**part, **dict(zip(BANK, stack))}
                return layer(params, x[None], layer=i if stack else None)[0][0]

            try:
                if form == "slab":
                    row["layer_slab"] = timed(over_layers(whole), x, bank)
                else:
                    row["layer_grouped"] = timed(
                        over_layers(whole), x, {"router": bank["router"]},
                        *(bank[n] for n in BANK))
            except Exception as e:
                row[f"layer_{form}"] = f"error: {str(e)[-200:]}"
        return {k_: (round(v, 4) if isinstance(v, float) else v)
                for k_, v in row.items()}

    rows_out = {}
    for name in shapes or BANK_SHAPES:     # a shape's bank is freed on return
        rows_out[name] = one_shape(name)
        sys.stderr.write(f"[moe bank] {name}: {rows_out[name]}\n")
    return rows_out


def main():
    import jax

    import jax.numpy as jnp
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.moe.layer import MoELayer, init_moe_ffn
    from deepspeed_tpu.moe.sharded_moe import compute_capacity

    backend = jax.default_backend()
    RESULT["detail"]["backend"] = backend
    on_tpu = backend == "tpu"
    if "--forms" in sys.argv:
        names = [a for a in sys.argv[1:] if a in BANK_SHAPES]
        tiles = [int(a.split("=")[1]) for a in sys.argv if
                 a.startswith("--tile=")]
        # the kernel's weight blocks, to tune them: blocks of columns within
        # N MB double-buffered, and no expert's matrices whole
        for a in sys.argv:
            if a.startswith("--weight-vmem-mb="):
                from deepspeed_tpu.ops.pallas import grouped_matmul
                grouped_matmul._BLOCK_VMEM = int(a.split("=")[1]) << 20
                grouped_matmul._WHOLE_VMEM = 0
        RESULT["metric"], RESULT["unit"] = "moe_bank_forms", "ms_a_tick"
        skew = [float(a.split("=")[1]) for a in sys.argv
                if a.startswith("--skew=")]
        RESULT["detail"]["bank_forms_ms"] = bank_forms(
            names or None, tiles=tiles, skew=skew[0] if skew else 0.0)
        return finalize(RESULT)
    if on_tpu:
        shapes = [(8192, 1024, 8, 2), (8192, 1024, 64, 2),
                  (16384, 2048, 16, 2)]
        steps = 10
    else:
        shapes = [(512, 64, 8, 2)]
        steps = 3
    mesh_lib.set_mesh(None)  # single-device: measure dispatch, not a2a

    rows = {}
    RESULT["detail"]["rows_ms"] = rows
    parity_checked = False
    for T, H, E, k in shapes:
        params = init_moe_ffn(jax.random.PRNGKey(0), n_experts=E, hidden=H,
                              intermediate=2 * H, dtype=jnp.bfloat16)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, T, H), jnp.bfloat16)
        cap = compute_capacity(T, E, k, 1.25)
        label = f"T{T}_H{H}_E{E}_k{k}_cap{cap}"

        # the SHIPPING implementations — both paths are MoELayer(dispatch=..)
        # so this bench can never drift from what the engine runs
        def run(impl, params, x):
            layer = MoELayer(n_experts=E, top_k=k, capacity_factor=1.25,
                             dispatch=impl)
            out, _ = layer(params, x)
            return out

        if not parity_checked:
            # the timing verdict is only meaningful if both paths compute
            # the same function — pin it in f32 (bf16 differs only by
            # accumulation-order noise, which would mask a real bug). On TPU
            # f32 matmuls themselves run as bf16 passes at DEFAULT precision,
            # so force true-f32 matmuls or the noise floor comes back.
            p32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
            x32 = x.astype(jnp.float32)
            with jax.default_matmul_precision("highest"):
                a = run("einsum", p32, x32)
                b = run("compact", p32, x32)
            diff = float(jnp.max(jnp.abs(a - b)))
            assert diff < 1e-3, f"einsum/compact diverge: max diff {diff}"
            RESULT["detail"]["parity_max_diff"] = diff
            parity_checked = True
        row = {}
        for name in ("einsum", "compact"):
            try:
                jf = jax.jit(run, static_argnums=0)
                out = jf(name, params, x)
                jax.block_until_ready(out)  # compile
                t0 = time.perf_counter()
                for _ in range(steps):
                    out = jf(name, params, x)
                jax.block_until_ready(out)
                row[name] = round((time.perf_counter() - t0) / steps * 1e3, 3)
            except Exception as e:
                row[name] = f"error: {str(e)[-150:]}"
        if all(isinstance(v, float) for v in row.values()):
            row["einsum_over_compact"] = round(row["einsum"] / row["compact"],
                                               3)
        rows[label] = row
        sys.stderr.write(f"[moe] {label}: {row}\n")
    ratios = [r.get("einsum_over_compact") for r in rows.values()
              if isinstance(r, dict) and "einsum_over_compact" in r]
    if ratios:
        RESULT["value"] = round(sum(ratios) / len(ratios), 3)
    return finalize(RESULT)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # report in the JSON line, then fail
        RESULT["detail"]["error"] = str(e)[-2000:]
        finalize(RESULT, ok=False)
        raise
