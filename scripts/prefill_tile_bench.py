#!/usr/bin/env python3
"""Times the multi-token walk (``paged_prefill``) alone, on the chip, at the
serve cells' shapes (``chiprun -- python3 scripts/prefill_tile_bench.py``):
one layer's call of a chunk at several contexts, with the KV tile forced to
each width and once as the program chooses it. Prints one JSON line a case:
microseconds a call (``--layers`` calls in one program, median of
``--reps``) and, from the host's mirror, the grid steps that held context.
How ``_WIDE_KV_TOKENS`` / ``_WIDE_WALK_TILES`` / ``_WIDE_VMEM`` in
``ops/pallas/paged_attention.py`` were chosen (PERF.md section 6, PR 48); a
number from here is a kernel's, never a cell's."""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: t, query heads, KV heads, key width, value width (None: a V pool),
# block, table width, pool blocks, window, sequences, contexts
SHAPES = {
    "command_a_full": (512, 128, 8, 128, None, 32, 1024, 12544, None, 1,
                       (512, 1024, 1536, 2048, 3072, 4096, 8192, 16384)),
    "command_a_window": (512, 128, 8, 128, None, 32, 145, 2321, 4096, 1,
                         (1024, 1536, 2048, 3072, 4128)),
    "chat": (256, 32, 8, 128, None, 32, 256, 896, None, 1,
             (128, 256, 512, 768, 1024, 1536, 2048, 4096)),
    "olmoe": (256, 16, 16, 128, None, 32, 128, 1536, None, 1,
              (768, 1024, 1536, 2048, 2560)),
    "axk1": (512, 64, 1, 640, 512, 128, 256, 3152, None, 1,
             (1024, 2048, 4096, 8192, 16384)),
    "verify_t5": (5, 32, 8, 128, None, 32, 256, 896, None, 16,
                  (1024, 3000, 6000)),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=sorted(SHAPES))
    ap.add_argument("--tiles", type=int, nargs="*", default=[256, 512, 1024],
                    help="KV tokens a step to force; 0 = the program's rule")
    ap.add_argument("--layers", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tables", choices=["ascending", "random"],
                    default="ascending",
                    help="block ids down a table: as a fresh allocator hands "
                    "them out, or scattered over the pool")
    ap.add_argument("--tiny", action="store_true",
                    help="a schema run at toy sizes (the CPU's interpreter)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas import paged_attention as pa

    rule = pa._wide_pages, pa._WIDE_WALK_TILES
    bf, i32 = jnp.bfloat16, jnp.int32
    for name in args.shapes:
        t, nh, nkv, hd, vd, bs, mb, nb, window, B, contexts = SHAPES[name]
        if args.tiny:
            t, nb, contexts, args.layers = min(t, 32), 2 * mb, contexts[:2], 2
        L = 2
        key = jax.random.PRNGKey(0)
        pools = [jax.random.normal(jax.random.fold_in(key, i),
                                   (L, nb, nkv, bs, hd), bf)
                 for i in range(1 if vd else 2)]
        if vd:
            pools.append(None)
        q = jax.random.normal(key, (B, t, nh, hd), bf)
        tables = np.random.default_rng(0).integers(1, nb, (B, mb)) \
            if args.tables == "random" \
            else 1 + np.arange(B * mb).reshape(B, mb) % (nb - 1)
        tables = jnp.asarray(tables, i32)
        lens = jnp.full((B,), t, i32)

        for tile in args.tiles:
            if tile % bs or tile // bs > mb:
                continue
            pa._wide_pages, pa._WIDE_WALK_TILES = rule if not tile else (
                lambda *a, _p=tile // bs, **k: _p, 0)   # every walk takes it

            def program(q_, k_, v_, tb_, ctx_):     # a new one a tile: jit
                def layer(i, acc):                  # keeps what it traced
                    out = pa.paged_prefill_attention(
                        q_, k_, v_, tb_, ctx_, lens, layer=i % L,
                        window=window, value_width=vd)
                    return acc + out.astype(jnp.float32)
                return jax.lax.fori_loop(
                    0, args.layers, layer,
                    jnp.zeros((B, t, nh, vd or hd), jnp.float32))

            fn = jax.jit(program)
            for c in contexts:
                c = min(c, mb * bs - t)
                ctx = jnp.full((B,), c, i32)
                jax.block_until_ready(fn(q, *pools, tables, ctx))
                ts = []
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(q, *pools, tables, ctx))
                    ts.append(time.perf_counter() - t0)
                live, grid, _ = pa.prefill_tile_counts(
                    [c] * B, [t] * B, t, nh, (nkv, bs, hd), mb, window,
                    pools=1 if vd else 2)
                took = pa.prefill_kv_pages(
                    [c] * B, [t] * B, t, nh, (nkv, bs, hd), mb,
                    pools=1 if vd else 2) * bs
                print(json.dumps({
                    "shape": name, "tables": args.tables,
                    "tile": tile or "rule", "kv_tile": took,
                    "ctx": c, "us": statistics.median(ts) / args.layers * 1e6,
                    "steps_live": live, "steps_grid": grid,
                    "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
