#!/usr/bin/env python3
"""Times the decode walk (``paged_decode``) alone, on the chip, at the serve
cells' shapes (``chiprun -- python3 scripts/decode_walk_bench.py``): one
layer's call over every slot, at contexts drawn as the cell draws them, as
the parent's walk (a grid of ``BlockSpec`` pages as wide as the longest
context: what int8 pools still take, and what the op builds for every pool
while ``_fetches_pages`` says no) and as the walk that fetches its own
pages, with its KV tile forced to each width and once as the program chooses
it. Prints one JSON line a case: microseconds a call (``--layers`` calls in
one program, median of ``--reps``), the share of the HBM floor (the live
context's K and V bytes at the chip's published rate), the tiles the walk
takes, and how far its result lies from the parent walk's. How
``_DECODE_KV_TOKENS`` in ``ops/pallas/paged_attention.py`` was chosen
(PERF.md section 6, PR 49); a number from here is a kernel's, never a
cell's."""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# name: slots, query heads, KV heads, key width, value width (None: a V
# pool), block, table width, pool blocks, window, contexts (from, to)
SHAPES = {
    "chat": (32, 32, 8, 128, None, 32, 256, 896, None, (128, 900)),
    "mixtral": (16, 32, 8, 128, None, 32, 256, 1536, None, (1024, 3072)),
    "olmoe": (16, 16, 16, 128, None, 32, 128, 1536, None, (1024, 3072)),
    "granite": (64, 32, 4, 128, None, 32, 256, 2816, None, (128, 1280)),
    "command_a_full": (16, 128, 8, 128, None, 32, 1024, 12544, None,
                       (4096, 25088)),
    # a window kind's contexts count from its first live block
    "command_a_window": (16, 128, 8, 128, None, 32, 145, 2321, 4096,
                         (4096, 4127)),
    "axk1": (16, 64, 1, 640, 512, 128, 256, 3152, None, (4096, 25088)),
}
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}    # Google Cloud, "TPU v5e"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=sorted(SHAPES))
    ap.add_argument("--tiles", type=int, nargs="*",
                    default=[-1, 256, 512, 1024, 0],
                    help="KV tokens a tile to force; 0 = the program's "
                    "rule, -1 = the parent's walk")
    ap.add_argument("--vmem-mb", type=int, default=0,
                    help="the walk's VMEM budget while a tile is forced "
                    "(0: the module's)")
    ap.add_argument("--layers", type=int, default=32)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seeds", type=int, nargs="*", default=[0, 1])
    ap.add_argument("--tables", choices=["ascending", "random"],
                    default="random",
                    help="block ids down a table: scattered over the pool, "
                    "or as a fresh allocator hands them out")
    ap.add_argument("--tiny", action="store_true",
                    help="a schema run at toy sizes (the CPU's interpreter)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas import paged_attention as pa

    rule = pa._DECODE_KV_TOKENS, pa._TILE_VMEM
    fetches = pa._fetches_pages
    bf, i32 = jnp.bfloat16, jnp.int32
    kind = jax.devices()[0].device_kind
    for name in args.shapes:
        B, nh, nkv, hd, vd, bs, mb, nb, window, (lo, hi) = SHAPES[name]
        if args.tiny:
            B, mb, nb, args.layers = 4, 16, 80, 2
            lo, hi = (lo * mb * bs // 32768, min(hi, mb * bs - 1))
        L, n_pools = 2, 1 if vd else 2
        key = jax.random.PRNGKey(0)
        pools = [jax.random.normal(jax.random.fold_in(key, i),
                                   (L, nb, nkv, bs, hd), bf)
                 for i in range(n_pools)] + [None] * (2 - n_pools)
        q = jax.random.normal(key, (B, nh, hd), bf)
        want = {}
        for tile in args.tiles:
            if tile > 0 and (tile % bs or tile // bs > mb):
                continue
            pa._DECODE_KV_TOKENS, pa._TILE_VMEM = rule if tile <= 0 else (
                tile, args.vmem_mb << 20 or rule[1])
            pa._fetches_pages = (lambda *a: False) if tile < 0 else fetches
            pages, heads, _ = pa._decode_tiles(nkv, nh // nkv, hd, bs, mb, 2,
                                               False, n_pools)
            if tile > 0 and pages * bs != tile:
                continue                # the budget does not hold it

            def walk(q_, k_, v_, tb_, ctx_, layer):
                return pa.paged_decode_attention(
                    q_, k_, v_, tb_, ctx_, layer=layer, window=window,
                    value_width=vd)

            def program(q_, k_, v_, tb_, ctx_):     # a new one a tile: jit
                def layer(i, acc):                  # keeps what it traced
                    return acc + walk(q_, k_, v_, tb_, ctx_, i % L) \
                        .astype(jnp.float32)
                return jax.lax.fori_loop(
                    0, args.layers, layer,
                    jnp.zeros((B, nh, vd or hd), jnp.float32))

            fn, one = jax.jit(program), jax.jit(walk)
            for seed in args.seeds:
                rng = np.random.default_rng(seed)
                ctx = np.exp(rng.uniform(np.log(lo), np.log(hi), B)) \
                    .astype(np.int64)
                tables = rng.integers(1, nb, (B, mb)) \
                    if args.tables == "random" \
                    else 1 + np.arange(B * mb).reshape(B, mb) % (nb - 1)
                ops = (q, *pools, jnp.asarray(tables, i32),
                       jnp.asarray(ctx, i32))
                got = np.asarray(one(*ops, 1), np.float32)
                want.setdefault(seed, got)
                ts = []
                jax.block_until_ready(fn(*ops))
                for _ in range(args.reps):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(*ops))
                    ts.append(time.perf_counter() - t0)
                us = statistics.median(ts) / args.layers * 1e6
                live = np.minimum(ctx + 1, window or ctx + 1)
                floor = float(live.sum()) * nkv * hd * 2 * n_pools \
                    / HBM_BYTES_PER_S[kind] * 1e6 \
                    if kind in HBM_BYTES_PER_S else None
                tiles = pa.decode_tile_counts(
                    ctx if tile < 0 else live - 1, nh, (nkv, bs, hd), 2, mb,
                    False, n_pools)[1]
                print(json.dumps({
                    "shape": name, "tables": args.tables, "seed": seed,
                    "walk": "parent" if tile < 0 else "own_pages",
                    "tile": "rule" if tile == 0 else pages * bs,
                    "kv_tile": pages * bs, "heads": heads, "us": us,
                    "floor_us": floor,
                    "floor_share": floor and floor / us,
                    "tiles": tiles,
                    "max_abs_diff": float(np.abs(got - want[seed]).max()),
                    "device": kind}), flush=True)
        pa._DECODE_KV_TOKENS, pa._TILE_VMEM = rule
        pa._fetches_pages = fetches
    return 0


if __name__ == "__main__":
    sys.exit(main())
