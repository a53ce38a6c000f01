#!/usr/bin/env python3
"""Times the multi-token walk (``paged_prefill``) alone, on the chip, at the
serve cells' shapes (``chiprun -- python3 scripts/prefill_tile_bench.py``):
one layer's call of a chunk at several contexts, with the KV tile forced to
each width and once as the program chooses it, in each of the walk's FORMS:

- ``own``: the walk as the tree builds it (where ``_fetches_pages`` holds, the
  kernel that fetches its own pages);
- ``grid``: the grid of table-indexed ``BlockSpec`` pages, forced;
- ``folded``: that grid with every page map folded onto table entry 0 - the
  index maps still run and every operand is still there, but Pallas elides
  every DMA after the first: what the operands cost WITHOUT their bytes;
- ``one_page``: that grid with the KV tile built from ONE page's ref,
  repeated: what reading and joining the other pages in VMEM costs.

A time is the DEVICE's: the kernels' own events (``paged_prefill*``) of one
traced run of a program of ``--layers`` calls (a call of 0.1-3 ms is of the
order of the host's dispatch, and a host timer reads the host). One JSON line
a case - microseconds a call and, from the host's mirror, the steps that
held context and the steps taken - then one ``fit`` line a (shape, form,
context) over the narrowest and the widest tile run: ``F`` us a step
whatever its width and the slope a 256 keys. How ``_WIDE_KV_TOKENS`` /
``_WIDE_WALK_TILES`` / ``_WIDE_VMEM`` in ``ops/pallas/paged_attention.py``
were chosen (PERF.md section 6, PRs 48 and 62); a number from here is a
kernel's, never a cell's."""

import argparse
import json
import os
import re
import shutil
import sys
import tempfile

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
    "mellum_full": (512, 32, 4, 128, None, 32, 1024, 10240, None, 1,
                    (512, 1024, 2048, 4096, 8192, 12288)),
    "mellum_window": (512, 32, 4, 128, None, 32, 49, 1569, 1024, 1,
                      (256, 512, 1024)),
    "verify_t5": (5, 32, 8, 128, None, 32, 256, 896, None, 16,
                  (1024, 3000, 6000)),
}
FORMS = ("own", "grid", "folded", "one_page")


def kernel_us(run, where, calls, name="paged_prefill"):
    """Microseconds of each of the ``calls`` events of the kernel ``name``
    (``paged_prefill``) of one traced ``run()``, in the device's order (None
    off a chip: no device plane)."""
    import jax
    from jax.profiler import ProfileData

    from benchmark.harness import trace as tr

    shutil.rmtree(where, ignore_errors=True)
    with jax.profiler.trace(where):
        run()
    data = ProfileData.from_file(tr.find_xplane(where))
    events = [e for plane in data.planes
              if plane.name.startswith("/device:TPU:0")
              for line in plane.lines if line.name == "XLA Ops"
              for e in line.events
              if re.match("%?" + name, e.name)]
    if not events:
        return None
    events.sort(key=lambda e: e.start_ns)
    assert len(events) == calls, (len(events), calls)
    return [e.duration_ns / 1e3 for e in events]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="*", default=sorted(SHAPES))
    ap.add_argument("--tiles", type=int, nargs="*", default=[256, 512, 1024],
                    help="KV tokens a step to force; 0 = the program's rule")
    ap.add_argument("--forms", nargs="*", default=["own"], choices=FORMS)
    ap.add_argument("--contexts", type=int, nargs="*",
                    help="these contexts and not the shape's own list")
    ap.add_argument("--layers", type=int, default=8)
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

    tree = {n: getattr(pa, n) for n in ("_wide_pages", "_WIDE_WALK_TILES",
                                        "_fetches_pages", "_live_page",
                                        "_kv_tile")}
    where = tempfile.mkdtemp()
    bf, i32 = jnp.bfloat16, jnp.int32
    for name in args.shapes:
        t, nh, nkv, hd, vd, bs, mb, nb, window, B, contexts = SHAPES[name]
        contexts = args.contexts or contexts
        if args.tiny:
            t, nb, contexts, args.layers = min(t, 32), 2 * mb, contexts[:2], 2
        contexts = sorted({min(c, mb * bs - t) for c in contexts})
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
        how = dict(pools=1 if vd else 2)
        read = {}           # (form, ctx) -> {tile: (us, steps taken)}

        for form in args.forms:
            for tile in args.tiles:
                if tile % bs or tile // bs > mb:
                    continue
                for n, v in tree.items():
                    setattr(pa, n, v)
                if tile:                        # every walk takes it
                    pa._wide_pages = lambda *a, _p=tile // bs, **k: _p
                    pa._WIDE_WALK_TILES = 0
                if form != "own":
                    pa._fetches_pages = lambda *a: False
                if form == "folded":
                    pa._live_page = lambda *a, **k: jax.lax.min(
                        tree["_live_page"](*a, **k), 0)
                if form == "one_page":
                    pa._kv_tile = lambda pages, scales, dtype: \
                        tree["_kv_tile"]([pages[0]] * len(pages),
                                         scales, dtype)

                def program(q_, k_, v_, tb_, ctx_):     # a new one a case:
                    def layer(i, acc):                  # jit keeps its trace
                        out = pa.paged_prefill_attention(
                            q_, k_, v_, tb_, ctx_, lens, layer=i % L,
                            window=window, value_width=vd)
                        return acc + out.astype(jnp.float32)
                    return jax.lax.fori_loop(
                        0, args.layers, layer,
                        jnp.zeros((B, t, nh, vd or hd), jnp.float32))

                fn = jax.jit(program)
                ctxs = [jnp.full((B,), c, i32) for c in contexts]
                jax.block_until_ready(fn(q, *pools, tables, ctxs[-1]))
                us = kernel_us(
                    lambda: jax.block_until_ready(
                        [fn(q, *pools, tables, c) for c in ctxs]),
                    where, args.layers * len(ctxs))
                for i, c in enumerate(contexts):
                    live, grid, _ = pa.prefill_tile_counts(
                        [c] * B, [t] * B, t, nh, (nkv, bs, hd), mb, window,
                        **how)
                    took = pa.prefill_kv_pages(
                        [c] * B, [t] * B, t, nh, (nkv, bs, hd), mb,
                        **how) * bs
                    mine = us and float(np.median(
                        us[i * args.layers:(i + 1) * args.layers]))
                    if mine and tile:
                        read.setdefault((form, c), {})[took] = (mine, grid)
                    print(json.dumps({
                        "shape": name, "form": form, "tables": args.tables,
                        "tile": tile or "rule", "kv_tile": took, "ctx": c,
                        "us": mine, "steps_live": live, "steps_grid": grid,
                        "device": jax.devices()[0].device_kind}), flush=True)

        # T = steps x (F + slope x keys / 256), at two widths of one context
        for (form, c), by_tile in read.items():
            if len(by_tile) < 2:
                continue
            (w1, (t1, n1)), (w2, (t2, n2)) = min(by_tile.items()), \
                max(by_tile.items())
            slope = (t2 / n2 - t1 / n1) / ((w2 - w1) / 256)
            print(json.dumps({
                "fit": name, "form": form, "ctx": c, "tiles": [w1, w2],
                "F_us": round(t1 / n1 - slope * w1 / 256, 3),
                "us_per_256_keys": round(slope, 3),
                "step_us": [round(t1 / n1, 3), round(t2 / n2, 3)]}),
                flush=True)
    for n, v in tree.items():
        setattr(pa, n, v)
    shutil.rmtree(where, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
