#!/usr/bin/env python3
"""Times the learned selection's kernels alone, on the chip, at the Keye
cell's shapes (``chiprun -- python3 scripts/sparse_kernel_bench.py``): one
layer's calls of a tick - a 512-row chunk and 8 decode rows at ``--ctx``
cached tokens each - at several tile sizes. Prints one JSON line a case:
milliseconds a call, median of ``--reps``. How the tile sizes in
``ops/pallas/paged_sparse_attention.py`` were chosen (PERF.md section 6,
PR 38); a number from here is a kernel's, never a cell's."""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctx", type=int, default=15000)
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas import paged_sparse_attention as S

    L, NB, BS, D, H = 2, 7808, 32, 64, 16
    NKV, NH, HD, MB, TOPK = 4, 32, 128, 1024, 2048
    key = jax.random.PRNGKey(0)
    bf, i32 = jnp.bfloat16, jnp.int32
    ipool = jax.random.normal(key, S.index_pool_shape(L, NB, BS, D), bf)
    kpool = jax.random.normal(key, (L, NB, NKV, BS, HD), bf)
    vpool = jax.random.normal(jax.random.fold_in(key, 1),
                              (L, NB, NKV, BS, HD), bf)
    rng = np.random.default_rng(0)

    def tables(b):
        return jnp.asarray(rng.integers(1, NB, (b, MB)), i32)

    def timed(name, fn, *a, **extra):
        out = fn(*a)
        jax.block_until_ready(out)
        ts = []
        for _ in range(args.reps):      # ten calls in flight: the device's
            t0 = time.perf_counter()    # time, not the dispatch's
            for _ in range(9):
                fn(*a)
            jax.block_until_ready(fn(*a))
            ts.append((time.perf_counter() - t0) / 10)
        print(json.dumps({"case": name, "ms": statistics.median(ts) * 1e3,
                          "ctx": args.ctx, **extra}), flush=True)
        return out

    layer = jnp.int32(1)
    for B, t in ((1, 512), (8, 1)):
        tb = tables(B)
        ctx = jnp.full((B,), args.ctx, i32) if t > 1 else jnp.asarray(
            np.linspace(args.ctx // 2, args.ctx * 2, B).astype(np.int32))
        ctx = jnp.minimum(ctx, MB * BS - t - 1)
        lens = jnp.full((B,), t, i32)
        q_idx = jax.random.normal(key, (B, t, H, D), bf)
        w_idx = jax.random.normal(key, (B, t, H), bf)
        q = jax.random.normal(key, (B, t, NH, HD), bf)
        rows = 8 if t == 1 else S.prefill_rows(t, NH, NKV, HD, BS, MB)
        idx = None
        for pages in (8, 32):
            S._INDEX_PAGES = pages
            fn = jax.jit(lambda q_, w_, p_, tb_, c_, l_: S.paged_index_scores(
                q_, w_, p_, tb_, c_, l_, layer=layer, rows=rows))
            idx = timed(f"index_scores {B}x{t}", fn, q_idx, w_idx, ipool, tb,
                        ctx, lens, pages=pages)
        width = 1 if t == 1 else rows
        q_abs = (ctx[:, None] + jnp.arange(width)[None]).reshape(-1)
        sel = jax.jit(lambda s_, q_: S.paged_sparse_select(s_, q_, topk=TOPK))
        scores = jax.random.normal(key, (B * width, idx.shape[-1]),
                                   jnp.float32)
        tau, cut = timed(f"select {B * width} rows", sel, scores, q_abs)
        tau, cut = tau.reshape(B, width), cut.reshape(B, width)
        idx = jnp.broadcast_to(scores.reshape(B, width, -1)[:, :1],
                               (B, rows, scores.shape[-1])) if t == 1 \
            else scores.reshape(B, rows, -1)
        if t == 1:
            for pages in (8, 16, 32):
                S._DECODE_PAGES = pages
                fn = jax.jit(lambda *a: S.paged_sparse_decode_attention(
                    *a, layer=layer))
                timed("sparse_decode 8 rows", fn, q[:, 0], kpool, vpool, idx,
                      tau[:, 0], cut[:, 0], tb, ctx, pages=pages)
        else:
            for pages in (8, 16, 32):
                S._PREFILL_PAGES = pages
                fn = jax.jit(lambda *a: S.paged_sparse_prefill_attention(
                    *a, layer=layer))
                timed("sparse_prefill 512 rows", fn, q, kpool, vpool, idx,
                      tau, cut, tb, ctx, lens, pages=pages)
    return 0


if __name__ == "__main__":
    sys.exit(main())
