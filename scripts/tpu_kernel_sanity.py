#!/usr/bin/env python
"""Real-chip sanity for every Pallas kernel.

The Mosaic TPU lowering enforces tiling rules the CPU interpreter never
checks (three such failures were once found only on silicon: squeezed dims
in the paged-KV block, row-blocks of 1..7 in the norms/quant kernels, and the
serving path they broke). This script executes each registered Pallas op on
the TPU at BOTH a training-ish and a decode-ish shape and compares against
its XLA reference, printing one JSON line; it exits non-zero unless every
kernel passed. (``tests/test_chip_compile.py`` compiles the main-path kernels
for a described chip without one; ``chip_smoke.py`` runs them end to end.)
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

RESULT = {"metric": "pallas_kernel_sanity_pass", "value": 0, "unit": "kernels",
          "vs_baseline": None, "detail": {}}


def emit_and_exit(ok: bool):
    """The one stdout JSON line; the exit code is the verdict."""
    RESULT["detail"]["ok"] = ok
    print(json.dumps(RESULT), flush=True)
    sys.exit(0 if ok else 1)


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    RESULT["detail"]["backend"] = jax.default_backend()
    rows = {}
    RESULT["detail"]["kernels"] = rows

    only = set(sys.argv[1:])        # check names; none: every check

    def check(name, fn):
        if only and name not in only:
            return
        rows[name] = "RUNNING"  # visible in the artifact if killed mid-check
        try:
            fn()
            rows[name] = "ok"
        except Exception as e:
            rows[name] = f"FAIL: {str(e)[-300:]}"

    def diff_ok(a, b, tol):
        d = float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                  - jnp.asarray(b, jnp.float32))))
        assert d < tol, f"max diff {d} >= {tol}"

    rs = np.random.RandomState(0)

    def randn(*shape):
        return jnp.asarray(rs.randn(*shape).astype(np.float32))

    # flash attention fwd+bwd (train shape, bf16; GQA)
    def flash():
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

        q = randn(2, 256, 8, 128).astype(jnp.bfloat16)
        k = randn(2, 256, 4, 128).astype(jnp.bfloat16)
        v = randn(2, 256, 4, 128).astype(jnp.bfloat16)

        def loss(fn, q, k, v):
            return jnp.sum(fn(q, k, v, causal=True).astype(jnp.float32) ** 2)

        diff_ok(flash_attention(q, k, v, causal=True),
                attention_xla(q, k, v, causal=True), 0.05)
        gp = jax.grad(lambda q: loss(flash_attention, q, k, v))(q)
        gx = jax.grad(lambda q: loss(attention_xla, q, k, v))(q)
        diff_ok(gp, gx, 1.0)  # bf16 grad-scale tolerance; NaN/shape guard

    check("flash_attention", flash)

    # paged decode (decode shape, odd batch)
    def paged():
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention, paged_decode_attention_xla)

        q = randn(3, 8, 128).astype(jnp.bfloat16)
        kp = randn(16, 4, 32, 128).astype(jnp.bfloat16)
        vp = randn(16, 4, 32, 128).astype(jnp.bfloat16)
        bt = jnp.asarray(rs.choice(np.arange(1, 16), (3, 4), replace=False)
                         .astype(np.int32))
        cl = jnp.asarray([0, 17, 100], np.int32)
        diff_ok(paged_decode_attention(q, kp, vp, bt, cl),
                paged_decode_attention_xla(q, kp, vp, bt, cl), 0.05)
        # sliding-window variant (mistral/exaone4 serving): extra prefetched
        # scalar + window masking — silicon numerics are chip-only
        diff_ok(paged_decode_attention(q, kp, vp, bt, cl, window=32),
                paged_decode_attention_xla(q, kp, vp, bt, cl, window=32),
                0.05)

    check("paged_decode_attention", paged)

    # paged decode at SERVING pool sizes — round-4's silicon failure mode:
    # the bench-toy pool (16 blocks) lowered while 192/376/744-block pools
    # hit the Mosaic BlockSpec check (pre-04:30Z squeezed-dim layout,
    # bench_runs/SERVING_20260731T034754Z.json). This gate reproduces the
    # exact 32-client geometry so any layout regression fails HERE first.
    def paged_serving():
        from deepspeed_tpu.ops.pallas.paged_attention import (
            paged_decode_attention, paged_decode_attention_xla)

        B, nblocks, max_blocks = 32, 744, 64
        q = randn(B, 8, 128).astype(jnp.bfloat16)
        kp = randn(nblocks, 4, 32, 128).astype(jnp.bfloat16)
        vp = randn(nblocks, 4, 32, 128).astype(jnp.bfloat16)
        bt = jnp.asarray(rs.randint(1, nblocks, (B, max_blocks), np.int32))
        cl = np.asarray(rs.randint(0, max_blocks * 32, (B,), np.int32))
        # full-capacity boundary: the kernel attends ctx = cl + 1 tokens
        # (the current token's KV was just written at position cl), so
        # cl = capacity - 1 puts the current token in the table's LAST slot
        cl[0] = max_blocks * 32 - 1
        cl = jnp.asarray(cl)
        diff_ok(paged_decode_attention(q, kp, vp, bt, cl),
                paged_decode_attention_xla(q, kp, vp, bt, cl), 0.05)

    check("paged_decode_serving_pool", paged_serving)

    # the in-place pool write and the layer-indexed reads over [L, ...] pools
    # at a serving geometry: the write is a copy, so it must match its
    # scatter reference bit for bit, a chunk from mid page and a decode batch
    def paged_kv_write_layered():
        from deepspeed_tpu.ops.pallas import paged_attention as pa

        L, nblocks, nkv, bs, hd, mb = 3, 896, 8, 32, 128, 64
        kp = randn(L, nblocks, nkv, bs, hd).astype(jnp.bfloat16)
        vp = randn(L, nblocks, nkv, bs, hd).astype(jnp.bfloat16)
        for b, t, ctx in ((1, 256, [1013]), (8, 1, list(range(40, 840, 100)))):
            k = randn(b, t, nkv, hd).astype(jnp.bfloat16)
            v = randn(b, t, nkv, hd).astype(jnp.bfloat16)
            bt = jnp.asarray(rs.permutation(np.arange(1, nblocks))[:b * mb]
                             .reshape(b, mb).astype(np.int32))
            ctx = jnp.asarray(ctx, jnp.int32)
            lens = jnp.full((b,), t, jnp.int32)
            got = pa.paged_kv_write(k, v, kp, vp, bt, ctx, lens, layer=1)
            want = pa.paged_kv_write_xla(k, v, kp, vp, bt, ctx, lens, layer=1)
            assert bool(jnp.array_equal(got[0], want[0])) \
                and bool(jnp.array_equal(got[1], want[1])), f"write b={b}"
            q = randn(b, 32, hd).astype(jnp.bfloat16)
            diff_ok(pa.paged_decode_attention(q, got[0], got[1], bt, ctx,
                                              layer=1),
                    pa.paged_decode_attention_xla(q, got[0], got[1], bt, ctx,
                                                  layer=1), 0.05)

    check("paged_kv_write_layered_pools", paged_kv_write_layered)

    # the decode walk fetches its own pages (ISSUE 49): what the interpreter
    # cannot show - real DMAs out of the pools, the semaphores, the SMEM
    # carry across grid steps - at the cells' geometries: contexts 0, one
    # token short of a tile, a tile, the table; table entries past a
    # sequence's end out of range (a walk that fetched them would fault or
    # read NaN: the last block is poisoned); a static and a traced window
    # that begin past page 0; a latent pool; heads in blocks
    def garbage_past_the_end(ctx, bs, mb, nb):
        """Random tables for sequences at ``ctx``: ``(bad, clean)`` - past a
        sequence's last block the poisoned block ``nb - 1``, an index past
        the pool and a negative one, in turn, or the trash block."""
        bt = rs.randint(1, nb - 1, (len(ctx), mb)).astype(np.int32)
        bad = np.resize(np.asarray([nb - 1, 10 ** 6, -3], np.int32), bt.shape)
        live = np.arange(mb)[None, :] <= ctx[:, None] // bs
        return jnp.asarray(np.where(live, bt, bad)), \
            jnp.asarray(np.where(live, bt, 0))

    def paged_decode_own_pages():
        from deepspeed_tpu.ops.pallas import paged_attention as pa

        L, bs, mb = 2, 32, 64
        for nkv, g, hd, vd in ((8, 4, 128, None), (16, 1, 128, None),
                               (8, 16, 128, None), (1, 64, 640, 512),
                               (64, 1, 256, None)):     # eight head blocks
            nb = 600
            pages, _, _ = pa._decode_tiles(nkv, g, hd, bs, mb, 2, False,
                                           1 if vd else 2)
            tile = pages * bs
            ctx = np.asarray([0, tile - 2, tile - 1, tile, mb * bs - 1, 700,
                              0, 33], np.int32)
            B = len(ctx)
            pools = [randn(L, nb, nkv, bs, hd).astype(jnp.bfloat16)
                     .at[:, nb - 1].set(jnp.nan)
                     for _ in range(1 if vd else 2)] + [None] * bool(vd)
            bad, bt = garbage_past_the_end(ctx, bs, mb, nb)
            q = randn(B, nkv * g, hd).astype(jnp.bfloat16)
            ctx = jnp.asarray(ctx)
            kw = dict(layer=1, value_width=vd)
            walk = jax.jit(lambda w: pa.paged_decode_attention(
                q, *pools, bad, ctx, window=w, **kw))
            for w in (None, 40, 1000):
                want = pa.paged_decode_attention_xla(q, *pools, bt, ctx,
                                                     window=w, **kw)
                diff_ok(pa.paged_decode_attention(q, *pools, bad, ctx,
                                                  window=w, **kw), want, 0.05)
                if w:
                    diff_ok(walk(jnp.asarray(w, jnp.int32)), want, 0.05)

    check("paged_decode_own_pages", paged_decode_own_pages)

    # the multi-token walk that fetches its own pages (ISSUE 62) against its
    # XLA twin and, bit for bit, against the grid of ``BlockSpec`` pages it
    # replaced (same tiles, same flash sums): command-a's group of 16 over
    # its window kind's 145-entry table, chat's chunk, the verify window, a
    # latent pool; a zero-length dummy, a padded chunk, a context that ends
    # on a wide tile's edge and one key past it; static and traced windows
    # that begin past page 0
    def paged_prefill_own_pages():
        from deepspeed_tpu.ops.pallas import paged_attention as pa

        L, nb = 2, 600
        fetches = pa._fetches_pages
        for nkv, g, hd, vd, t, bs, mb in (
                (8, 16, 128, None, 512, 32, 145),
                (8, 4, 128, None, 256, 32, 256), (8, 4, 128, None, 5, 32, 256),
                (1, 64, 640, 512, 512, 128, 64),
                (16, 1, 128, None, 256, 32, 128)):
            room = mb * bs - t
            ctx = np.minimum(np.asarray([0, 700, 2048 - t, 2049 - t, room,
                                         33], np.int32), room)
            lens = np.asarray([0, t // 2 + 3, t, t, t, 1], np.int32)
            B = len(ctx)
            pools = [randn(L, nb, nkv, bs, hd).astype(jnp.bfloat16)
                     .at[:, nb - 1].set(jnp.nan)
                     for _ in range(1 if vd else 2)] + [None] * bool(vd)
            bad, bt = garbage_past_the_end(
                np.where(lens > 0, ctx + lens - 1, -bs), bs, mb, nb)
            q = randn(B, t, nkv * g, hd).astype(jnp.bfloat16)
            ctx, lens = jnp.asarray(ctx), jnp.asarray(lens)
            kw = dict(layer=1, value_width=vd)

            def walk(w):
                return pa.paged_prefill_attention(q, *pools, bad, ctx, lens,
                                                  window=w, **kw)

            for w in (None, 40, 1000, 4096):
                got = jax.jit(walk)(w and jnp.asarray(w, jnp.int32))
                pa._fetches_pages = lambda *a: False
                try:
                    grid = walk(w)
                finally:
                    pa._fetches_pages = fetches
                assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
                for b, n in enumerate(np.asarray(lens)):
                    if n:   # a sequence at a time: the scores are [nh, t, S]
                        want = pa.paged_prefill_attention_xla(
                            q[b:b + 1], *pools, bt[b:b + 1], ctx[b:b + 1],
                            lens[b:b + 1], window=w, **kw)
                        diff_ok(got[b, :n], want[0, :n], 0.05)
                        diff_ok(got[b, :n], grid[b, :n], 1e-9)

    check("paged_prefill_own_pages", paged_prefill_own_pages)

    # the same walk under the learned selection's mask (Keye's decode rows,
    # ISSUE 51): the tile's index scores one more DMA, the row's threshold
    # two more scalars. Garbage table entries past a sequence's end, and
    # garbage SCORES past each row's own position (the scores call never
    # writes them): NaN, which only the position mask keeps out
    def paged_sparse_decode_own_pages():
        from deepspeed_tpu.ops.pallas import paged_attention as pa
        from deepspeed_tpu.ops.pallas import paged_sparse_attention as sparse

        L, bs, mb, nb, topk = 2, 32, 64, 600, 256
        for nkv, g in ((4, 8), (8, 4)):
            pages, _, _ = pa._decode_tiles(nkv, g, 128, bs, mb, 2, False)
            tile = pages * bs
            ctx = np.asarray([0, tile - 2, tile - 1, tile, mb * bs - 1, 700,
                              0, 33], np.int32)
            B = len(ctx)
            k, v = (randn(L, nb, nkv, bs, 128).astype(jnp.bfloat16)
                    .at[:, nb - 1].set(jnp.nan) for _ in range(2))
            bad, bt = garbage_past_the_end(ctx, bs, mb, nb)
            q = randn(B, nkv * g, 128).astype(jnp.bfloat16)
            idx = randn(B, 8, mb * bs)
            past = np.arange(mb * bs)[None, None] > ctx[:, None, None]
            ctx = jnp.asarray(ctx)
            tau, cut = sparse.paged_sparse_select(idx[:, 0], ctx, topk=topk)
            want = sparse.paged_sparse_decode_attention_xla(
                q, k, v, idx, tau, cut, bt, ctx, layer=1)
            diff_ok(sparse.paged_sparse_decode_attention(
                q, k, v, jnp.where(past, jnp.nan, idx), tau, cut, bad, ctx,
                layer=1), want, 0.05)

    check("paged_sparse_decode_own_pages", paged_sparse_decode_own_pages)

    # the chunk's masked walk fetches its own pages too (ISSUE 63): the plain
    # multi-token walk's kernel with the query tile's index scores one more
    # DMA a KV tile and its rows' thresholds two more blocks. Keye's group of
    # 8 at its 1 024-key tile (and a group of 4 over a table that is no
    # multiple of it), a zero-length dummy, a padded chunk, contexts that
    # end on a tile's edge and one key past it; garbage table entries past a
    # sequence's end and garbage SCORES past each row's own position (NaN
    # and +inf: the chunk's scores call never writes them). Against the XLA
    # twin and - bit for bit - against the grid of ``BlockSpec`` pages
    def paged_sparse_prefill_own_pages():
        from deepspeed_tpu.ops.pallas import paged_attention as pa
        from deepspeed_tpu.ops.pallas import paged_sparse_attention as sparse

        L, bs, nb, topk, hd = 2, 32, 600, 256, 128
        fetches = pa._fetches_pages
        for nkv, g, t, mb in ((4, 8, 512, 128), (8, 4, 256, 72)):
            tile = sparse.prefill_pages(t, nkv * g, (nkv, bs, hd), mb) * bs
            room = mb * bs - t
            ctx = np.minimum(np.asarray([0, 700, tile - t, tile + 1 - t, room,
                                         33, 0], np.int32), room)
            lens = np.asarray([0, t // 2 + 3, t, t, t, 1, t], np.int32)
            B = len(ctx)
            k, v = (randn(L, nb, nkv, bs, hd).astype(jnp.bfloat16)
                    .at[:, nb - 1].set(jnp.nan) for _ in range(2))
            bad, bt = garbage_past_the_end(
                np.where(lens > 0, ctx + lens - 1, -bs), bs, mb, nb)
            q = randn(B, t, nkv * g, hd).astype(jnp.bfloat16)
            rows = sparse.prefill_rows(t, nkv * g, nkv, hd, bs, mb)
            tq = pa._prefill_tiles(t, g, hd, bs, mb)[0]
            idx = randn(B, rows, mb * bs)
            q_abs = np.where(np.arange(rows)[None] < lens[:, None],
                             ctx[:, None] + np.arange(rows)[None], -1)
            tau, cut = sparse.paged_sparse_select(
                idx.reshape(B * rows, -1), jnp.asarray(q_abs.reshape(-1)),
                topk=topk)
            tau, cut = tau.reshape(B, rows), cut.reshape(B, rows)
            past = np.arange(mb * bs)[None, None] > q_abs[..., None]
            junk = jnp.where(past, jnp.where(np.arange(mb * bs) % 2 == 0,
                                             jnp.nan, jnp.inf), idx)
            ctx, lens = jnp.asarray(ctx), jnp.asarray(lens)

            def walk():
                return sparse.paged_sparse_prefill_attention(
                    q, k, v, junk, tau, cut, bad, ctx, lens, layer=1)

            assert sparse._fetches_pages(hd, False)
            got = jax.jit(walk)()
            sparse._fetches_pages = lambda *a: False
            try:
                grid = walk()
            finally:
                sparse._fetches_pages = fetches
            assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
            for b, n in enumerate(np.asarray(lens)):
                if n:       # a sequence at a time: the scores are [nh, t, S]
                    want = sparse.paged_sparse_prefill_attention_xla(
                        q[b:b + 1], k, v, idx[b:b + 1], tau[b:b + 1],
                        cut[b:b + 1], bt[b:b + 1], ctx[b:b + 1],
                        lens[b:b + 1], layer=1)
                    diff_ok(got[b, :n], want[0, :n], 0.05)
                    diff_ok(got[b, :n], grid[b, :n], 1e-9)
                # whole query tiles of padding: nothing fetched, zeros
                assert not bool(got[b, -(-int(n) // tq) * tq:].any())

    check("paged_sparse_prefill_own_pages", paged_sparse_prefill_own_pages)

    # the decode rows' index scores walk their own pages too (ISSUE 54): one
    # DMA a packed index page. Garbage table entries past a sequence's end,
    # idle slots (no row: their whole table row is garbage) first, between
    # and last, NaN in every block no live sequence holds; compared on the
    # entries the selection reads, with the XLA op and - bit for bit - with
    # the grid of ``BlockSpec`` pages at the same tile
    def paged_index_scores_own_pages():
        from deepspeed_tpu.ops.pallas import paged_sparse_attention as sparse

        L, bs, mb, nb, H, d = 2, 32, 160, 900, 16, 64
        tile = sparse._index_pages(1, mb) * bs
        ctx = np.asarray([0, 5, tile - 2, 0, tile - 1, tile, mb * bs - 1,
                          2 * tile + 700, 33, 0], np.int32)
        lens = np.asarray([0, 1, 1, 0, 1, 1, 1, 1, 1, 0], np.int32)
        assert sparse._fetches_index_pages(
            sparse.index_pool_shape(L, nb, bs, d))
        bad, bt = garbage_past_the_end(ctx, bs, mb, nb)
        bad = jnp.where(lens[:, None] > 0, bad, bad[:, -1:])
        held = np.unique(np.asarray(bt)[lens > 0])
        pool = randn(*sparse.index_pool_shape(L, nb, bs, d)) \
            .astype(jnp.bfloat16)
        poisoned = jnp.full_like(pool, jnp.nan).at[:, held].set(pool[:, held])
        q_idx = randn(len(ctx), 1, H, d).astype(jnp.bfloat16)
        w_idx = randn(len(ctx), 1, H).astype(jnp.bfloat16)
        args = (q_idx, w_idx, poisoned, bad, jnp.asarray(ctx),
                jnp.asarray(lens))
        # the result is padded to whole tiles: the table's width of it
        got = sparse.paged_index_scores(*args, layer=1,
                                        rows=8)[:, 0, :mb * bs]
        grid = sparse._index_scores(sparse._index_grid, *args, layer=1,
                                     rows=8)[:, 0, :mb * bs]
        want = sparse.paged_index_scores_xla(
            q_idx, w_idx, pool, bt, None, None, layer=1)[:, 0]
        read = (np.arange(mb * bs)[None] <= ctx[:, None]) \
            & (lens[:, None] > 0)
        diff_ok(jnp.where(read, got, 0), jnp.where(read, want, 0), 0.05)
        assert bool(jnp.all(jnp.where(read, got == grid, True))), \
            "the walk's scores are not the grid's, bit for bit"

    check("paged_index_scores_own_pages", paged_index_scores_own_pages)

    # compact MoE dispatch parity ON CHIP at true-f32 matmul precision —
    # round-4's 1.1e-2 divergence (bench_runs/MOE_20260731T034754Z.json)
    # was captured before the 06:54Z compact-gating rewrite; this pins the
    # chip-side verdict every window.
    def moe_compact():
        from deepspeed_tpu.comm import mesh as mesh_lib
        from deepspeed_tpu.moe.layer import MoELayer, init_moe_ffn

        mesh_lib.set_mesh(None)
        E, k, T, H = 16, 2, 2048, 512
        params = init_moe_ffn(jax.random.PRNGKey(0), n_experts=E, hidden=H,
                              intermediate=2 * H, dtype=jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, T, H), jnp.float32)
        with jax.default_matmul_precision("highest"):
            a, _ = MoELayer(n_experts=E, top_k=k, capacity_factor=1.25,
                            dispatch="einsum")(params, x)
            b, _ = MoELayer(n_experts=E, top_k=k, capacity_factor=1.25,
                            dispatch="compact")(params, x)
        diff_ok(a, b, 1e-3)
        mesh_lib.set_mesh(None)

    check("moe_compact_dispatch_parity", moe_compact)

    # FPDT at 128K: AOT compile the fwd+bwd on the REAL lowering (no
    # execute) and assert the compiled program's temp allocation is
    # chunk-sized, not S^2 — round-4's 32 GiB dense-score lowering
    # (bench_runs/LONGCTX_20260731T042825Z.json) predates the 04:58Z
    # flash-VJP rewrite; this catches any re-densification at compile time.
    def fpdt_128k_compile():
        from deepspeed_tpu.sequence.fpdt import fpdt_attention

        on_tpu = RESULT["detail"]["backend"] == "tpu"
        # off-TPU this is a smoke of the check itself — keep the trace cheap
        S, H, Hkv, D = (128 * 1024 if on_tpu else 16 * 1024), 8, 4, 128
        chunks = S // 8192

        def loss(q, k, v):
            o = fpdt_attention(q, k, v, chunks=chunks, causal=True,
                               offload_kv=True)
            return jnp.sum(o.astype(jnp.float32) ** 2)

        args = [jax.ShapeDtypeStruct((1, S, H, D), jnp.bfloat16),
                jax.ShapeDtypeStruct((1, S, Hkv, D), jnp.bfloat16),
                jax.ShapeDtypeStruct((1, S, Hkv, D), jnp.bfloat16)]
        compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).compile()
        ma = compiled.memory_analysis()
        temp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        RESULT["detail"]["fpdt_128k_temp_gib"] = round(temp / 2**30, 2)
        if on_tpu:
            # temp==0 means memory_analysis didn't report — a vacuous pass
            # here would blind the exact gate this check exists to be
            assert temp > 0, "memory_analysis reported no temp allocation"
        assert temp < 13 * 2**30, f"temp alloc {temp / 2**30:.1f} GiB >= 13"

    check("fpdt_128k_compile", fpdt_128k_compile)

    # norms at train AND decode row counts
    def norms():
        from deepspeed_tpu.ops.norms import layer_norm_xla, rms_norm_xla
        from deepspeed_tpu.ops.pallas.norms import (layer_norm_pallas,
                                                    rms_norm_pallas)

        w = 1.0 + 0.1 * randn(256)
        b = 0.1 * randn(256)
        for n in (1024, 3, 1):
            x = randn(n, 256)
            diff_ok(rms_norm_pallas(x, w), rms_norm_xla(x, w), 1e-4)
            diff_ok(layer_norm_pallas(x, w, b), layer_norm_xla(x, w, b), 1e-4)

    check("rms_norm/layer_norm", norms)

    # int8 quant roundtrip at odd group counts
    def quant():
        from deepspeed_tpu.ops.pallas.quantize import (dequantize_int8_pallas,
                                                       quantize_int8_pallas)
        from deepspeed_tpu.ops.quantization import quantize_int8_xla

        for groups in (64, 5):
            x = randn(groups * 256)
            qv, s = quantize_int8_pallas(x, group_size=256)
            qx, sx = quantize_int8_xla(x, group_size=256)
            assert (np.asarray(qv) == np.asarray(qx)).all()
            back = dequantize_int8_pallas(qv, s, group_size=256)
            diff_ok(back, x, float(jnp.max(jnp.abs(x))) / 127.0 + 1e-6)

    check("quantize/dequantize_int8", quant)

    # block-sparse attention vs dense-masked reference (fwd AND the round-5
    # skipping backward through the custom-vjp path)
    def sparse():
        from deepspeed_tpu.ops.attention import attention_xla
        from deepspeed_tpu.ops.pallas.sparse_attention import (
            sparse_flash_attention_fwd)
        from deepspeed_tpu.ops.sparse_attention import blocksparse_attention

        bs, nb = 128, 4
        layout = np.tril(np.ones((nb, nb), bool))
        layout[2, 0] = False  # ragged row
        q = randn(1, bs * nb, 4, 128).astype(jnp.bfloat16)
        k = randn(1, bs * nb, 4, 128).astype(jnp.bfloat16)
        v = randn(1, bs * nb, 4, 128).astype(jnp.bfloat16)
        out = sparse_flash_attention_fwd(q, k, v, layout, bs, causal=True)
        blk = jnp.kron(jnp.asarray(layout, jnp.int32),
                       jnp.ones((bs, bs), jnp.int32)).astype(bool)
        mask = blk[None, None] & (jnp.arange(bs * nb)[None, None, :, None]
                                  >= jnp.arange(bs * nb)[None, None, None, :])
        ref = attention_xla(q, k, v, causal=False, mask=mask)
        diff_ok(out, ref, 0.05)

        def loss(use_kernel, q):
            return jnp.sum(blocksparse_attention(
                q, k, v, layout, bs, causal=True,
                use_kernel=use_kernel).astype(jnp.float32) ** 2)

        gk = jax.grad(lambda q: loss(True, q))(q)
        gx = jax.grad(lambda q: loss(False, q))(q)
        diff_ok(gk, gx, 1.0)  # bf16 grad-scale tolerance; NaN/shape guard

    check("sparse_flash_attention", sparse)

    # a retention layer's two kernels over the state pool where it lies
    # (ops/pallas/retention.py, ISSUE 55) at the Brumby cell's head geometry,
    # each against its XLA twin: a live row, a fresh one, a row aimed at the
    # trash row whose state is NaN
    def retention_pool():
        from deepspeed_tpu.ops import retention as ret

        pool = 0.05 * jnp.abs(randn(*ret.state_shape(2, 3, 8, 128)))
        return pool.at[:, -1].set(jnp.nan)

    def retention_operands(*lead):
        bf = lambda x: x.astype(jnp.bfloat16)
        return (bf(randn(*lead, 40, 128)), bf(randn(*lead, 8, 128)),
                bf(randn(*lead, 8, 128)),
                jax.nn.log_sigmoid(2 + randn(*lead, 8)))

    def retention_decode():
        from deepspeed_tpu.ops import retention as ret
        from deepspeed_tpu.ops.pallas import retention as kernels

        rows = jnp.asarray([1, 3, 0, 3], jnp.int32)
        fresh = jnp.asarray([False, False, True, False])
        args = retention_operands(4)
        want_pool, want = ret.retention_decode_update_xla(
            retention_pool(), 1, rows, fresh, *args)
        pool, got = jax.jit(kernels.retention_decode_update)(
            retention_pool(), 1, rows, fresh, *args)
        live = jnp.asarray([0, 2])
        assert bool(jnp.isfinite(got[live]).all())
        diff_ok(got[live], want[live], 2e-3)
        diff_ok(pool[1, :2], want_pool[1, :2], 1e-4)
        diff_ok(pool[0, :3], retention_pool()[0, :3], 1e-9)
        diff_ok(pool[1, 2], retention_pool()[1, 2], 1e-9)

    check("retention_decode_update_in_place", retention_decode)

    def retention_chunked():
        from deepspeed_tpu.ops import retention as ret
        from deepspeed_tpu.ops.pallas import retention as kernels

        rows = jnp.asarray([2, 0], jnp.int32)
        fresh = jnp.asarray([False, True])
        args = retention_operands(2, 200)       # two tiles, the last short
        with jax.default_matmul_precision("highest"):
            want_pool, want = ret.retention_chunk_xla(
                retention_pool(), 0, rows, fresh, *args)
        pool, got = jax.jit(kernels.retention_chunk)(
            retention_pool(), 0, rows, fresh, *args)
        diff_ok(got, want, 0.05)                # bf16 operands on the MXU
        scale = float(jnp.abs(want_pool[0, 2]).mean())
        diff_ok(pool[0, jnp.asarray([2, 0])] / scale,
                want_pool[0, jnp.asarray([2, 0])] / scale, 0.5)
        diff_ok(pool[1, :3], retention_pool()[1, :3], 1e-9)
        diff_ok(pool[0, 1], retention_pool()[0, 1], 1e-9)

    check("retention_chunk_in_place", retention_chunked)

    RESULT["value"] = sum(1 for v in rows.values() if v == "ok")
    RESULT["detail"]["total"] = len(rows)
    emit_and_exit(ok=RESULT["value"] == len(rows))


if __name__ == "__main__":
    try:
        main()
    except SystemExit:
        raise
    except Exception as e:  # report in the JSON line, then fail
        RESULT["detail"]["error"] = str(e)[-2000:]
        RESULT["detail"]["ok"] = False
        print(json.dumps(RESULT))
        raise
