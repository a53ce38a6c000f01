#!/usr/bin/env python3
"""Times the learned selection's kernels alone, on the chip, at the Keye
cell's shapes (``chiprun -- python3 scripts/sparse_kernel_bench.py``): one
layer's calls of a tick - a 512-row chunk and 8 decode rows at ``--ctx``
cached tokens each - at several tile sizes, one JSON line a case:
milliseconds a call, median of ``--reps``. How the tile sizes in
``ops/pallas/paged_sparse_attention.py`` were chosen (PERF.md section 6,
PR 38).

Before them, the decode rows' two walks - their masked attention
(``paged_sparse_decode``, ``--only walk``) and their index scores
(``paged_index_scores`` at one token a sequence, ``--only index``) - as the
PARENT commit builds each against this tree's, each side in a process of its
own (a chip belongs to one process, so this one stays off JAX until all are
done): 8 slots of which half are idle (the scores: all 8 decoding too),
contexts drawn log-uniformly over 6-31 k tokens as the cell's prompts are,
block ids scattered over the pool. One JSON line a (side, seed):
microseconds a call (``--layers`` calls in one program, median of
``--reps``), the share of the dense-read floor (the decoding rows' whole live
context - K and V, or the index keys - at the chip's published HBM rate) and
how far the result lies from the gathered XLA op's. Then the chunk's masked
walk (``paged_sparse_prefill``, 512 rows, ``--only prefill``) as the tree
builds it (``own``: the kernel that fetches its own pages, ISSUE 63) against
the grid of ``BlockSpec`` pages it replaced (``grid``: ``_fetches_pages``
forced off, the parent's walk) at ``--contexts``, by the DEVICE's time of the
kernel's own events in one traced run of a program of ``--layers`` calls (a
call of ~1 ms is of the order of the host's dispatch), and whether the two
results are one to the bit. The parent is
``--parent DIR`` (an unpacked ``git archive``: what a chip machine, which has
no ``.git``, needs) or else ``git archive --parent-rev`` unpacked under
``/tmp``. A number from here is a kernel's, never a cell's."""

import argparse
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}    # Google Cloud, "TPU v5e"
L, NB, BS, D, H = 2, 7808, 32, 64, 16
NKV, NH, HD, MB, TOPK = 4, 32, 128, 1024, 2048


def side_of(args):
    """The selection's kernels of the side this process times:
    ``deepspeed_tpu`` is whatever ``args.walk_of`` holds."""
    sys.path.insert(0, os.path.abspath(args.walk_of))
    from deepspeed_tpu.ops.pallas import paged_sparse_attention as S

    assert os.path.abspath(S.__file__).startswith(
        os.path.abspath(args.walk_of)), S.__file__
    return S


def table_of(args):
    """(pool blocks, table width, slots) of a walk's case."""
    return (80, 16, 4) if args.tiny else (NB, MB, 8)


def drawn_slots(args, seed: int, idle_share: int = 2):
    """(contexts, idle, block tables) of a case's slots: contexts
    log-uniform over the cell's prompts' range, one slot in ``idle_share``
    idle (0: none) at context 0 on the trash block, block ids scattered."""
    import numpy as np

    nb, mb, slots = table_of(args)
    rng = np.random.default_rng(seed)
    lo, hi = (6144, 30720) if not args.tiny else (100, mb * BS - 2)
    ctx = np.exp(rng.uniform(np.log(lo), np.log(hi), slots)).astype(np.int64)
    idle = rng.permutation(slots) < (slots // idle_share if idle_share
                                     else 0)
    ctx[idle] = 0
    tables = rng.integers(1, nb, (slots, mb))
    tables[idle] = 0
    return ctx, idle, tables


def median_us(fn, ops, args) -> float:
    """Microseconds a call of ``fn``, a program of ``args.layers`` calls."""
    import jax

    jax.block_until_ready(fn(*ops))
    ts = []
    for _ in range(args.reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*ops))
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) / args.layers * 1e6


def index_scores(args) -> int:
    """One side's timing of the decode rows' index scores."""
    S = side_of(args)
    import jax
    import jax.numpy as jnp
    import numpy as np

    nb, mb, slots = table_of(args)
    bf, i32 = jnp.bfloat16, jnp.int32
    key = jax.random.PRNGKey(0)
    pool = jax.random.normal(key, S.index_pool_shape(L, nb, BS, D), bf)
    q_idx = jax.random.normal(jax.random.fold_in(key, 1), (slots, 1, H, D),
                              bf)
    w_idx = jax.random.normal(jax.random.fold_in(key, 2), (slots, 1, H), bf)
    kind = jax.devices()[0].device_kind
    if args.index_pages:
        S._INDEX_PAGES = args.index_pages

    def scores(q_, w_, p_, tb_, ctx_, lens_, layer):
        return S.paged_index_scores(q_, w_, p_, tb_, ctx_, lens_,
                                    layer=layer, rows=8)

    def program(*ops):
        def layer(i, acc):      # a lane tile of every row keeps the call
            return acc + scores(*ops, i % L)[:, 0, :128]
        return jax.lax.fori_loop(0, args.layers, layer,
                                 jnp.zeros((slots, 128), jnp.float32))

    fn, one = jax.jit(program), jax.jit(scores)
    for seed in args.seeds:
        for idle_share in (2, 0):
            ctx, idle, tables = drawn_slots(args, seed, idle_share)
            ops = (q_idx, w_idx, pool, jnp.asarray(tables, i32),
                   jnp.asarray(ctx, i32), jnp.asarray(~idle, i32))
            got = np.asarray(one(*ops, 1))[:, 0]
            want = np.asarray(S.paged_index_scores_xla(*ops, layer=1))[:, 0]
            read = (np.arange(want.shape[1])[None] <= ctx[:, None]) \
                & ~idle[:, None]
            us = median_us(fn, ops, args)
            floor = float((ctx[~idle] + 1).sum()) * D * 2 \
                / HBM_BYTES_PER_S[kind] * 1e6 \
                if kind in HBM_BYTES_PER_S else None
            print(json.dumps({
                "case": "index_scores decode rows", "side": args.side,
                "seed": seed, "decoding": int((~idle).sum()),
                "index_pages": S._INDEX_PAGES, "contexts": ctx.tolist(),
                "us": us, "floor_us": floor,
                "floor_share": floor and floor / us,
                "max_abs_diff_from_xla": float(np.abs(np.where(
                    read, got[:, :want.shape[1]] - want, 0)).max()),
                "device": kind}), flush=True)
    return 0


def decode_walk(args) -> int:
    """One side's timing of the decode rows' masked walk."""
    S = side_of(args)
    import jax
    import jax.numpy as jnp
    import numpy as np

    nb, mb, slots = table_of(args)
    bf, i32 = jnp.bfloat16, jnp.int32
    key = jax.random.PRNGKey(0)
    kpool, vpool = (jax.random.normal(jax.random.fold_in(key, i),
                                      (L, nb, NKV, BS, HD), bf)
                    for i in range(2))
    q = jax.random.normal(key, (slots, NH, HD), bf)
    kind = jax.devices()[0].device_kind

    def walk(q_, k_, v_, idx_, tau_, cut_, tb_, ctx_, layer):
        return S.paged_sparse_decode_attention(q_, k_, v_, idx_, tau_, cut_,
                                               tb_, ctx_, layer=layer)

    def program(*ops):
        def layer(i, acc):
            return acc + walk(*ops, i % L).astype(jnp.float32)
        return jax.lax.fori_loop(0, args.layers, layer,
                                 jnp.zeros((slots, NH, HD), jnp.float32))

    fn, one = jax.jit(program), jax.jit(walk)
    select = jax.jit(lambda s_, q_: S.paged_sparse_select(
        s_, q_, topk=min(TOPK, mb * BS // 4)))
    for seed in args.seeds:
        ctx, idle, tables = drawn_slots(args, seed)
        idx = jax.random.normal(jax.random.fold_in(key, seed),
                                (slots, 8, mb * BS), jnp.float32)
        tau, cut = select(idx[:, 0], jnp.asarray(ctx, i32))
        ops = (q, kpool, vpool, idx, tau, cut, jnp.asarray(tables, i32),
               jnp.asarray(ctx, i32))
        got = np.asarray(one(*ops, 1), np.float32)
        want = np.asarray(S.paged_sparse_decode_attention_xla(*ops, layer=1),
                          np.float32)
        us = median_us(fn, ops, args)
        floor = float((ctx[~idle] + 1).sum()) * NKV * HD * 2 * 2 \
            / HBM_BYTES_PER_S[kind] * 1e6 if kind in HBM_BYTES_PER_S else None
        print(json.dumps({
            "case": "sparse_decode walk", "side": args.side, "seed": seed,
            "contexts": ctx.tolist(), "us": us, "floor_us": floor,
            "floor_share": floor and floor / us,
            "max_abs_diff_from_xla": float(np.abs(
                got - want)[~idle].max()), "device": kind}), flush=True)
    return 0


def prefill_walk(args) -> int:
    """The chunk's masked walk, ``own`` against ``grid``: a JSON line a
    (form, context) - microseconds a call by the kernel's own device events
    (median of ``--layers`` calls; None off a chip), the KV tiles a call
    takes - and a ``pair`` line a context: the change and whether the results
    are bit-equal."""
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas import paged_attention as pa
    from deepspeed_tpu.ops.pallas import paged_sparse_attention as S
    from scripts.prefill_tile_bench import kernel_us

    nb, mb, _ = table_of(args)
    t, bf, i32 = (16 if args.tiny else 512), jnp.bfloat16, jnp.int32
    topk = 64 if args.tiny else TOPK
    layers = 2 if args.tiny else args.layers
    contexts = [c for c in args.contexts if c + t <= mb * BS] \
        or [mb * BS - t]
    key = jax.random.PRNGKey(0)
    k, v = (jax.random.normal(jax.random.fold_in(key, i),
                              (L, nb, NKV, BS, HD), bf) for i in range(2))
    q = jax.random.normal(key, (1, t, NH, HD), bf)
    rows = S.prefill_rows(t, NH, NKV, HD, BS, mb)
    idx = jax.random.normal(key, (1, rows, mb * BS), jnp.float32)
    tables = jnp.asarray(np.random.default_rng(0).integers(1, nb, (1, mb)),
                         i32)
    lens = jnp.full((1,), t, i32)
    fetches, where = pa._fetches_pages, tempfile.mkdtemp(dir="/tmp")
    read = {}
    for form in ("own", "grid"):    # the walk's choice, and the host mirror's
        S._fetches_pages = pa._fetches_pages = \
            fetches if form == "own" else lambda *a: False

        def walk(ctx_, tau_, cut_, layer):      # a new one a form: jit
            return S.paged_sparse_prefill_attention(    # keeps its trace
                q, k, v, idx, tau_, cut_, tables, ctx_, lens, layer=layer)

        def program(ctx_, tau_, cut_):
            def layer(i, acc):
                return acc + walk(ctx_, tau_, cut_, i % L).astype(jnp.float32)
            return jax.lax.fori_loop(0, layers, layer,
                                     jnp.zeros((1, t, NH, HD), jnp.float32))

        fn, one = jax.jit(program), jax.jit(walk)
        ops = []
        for c in contexts:
            ctx = jnp.full((1,), c, i32)
            tau, cut = S.paged_sparse_select(
                idx[0], c + jnp.arange(rows), topk=topk)
            ops.append((ctx, tau[None], cut[None]))
        jax.block_until_ready(fn(*ops[-1]))
        us = kernel_us(lambda: jax.block_until_ready([fn(*o) for o in ops]),
                       where, layers * len(contexts), "paged_sparse_prefill")
        for i, (c, o) in enumerate(zip(contexts, ops)):
            pages = S.prefill_pages(t, NH, (NKV, BS, HD), mb)
            live, taken, _ = pa.prefill_tile_counts(
                [c], [t], t, NH, (NKV, BS, HD), mb, pages=pages)
            mine = us and float(np.median(us[i * layers:(i + 1) * layers]))
            read.setdefault(c, {})[form] = (mine, np.asarray(one(*o, 1)))
            print(json.dumps({
                "case": "sparse_prefill 512 rows", "form": form, "ctx": c,
                "us": mine, "kv_tile": pages * BS, "steps_live": live,
                "steps_taken": taken,
                "device": jax.devices()[0].device_kind}), flush=True)
    S._fetches_pages = pa._fetches_pages = fetches
    shutil.rmtree(where, ignore_errors=True)
    for c, forms in read.items():
        (own, a), (grid, b) = forms["own"], forms["grid"]
        print(json.dumps({
            "pair": "sparse_prefill 512 rows", "ctx": c, "own_us": own,
            "grid_us": grid, "change": own and grid and own / grid - 1,
            "bit_equal": bool(np.array_equal(a, b))}), flush=True)
    return 0


def both_sides(args, case: str) -> int:
    """The parent's kernel of ``case``, then this tree's, each in its own
    process."""
    with tempfile.TemporaryDirectory(dir="/tmp") as tmp:
        if args.parent is None:
            tar = subprocess.run(["git", "archive", args.parent_rev],
                                 cwd=ROOT, capture_output=True)
            if tar.returncode:
                print("no --parent DIR and no git archive "
                      f"{args.parent_rev}: {tar.stderr.decode().strip()}",
                      file=sys.stderr)
                return 2
            subprocess.run(["tar", "-x", "-C", tmp], input=tar.stdout,
                           check=True)
        for side, root in (("parent", args.parent or tmp), ("change", ROOT)):
            rc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--walk-of", root,
                 "--side", side, "--case", case, "--layers", str(args.layers), "--reps",
                 str(args.reps), "--seeds", *map(str, args.seeds)]
                + ["--tiny"] * args.tiny
                + (["--index-pages", str(args.index_pages)]
                   if args.index_pages and side == "change" else [])
            ).returncode
            if rc:
                return rc
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ctx", type=int, default=15000)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--only", choices=["walk", "index", "prefill", "tiles"],
                    help="the decode rows' masked walk or their index "
                    "scores, parent against tree, the chunk's masked walk, "
                    "own pages against the grid, or the other kernels' tile "
                    "sizes; all four where not given")
    ap.add_argument("--contexts", type=int, nargs="*",
                    default=[2048, 8192, 15000, 30000],
                    help="--only prefill: cached tokens under the chunk")
    ap.add_argument("--parent", help="the parent commit, unpacked")
    ap.add_argument("--parent-rev", default="HEAD")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--seeds", type=int, nargs="*", default=[0, 1, 2])
    ap.add_argument("--tiny", action="store_true",
                    help="the walk as a schema run at a toy table (the "
                    "CPU's interpreter; heads stay 128 lanes)")
    ap.add_argument("--walk-of", help=argparse.SUPPRESS)
    ap.add_argument("--side", help=argparse.SUPPRESS)
    ap.add_argument("--case", help=argparse.SUPPRESS)
    ap.add_argument("--index-pages", type=int,
                    help="--only index: the tree's scores at a forced tile "
                    "of this many pages (a power of two)")
    args = ap.parse_args()
    if args.walk_of:
        return {"walk": decode_walk, "index": index_scores,
                "prefill": prefill_walk}[args.case](args)
    for case in ("walk", "index"):
        if args.only in (None, case):
            rc = both_sides(args, case)
            if rc:
                return rc
    if args.only in (None, "prefill"):
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--case", "prefill",
             "--walk-of", ROOT, "--layers", str(args.layers), "--contexts",
             *map(str, args.contexts)] + ["--tiny"] * args.tiny).returncode
        if rc:
            return rc
    if args.only not in (None, "tiles"):
        return 0
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.ops.pallas import paged_sparse_attention as S

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
        # the decode rows' scores walk their own pages: the walk's tile; a
        # chunk's scores take ``_MAX_PAGES``
        for pages in (32, 64, 128) if t == 1 else (None,):
            if pages:
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
        if t == 1:      # its tile is paged_attention._decode_tiles' to say
            fn = jax.jit(lambda *a: S.paged_sparse_decode_attention(
                *a, layer=layer))
            timed("sparse_decode 8 rows", fn, q[:, 0], kpool, vpool, idx,
                  tau[:, 0], cut[:, 0], tb, ctx)
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
