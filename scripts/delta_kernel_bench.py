#!/usr/bin/env python3
"""Times the two delta-rule ops alone, on the chip, at the Solar-Open2 cell's
shapes (``chiprun -- python3 scripts/delta_kernel_bench.py``): ONE layer's
``delta_decode_update`` over 16 rows (all live, then 12 live and 4 aimed at
the trash row) and ONE layer's ``delta_chunk`` over a 512- and a 64-token
row, on a ``[2, 17, 144, 8192]`` float32 pool, 64 heads of 128 x 128 (two
layers of the cell's three KDA layers: a call touches one). The chunk runs in
BOTH forms in one run, each with the write of the row's new state: the
Mosaic kernel (``ops/pallas/delta_chunk.py``) and the XLA form between the row-table kernels it
replaced (``delta_chunk_between_rows``; ``--tiles 64,128``: that form's
tile), the token operands ARGUMENTS of the program as they are in the cell
(closed over, XLA folds part of the XLA form's preparation away: PR 57's 678
us was such a reading). Prints one JSON line a case: microseconds a call
(the decode update: the median of ``--reps`` of ten calls in flight; the
chunk: the DEVICE's time from three traced calls, every operation its own
time - a kernel call is shorter than the host's dispatch of one -, with the
wall time beside it), the share of the op's floor - for the decode update
each LIVE row's state read once and written once at the HBM peak; for the
chunk the larger of the recurrence's operations at the bf16 peak and one
read and one write of the row's state (``floor_share``:
``benchmark/harness/costs_delta.py`` counts the same), and that with the
token operands in and the float32 output out (``floor_share_with_operands``:
the kernel's real traffic) -, and the largest difference of the outputs and
of the written state from the token-by-token recurrence at full precision
under STRONG decay (``log a`` down to -6 a token). ``--profile``: a chunk
case's device operations by time. ``--tiny``: a schema run
at a toy size, on any device (the CPU interprets the kernels). A number from
here is an op's, never a cell's."""

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

HBM, MXU = 819e9, 197e12    # v5e's published peaks (benchmark/harness/peaks.py)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--only", default="", help="decode | chunk")
    ap.add_argument("--tiles", default="64")
    ap.add_argument("--profile", action="store_true",
                    help="chunk: one traced call a form, its operations "
                         "by device time")
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--tiny", action="store_true",
                    help="a schema run at a toy size, on any device")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import delta
    from deepspeed_tpu.ops.pallas import delta as kernels

    L, S, H, d, tail, b, chunks = (2, 4, 2, 128, 16, 4, (40, 8)) \
        if args.tiny else (2, 16, 64, 128, 16, 16, (512, 64))
    key = jax.random.split(jax.random.PRNGKey(0), 8)
    layer = jnp.int32(L // 2)
    row_bytes = H * d * d * 4

    def tokens(shape_bt):
        """A token's operands as the family makes them: unit-length q and
        k, a decay of 0 to -6 a channel, a step in (0, 2)."""
        unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
        q = unit(jax.random.normal(key[1], shape_bt + (H, d))) * d ** -0.5
        k = unit(jax.random.normal(key[2], shape_bt + (H, d)))
        v = jax.random.normal(key[3], shape_bt + (H, d)).astype(jnp.bfloat16)
        log_a = -6.0 * jax.random.uniform(key[4], shape_bt + (H, d)) ** 3
        beta = 2.0 * jax.random.uniform(key[5], shape_bt + (H,))
        return q, k, v, log_a, beta

    def fresh_pool():
        pool = jnp.zeros((L, S + 1, d + tail, H * d), jnp.float32)
        state = 0.1 * jax.random.normal(key[6], (S + 1, d, H * d))
        return pool.at[layer, :, :d].set(state)

    def differ(got, want, pool, want_pool, live):
        at = jnp.asarray(live)
        return {"largest_difference": float(jnp.abs(got - want).max()),
                "output_scale": float(jnp.abs(want).mean()),
                "largest_state_difference": float(jnp.abs(
                    pool[layer, at, :d] - want_pool[layer, at, :d]).max()),
                "tail_untouched": bool(
                    (pool[layer, :, d:] == 0).all())}

    def timed(step, pool):
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            for _ in range(10):     # ten calls in flight: the device's
                pool, y = step(pool)    # time, not the dispatch's
            jax.block_until_ready(y)
            ts.append((time.perf_counter() - t0) / 10)
        return statistics.median(ts) * 1e6

    def device_time(step, pool):
        """Three traced calls: the device's time a call in microseconds -
        every operation its own time, a loop less its body's (the host's
        dispatch of a call, ~340 us, is longer than the kernel: calls timed
        from the host one by one time the host; and a program that chains
        them lets XLA hoist the XLA form's preparation, which does not
        depend on the state, out of the chain) - and the operations by
        time, dearest first (``[name, us, calls]``)."""
        import shutil
        import tempfile

        from jax.profiler import ProfileData

        from benchmark.harness import trace as tr

        where = tempfile.mkdtemp()
        try:
            with jax.profiler.trace(where):
                for _ in range(3):
                    pool, y = step(pool)
                jax.block_until_ready(y)
            data = ProfileData.from_file(tr.find_xplane(where))
        finally:
            shutil.rmtree(where, ignore_errors=True)
        ops, calls = [], {}
        for plane in data.planes:
            if not plane.name.startswith("/device:TPU:0"):
                continue
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    ops.append(tr.Op(e.name[:80], e.start_ns,
                                     e.start_ns + e.duration_ns, "xla"))
                    calls[e.name[:80]] = calls.get(e.name[:80], 0) + 1
        if not ops:     # no device plane: not a chip
            return None, []
        own = tr.self_times(ops, (min(o.start for o in ops),
                                  max(o.end for o in ops)))
        return sum(own.values()) / 3 * 1e6, [
            [name, round(s / 3 * 1e6, 2), calls[name] // 3] for name, s in
            sorted(own.items(), key=lambda kv: -kv[1])[:args.top]]

    def say(case, us, floor_s, **more):
        print(json.dumps({"case": case, "us": us,
                          "floor_share": us and 100 * floor_s * 1e6 / us,
                          **more,
                          "device": jax.devices()[0].device_kind}),
              flush=True)

    if args.only in ("", "decode"):
        ops = tokens((b,))
        for idle in (0, b // 4):
            rows = jnp.where(jnp.arange(b) < b - idle, jnp.arange(b), S) \
                .astype(jnp.int32)
            fresh = jnp.arange(b) == 1
            step = jax.jit(lambda pool, rows=rows, fresh=fresh:
                           kernels.delta_decode_update(
                               pool, layer, rows, fresh, *ops),
                           donate_argnums=0)
            twin = jax.jit(lambda pool, rows=rows, fresh=fresh:
                           delta.delta_decode_update_xla(
                               pool, layer, rows, fresh, *ops))
            with jax.default_matmul_precision("highest"):
                want_pool, want = jax.block_until_ready(twin(fresh_pool()))
            pool, got = step(fresh_pool())
            n = b - idle
            seen = differ(got[:n], want[:n], pool, want_pool, range(n))
            del want_pool
            say(f"decode_{n}_live_of_{b}", timed(step, pool),
                2 * n * row_bytes / HBM, **seen)
            del pool
    if args.only in ("", "chunk"):
        for t in chunks:
            ops = tokens((1, t))
            rows, fresh = jnp.asarray([1], jnp.int32), jnp.asarray([False])

            def twin(pool):
                S0 = delta.state_to_heads(pool[layer, rows, :d], H)
                o, S1 = delta.delta_recurrence(*ops, S0)
                return pool.at[layer, rows, :d].set(
                    delta.state_from_heads(S1)), o

            with jax.default_matmul_precision("highest"):
                want_pool, want = jax.block_until_ready(
                    jax.jit(twin)(fresh_pool()))
            # both floors of a call: the row's state read and written once,
            # and that with the token operands in (q, k, log_a float32, v
            # bfloat16, beta) and the float32 output out - the kernel's
            # real traffic
            state_s = 2 * row_bytes / HBM
            operands_s = state_s + t * H * (d * (3 * 4 + 2 + 4) + 4) / HBM
            mxu_s = t * 6.0 * H * d * d / MXU
            cases = [("kernel", 0, kernels.delta_chunk)] + [
                ("xla", tile, functools.partial(
                    kernels.delta_chunk_between_rows, tile=tile))
                for tile in map(int, args.tiles.split(","))]
            for form, tile, op in cases:
                # (the operands are ARGUMENTS: closed over, 75 MB of them
                # are constants of the program)
                step = jax.jit(lambda pool, *ops, op=op: op(
                    pool, layer, rows, fresh, *ops), donate_argnums=0)
                pool, got = step(fresh_pool(), *ops)
                seen = differ(got, want, pool, want_pool, [1])
                us, dearest = device_time(
                    lambda pool: step(pool, *ops), pool)
                if args.profile:
                    print(json.dumps({"case": f"chunk_{t}_{form}",
                                      "profile": dearest}), flush=True)
                pool = fresh_pool()
                wall = timed(lambda pool: step(pool, *ops), pool)
                say(f"chunk_{t}_{form}" + (f"_tile_{tile}" if tile else ""),
                    us, max(mxu_s, state_s), wall_us_dispatched_one_by_one=wall,
                    floor_share_with_operands=us and 100 * max(
                        mxu_s, operands_s) * 1e6 / us,
                    state_floor_us=state_s * 1e6,
                    operands_floor_us=operands_s * 1e6,
                    mxu_floor_us=mxu_s * 1e6,
                    finite=bool(jnp.isfinite(got).all()), **seen)
                del pool
            del want_pool
    return 0


if __name__ == "__main__":
    sys.exit(main())
