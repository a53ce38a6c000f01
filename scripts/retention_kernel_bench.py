#!/usr/bin/env python3
"""Times the two retention kernels alone, on the chip, at the Brumby cell's
shapes (``chiprun -- python3 scripts/retention_kernel_bench.py``): ONE
layer's ``retention_decode_update`` over 32 rows (all live, then 24 live and
8 aimed at the trash row) and ONE layer's ``retention_chunk`` over a 512-
and a 64-token row, on a ``[2, 33, 1032, 8704]`` float32 pool, 40 query and
8 key-value heads of 128, bfloat16 operands (two layers of the cell's
five: a call touches one, and the XLA twin beside the kernel needs the room). Prints one JSON line a case:
microseconds a call (median of ``--reps``), the share of the kernel's floor
- for the decode update each LIVE row's SYMMETRIC state (8256 products a
head: ``benchmark/harness/costs_retention.py`` counts the same) read once
and written once at the HBM peak, for the chunk the larger of its linear
form's operations at the bf16 peak and one read and one write of the row's
state -, and the largest difference of the outputs and of the written state
from the XLA twin's (float32 twin on the same bfloat16 operands).
``--tiny``: a schema run at a toy size, on any device (the CPU interprets
the kernels). A number from here is a kernel's, never a cell's."""

import argparse
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
    ap.add_argument("--tiny", action="store_true",
                    help="a schema run at a toy size, on any device")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import retention as ret
    from deepspeed_tpu.ops.pallas import retention as kernels

    L, S, nkv, g, d, b, chunks, tile = (2, 4, 2, 2, 16, 4, (24, 8), 8) \
        if args.tiny else (2, 32, 8, 5, 128, 32, (512, 64), 128)
    nh = nkv * g
    key = jax.random.split(jax.random.PRNGKey(0), 8)
    layer = jnp.int32(L // 2)
    entries = d * (d + 1) // 2
    row_bytes = nkv * entries * (d + 1) * 4         # the symmetric state's
    unit = lambda k_, shape: (jax.random.normal(k_, shape, jnp.float32)
                              ).astype(jnp.bfloat16)

    def fresh_pool():
        """Zeros but the timed layer, whose rows hold what four random
        tokens leave: a served state (every normaliser a sum of squares),
        so that the outputs compared are as well conditioned as a cell's."""
        t = 4
        k, v = unit(key[5], (S + 1, t, nkv, d)), unit(key[6], (S + 1, t,
                                                                nkv, d))
        lanes = ret.phi_rows(d)
        _, state, z = ret.retention_recurrence(
            jnp.zeros((S + 1, t, nkv, d)), k, v, jnp.zeros((S + 1, t, nkv)),
            jnp.zeros((S + 1, nkv, d, lanes)), jnp.zeros((S + 1, nkv, lanes)))
        shape = ret.state_shape(L, S, nkv, d)
        return jnp.zeros(shape, jnp.float32).at[layer].set(
            ret.state_from_heads(state, z, shape[2]))

    def differ(got, want, pool, want_pool, rows):
        live = [int(r) for r in rows if int(r) != S]
        state = lambda p: jnp.stack([jnp.concatenate([x.reshape(-1) for x in
            ret.state_to_heads(p[layer, r][None], nkv, d)]) for r in live])
        return {"largest_difference": float(jnp.abs(got - want).max()),
                "largest_state_difference": float(jnp.abs(
                    state(pool) - state(want_pool)).max()),
                "state_scale": float(jnp.abs(state(want_pool)).mean())}

    def timed(step, pool):
        ts = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            for _ in range(10):     # ten calls in flight: the device's
                pool, y = step(pool)    # time, not the dispatch's
            jax.block_until_ready(y)
            ts.append((time.perf_counter() - t0) / 10)
        return statistics.median(ts) * 1e6

    def say(case, us, floor_s, **more):
        print(json.dumps({"case": case, "us": us,
                          "floor_share": 100 * floor_s * 1e6 / us, **more,
                          "device": jax.devices()[0].device_kind}),
              flush=True)

    if args.only in ("", "decode"):
        q, k, v = (unit(key[1], (b, nh, d)), unit(key[2], (b, nkv, d)),
                   unit(key[3], (b, nkv, d)))
        log_g = jax.nn.log_sigmoid(2 + jax.random.normal(key[4], (b, nkv)))
        for idle in (0, b // 4):
            rows = jnp.where(jnp.arange(b) < b - idle, jnp.arange(b), S) \
                .astype(jnp.int32)
            fresh = jnp.arange(b) == 1
            step = jax.jit(lambda pool, rows=rows, fresh=fresh:
                           kernels.retention_decode_update(
                               pool, layer, rows, fresh, q, k, v, log_g),
                           donate_argnums=0)
            twin = jax.jit(lambda pool, rows=rows, fresh=fresh:
                           ret.retention_decode_update_xla(
                               pool, layer, rows, fresh, q, k, v, log_g))
            with jax.default_matmul_precision("highest"):
                want_pool, want = jax.block_until_ready(twin(fresh_pool()))
            pool, got = step(fresh_pool())
            n = b - idle
            seen = differ(got[:n], want[:n], pool, want_pool, rows)
            del want_pool
            say(f"decode_{n}_live_of_{b}", timed(step, pool),
                2 * n * row_bytes / HBM, **seen)
            del pool
    if args.only in ("", "chunk"):
        for t in chunks:
            q, k, v = (unit(key[1], (1, t, nh, d)),
                       unit(key[2], (1, t, nkv, d)),
                       unit(key[3], (1, t, nkv, d)))
            log_g = jax.nn.log_sigmoid(
                2 + jax.random.normal(key[4], (1, t, nkv)))
            rows, fresh = jnp.asarray([1], jnp.int32), jnp.asarray([False])
            step = jax.jit(lambda pool, q=q, k=k, v=v, log_g=log_g:
                           kernels.retention_chunk(
                               pool, layer, rows, fresh, q, k, v, log_g,
                               tile=tile), donate_argnums=0)
            twin = jax.jit(lambda pool, q=q, k=k, v=v, log_g=log_g:
                           ret.retention_chunk_xla(
                               pool, layer, rows, fresh, q, k, v, log_g,
                               tile=tile))
            with jax.default_matmul_precision("highest"):
                want_pool, want = jax.block_until_ready(twin(fresh_pool()))
            pool, got = step(fresh_pool())
            seen = differ(got, want, pool, want_pool, rows)
            del want_pool
            flops = t * (nh + nkv) * 2.0 * entries * (d + 1)
            say(f"chunk_{t}", timed(step, pool),
                max(flops / MXU, 2 * row_bytes / HBM), **seen)
            del pool
    return 0


if __name__ == "__main__":
    sys.exit(main())
