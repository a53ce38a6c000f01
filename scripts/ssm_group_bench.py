#!/usr/bin/env python3
"""Times the single-token state update alone, on the chip, at the Nemotron-3
cell's shapes (``chiprun -- python3 scripts/ssm_group_bench.py``): ONE Mamba
layer's ``ssm_decode_update`` over 64 rows of a ``[23, 65, 136, 4096]``
float32 pool with 8 groups of B and C, at several lane blocks - 2048 lanes
(a block spans four 512-lane groups and takes each group's B and C to its
own lanes), 512 (a block a group, four times the grid steps), 1024 - and,
beside them, Granite's one group at 2048. Prints one JSON line a case:
microseconds a call (median of ``--reps``), the share of the HBM floor (each
live row's state read once and written once), and the largest difference
from the XLA twin's result. How the block in ``ops/pallas/ssm.py`` was
chosen (PERF.md section 6, PR 50); a number from here is a kernel's, never a
cell's."""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--lanes", default="2048,1024,512")
    ap.add_argument("--tiny", action="store_true",
                    help="a schema run at a toy size, on any device")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import ssm
    from deepspeed_tpu.ops.pallas import ssm as kernels

    L, S, N, T, HP, b = (2, 5, 16, 8, 256, 4) if args.tiny else (
        23, 64, 128, 8, 4096, 64)
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    rows = jnp.arange(b, dtype=jnp.int32)
    fresh = jnp.zeros((b,), bool)
    decay = jax.random.uniform(k[1], (b, HP), jnp.float32, 0.5, 1.0)
    dtx = jax.random.normal(k[2], (b, HP), jnp.float32)
    layer = jnp.int32(L // 2)
    floor_s = 2 * b * N * HP * 4 / 819e9
    for groups in (8, 1):
        B, C = (jax.random.normal(k[i], (b, groups, N), jnp.bfloat16)
                for i in (3, 4))
        if groups == 1:
            B, C = B[:, 0], C[:, 0]
        for lanes in [int(x) for x in args.lanes.split(",")]:
            if groups == 1 and lanes != 2048:
                continue
            kernels._LANES = lanes
            # (a new function a case: ``_LANES`` is read when it is traced)
            step = jax.jit(lambda pool, B=B, C=C: kernels.ssm_decode_update(
                pool, layer, rows, fresh, decay, dtx, B, C),
                donate_argnums=0)
            pool = jax.random.normal(k[0], (L, S + 1, N + T, HP),
                                     jnp.float32)
            want_pool, want_y = ssm.ssm_decode_update_xla(
                pool, layer, rows, fresh, decay, dtx, B, C)
            want_pool, want_y = jax.block_until_ready((want_pool, want_y))
            pool, y = step(pool)
            worst = max(float(jnp.abs(y - want_y).max()),
                        float(jnp.abs(pool - want_pool).max()))
            del want_pool
            ts = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                for _ in range(10):     # ten calls in flight: the device's
                    pool, y = step(pool)    # time, not the dispatch's
                jax.block_until_ready(y)
                ts.append((time.perf_counter() - t0) / 10)
            us = statistics.median(ts) * 1e6
            print(json.dumps({
                "case": f"groups_{groups}_lanes_{lanes}", "us": us,
                "hbm_floor_share": 100 * floor_s * 1e6 / us,
                "largest_difference": worst,
                "device": jax.devices()[0].device_kind}), flush=True)
            del pool
    return 0


if __name__ == "__main__":
    sys.exit(main())
