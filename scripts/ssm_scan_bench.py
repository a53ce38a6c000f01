#!/usr/bin/env python3
"""Times a multi-token segment's state-space scan alone, on the chip, at
the two cells' shapes (``chiprun -- python3 scripts/ssm_scan_bench.py``):
ONE Mamba layer's ``ssm_chunk_scan`` on a ``[23, 65, 136, 4096]`` float32
pool - Nemotron-3-Nano's 512-row chunk with 8 groups of B and C, Granite's
256-row chunk with one, and the probes' 2048-row call - as the Mosaic kernel
(``ops/pallas/ssm_scan.py``) and as the XLA form it replaced
(``ops/ssm.ssm_chunk_scan_xla``: the rows read and ``ssd_chunked_scan``),
each followed by the write of the rows' new state. A case is timed as one
program of ``layers`` calls, a layer
of the pool each, so the host's dispatch is no part of it. Prints one JSON
line a case: microseconds a layer of each form (median of ``--reps``), the
kernel's share of its floor - the larger of its bytes (``x``, ``dt``, ``B``,
``C`` in, ``y`` out, the row's state read and written once) at 819 GB/s and
its operations at 197 TFLOP/s -, and the largest difference between the two
forms and from the token-by-token recurrence (the state whole, ``y`` at the
segment's last token). A number from here is a
kernel's, never a cell's (PERF.md section 6, PR 58)."""

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CASES = {       # name: (rows, tokens, groups, the XLA form's block)
    "nemotron_chunk512": (1, 512, 8, 128),
    "granite_chunk256": (1, 256, 1, 256),
    "nemotron_probe2048": (1, 2048, 8, 128),
}


def floors_us(b, t, H, P, N, G, tile):
    """(HBM, MXU) floors of one call in microseconds, from its shapes."""
    tiles = -(-t // tile)
    flops = b * tiles * (2 * tile * tile * N * G
                         + H * (2 * tile * tile * P + 4 * tile * N * P))
    bytes_ = b * (t * H * P * (2 + 4) + 2 * t * G * N * 2 + t * H * 4
                  + 2 * N * H * P * 4)
    return bytes_ / 819e9 * 1e6, flops / 197e12 * 1e6


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--cases", nargs="*", default=sorted(CASES))
    ap.add_argument("--tiny", action="store_true",
                    help="a schema run at a toy size, on any device")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops import ssm
    from deepspeed_tpu.ops.pallas import ssm as kernels
    from deepspeed_tpu.ops.pallas import ssm_scan

    L, S, N, T, H, P = (2, 3, 128, 8, 4, 64) if args.tiny else (
        23, 64, 128, 8, 64, 64)
    for name in args.cases:
        b, t, G, block = CASES[name]
        if args.tiny:
            t, G = t // 4 + 3, min(G, 2)
        # (the CPU's runtime has no bfloat16 dot for the interpreted kernel)
        mx = jnp.float32 if args.tiny else jnp.bfloat16
        k = jax.random.split(jax.random.PRNGKey(0), 8)
        x = jax.random.normal(k[1], (b, t, H, P), mx)
        dt = jax.nn.softplus(jax.random.normal(k[2], (b, t, H)) - 2.0)
        A = -jax.random.uniform(k[3], (H,), jnp.float32, 1.0, 16.0)
        shape = (b, t, N) if G == 1 else (b, t, G, N)
        B, C = (jax.random.normal(k[i], shape, mx) for i in (4, 5))
        rows = jnp.arange(b, dtype=jnp.int32) + 1
        fresh = jnp.zeros((b,), bool)

        def layers_of(op):
            def run(pool):
                def layer(carry, index):
                    pool, total, _ = carry
                    y, new = op(pool, index, rows, fresh, x, dt, A, B, C,
                                block)
                    pool = kernels.state_rows_write(pool, index, rows, new,
                                                    (0, N, H * P))
                    # (y is read once more, for its sum: a carried y would
                    # be copied a layer, 8 MB at Nemotron's shape)
                    return (pool, total + jnp.sum(y), y[:, -1]), None
                (pool, total, last), _ = jax.lax.scan(
                    layer, (pool, jnp.zeros(()), jnp.zeros((b, H * P))),
                    jnp.arange(L, dtype=jnp.int32))
                return pool, total, last
            return jax.jit(run, donate_argnums=0)

        out, us = {}, {}
        for form, op in (("kernel", ssm_scan.ssm_chunk_scan),
                         ("xla", ssm.ssm_chunk_scan_xla)):
            run = layers_of(op)
            pool = jax.random.normal(k[0], (L, S + 1, N + T, H * P),
                                     jnp.float32)
            pool, _, y = jax.block_until_ready(run(pool))
            out[form] = (pool[L - 1, 1, :N], y)
            ts = []
            for _ in range(args.reps):
                t0 = time.perf_counter()
                for _ in range(3):
                    pool, total, y = run(pool)
                jax.block_until_ready(total)
                ts.append((time.perf_counter() - t0) / (3 * L))
            us[form] = statistics.median(ts) * 1e6
            del pool, y, total
        # the truth: the recurrence a token at a time over the first run's
        # last layer (every layer starts from the same draw)
        pool = jax.random.normal(k[0], (L, S + 1, N + T, H * P), jnp.float32)
        h0 = ssm.state_to_heads(pool[L - 1, rows, :N], H)
        want_y, want_h = ssm.ssm_recurrence(x, dt, A, B, C, h0)
        want_y = want_y.reshape(b, t, H * P)[:, -1]     # the last token's
        want_h = ssm.state_from_heads(want_h)[0]
        del pool
        gap = lambda a, c: float(jnp.abs(a.astype(jnp.float32) - c).max())
        hbm, mxu = floors_us(b, t, H, P, N, G, ssm_scan.TOKENS)
        print(json.dumps({
            "case": name, "tokens": t, "groups": G,
            "kernel_us_a_layer": us["kernel"], "xla_us_a_layer": us["xla"],
            "hbm_floor_us": hbm, "mxu_floor_us": mxu,
            "kernel_floor_share": 100 * max(hbm, mxu) / us["kernel"],
            "y_gap_kernel_xla": gap(out["kernel"][1], out["xla"][1]),
            "y_gap_kernel_recurrence": gap(out["kernel"][1], want_y),
            "y_gap_xla_recurrence": gap(out["xla"][1], want_y),
            "state_gap_kernel_xla": gap(out["kernel"][0], out["xla"][0]),
            "state_gap_kernel_recurrence": gap(out["kernel"][0], want_h),
            "state_gap_xla_recurrence": gap(out["xla"][0], want_h),
            "y_scale": float(jnp.abs(want_y).max()),
            "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
