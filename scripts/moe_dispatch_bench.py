#!/usr/bin/env python
"""MoE dispatch: dense one-hot einsum vs compacted gather/scatter.

VERDICT r3 item 6: SURVEY §2.4 lists the reference's dedicated MoE
dispatch/top-k kernels (``inference/v2/kernels/ragged_ops/top_k_gating``,
``moe_scatter``, ``moe_gather``) as native-equivalent targets. Our MOELayer
dispatches with dense einsums ([T,E,C]·[T,H] → [E,C,H]) — MXU-friendly but
O(T·E·C·H) flops. The compacted alternative (what a Pallas scatter kernel
would compute) builds the [E,C] token index table from the gating output and
uses gather / scatter-add — O(k·T·H) memory movement, no E·C blowup.

This script times BOTH paths end-to-end (gating → dispatch → 2-matmul
expert FFN → combine) at serving/training-realistic shapes and prints one
JSON line, so the einsum-vs-kernel question is answered with data
(PERF.md records the verdict: implement the Pallas kernel only if compact
wins and XLA's lowering of it leaves time on the table).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _probe_common import finalize  # noqa: E402

RESULT = {"metric": "moe_dispatch_best_impl", "value": 0.0,
          "unit": "einsum_over_compact_speedup", "vs_baseline": None,
          "detail": {}}


def main():
    import jax

    import jax.numpy as jnp
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.moe.layer import MoELayer, init_moe_ffn
    from deepspeed_tpu.moe.sharded_moe import compute_capacity

    backend = jax.default_backend()
    RESULT["detail"]["backend"] = backend
    on_tpu = backend == "tpu"
    if on_tpu:
        shapes = [(8192, 1024, 8, 2), (8192, 1024, 64, 2),
                  (16384, 2048, 16, 2)]
        steps = 10
    else:
        shapes = [(512, 64, 8, 2)]
        steps = 3
    mesh_lib.set_mesh(None)  # single-device: measure dispatch, not a2a

    rows = {}
    RESULT["detail"]["rows_ms"] = rows
    parity_checked = False
    for T, H, E, k in shapes:
        params = init_moe_ffn(jax.random.PRNGKey(0), n_experts=E, hidden=H,
                              intermediate=2 * H, dtype=jnp.bfloat16)
        x = jax.random.normal(jax.random.PRNGKey(1), (1, T, H), jnp.bfloat16)
        cap = compute_capacity(T, E, k, 1.25)
        label = f"T{T}_H{H}_E{E}_k{k}_cap{cap}"

        # the SHIPPING implementations — both paths are MoELayer(dispatch=..)
        # so this bench can never drift from what the engine runs
        def run(impl, params, x):
            layer = MoELayer(n_experts=E, top_k=k, capacity_factor=1.25,
                             dispatch=impl)
            out, _ = layer(params, x)
            return out

        if not parity_checked:
            # the timing verdict is only meaningful if both paths compute
            # the same function — pin it in f32 (bf16 differs only by
            # accumulation-order noise, which would mask a real bug). On TPU
            # f32 matmuls themselves run as bf16 passes at DEFAULT precision,
            # so force true-f32 matmuls or the noise floor comes back.
            p32 = jax.tree.map(lambda t: t.astype(jnp.float32), params)
            x32 = x.astype(jnp.float32)
            with jax.default_matmul_precision("highest"):
                a = run("einsum", p32, x32)
                b = run("compact", p32, x32)
            diff = float(jnp.max(jnp.abs(a - b)))
            assert diff < 1e-3, f"einsum/compact diverge: max diff {diff}"
            RESULT["detail"]["parity_max_diff"] = diff
            parity_checked = True
        row = {}
        for name in ("einsum", "compact"):
            try:
                jf = jax.jit(run, static_argnums=0)
                out = jf(name, params, x)
                jax.block_until_ready(out)  # compile
                t0 = time.perf_counter()
                for _ in range(steps):
                    out = jf(name, params, x)
                jax.block_until_ready(out)
                row[name] = round((time.perf_counter() - t0) / steps * 1e3, 3)
            except Exception as e:
                row[name] = f"error: {str(e)[-150:]}"
        if all(isinstance(v, float) for v in row.values()):
            row["einsum_over_compact"] = round(row["einsum"] / row["compact"],
                                               3)
        rows[label] = row
        sys.stderr.write(f"[moe] {label}: {row}\n")
    ratios = [r.get("einsum_over_compact") for r in rows.values()
              if isinstance(r, dict) and "einsum_over_compact" in r]
    if ratios:
        RESULT["value"] = round(sum(ratios) / len(ratios), 3)
    return finalize(RESULT)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # report in the JSON line, then fail
        RESULT["detail"]["error"] = str(e)[-2000:]
        finalize(RESULT, ok=False)
        raise
