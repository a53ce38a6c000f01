#!/usr/bin/env python
"""Quantized-linear: is a fused dequant-matmul Pallas kernel worth building?

VERDICT r3 missing item 6: the reference ships fp6/wf6af16 fused
dequant-GEMM CUDA kernels (``inference/v2/kernels/core_ops/cuda_linear``).
Our inference tier stores int8/int4 weights and dequantizes on use, trusting
XLA to fuse the dequant into the matmul's operand read. This bench measures
whether that trust is justified: time (a) bf16 weights matmul (upper bound),
(b) int8 dequant→matmul under one jit (what we ship), at decode-realistic
shapes (small M, big K/N). If (b) ≈ (a) + HBM savings, the Pallas kernel is
not worth building; if (b) is much slower than the bandwidth model predicts,
it is. Prints ONE JSON line.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _probe_common import finalize  # noqa: E402

RESULT = {"metric": "int8_linear_slowdown_vs_bf16", "value": 0.0,
          "unit": "x", "vs_baseline": None, "detail": {}}


def main():
    import jax

    import jax.numpy as jnp
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from deepspeed_tpu.ops.quantization import (dequantize_int8,
                                                quantize_int8)

    backend = jax.default_backend()
    RESULT["detail"]["backend"] = backend
    on_tpu = backend == "tpu"
    # decode-realistic: M = live batch (small), K/N = model dims (big)
    if on_tpu:
        shapes = [(16, 4096, 4096), (16, 4096, 14336), (256, 4096, 4096)]
        steps = 20
    else:
        shapes = [(16, 256, 256)]
        steps = 3
    group = 256

    def bf16_linear(x, w):
        return x @ w

    def int8_linear(x, qw, scales):
        w = dequantize_int8(qw, scales, group_size=group, dtype=jnp.bfloat16)
        return x @ w

    rows = {}
    RESULT["detail"]["rows_us"] = rows
    ratios = []
    for M, K, N in shapes:
        key = jax.random.PRNGKey(0)
        kx, kw = jax.random.split(key)
        x = jax.random.normal(kx, (M, K), jnp.bfloat16)
        w = jax.random.normal(kw, (K, N), jnp.bfloat16)
        qw, scales = quantize_int8(w, group_size=group)  # setup, not timed
        row = {}
        for name, fn, args in (("bf16", bf16_linear, (x, w)),
                               ("int8", int8_linear, (x, qw, scales))):
            jf = jax.jit(fn)
            out = jf(*args)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(steps):
                out = jf(*args)
            jax.block_until_ready(out)
            row[name] = round((time.perf_counter() - t0) / steps * 1e6, 1)
        row["int8_over_bf16"] = round(row["int8"] / row["bf16"], 3)
        # bandwidth model: int8 weights halve the HBM bytes; at decode
        # (memory-bound) the IDEAL ratio is ~0.5, not 1.0
        rows[f"M{M}_K{K}_N{N}"] = row
        ratios.append(row["int8_over_bf16"])
        sys.stderr.write(f"[quant] M{M}_K{K}_N{N}: {row} (us)\n")
    RESULT["value"] = round(sum(ratios) / len(ratios), 3)
    return finalize(RESULT)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # report in the JSON line, then fail
        RESULT["detail"]["error"] = str(e)[-2000:]
        finalize(RESULT, ok=False)
        raise
