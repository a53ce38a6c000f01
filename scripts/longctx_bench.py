#!/usr/bin/env python
"""Long-context proof on one chip (VERDICT r3 item 8).

Runs FPDT attention with KV host-offload double buffering at escalating
sequence lengths (128K -> 1M tokens) on the real chip, fwd+bwd, and records
(seq, step time, attention MFU, peak HBM) per row — the single-chip analog of
BASELINE.md's Ulysses/FPDT long-context rows (reference proof point:
blogs/ulysses-offload 2M tokens on 4xA100 via chunked KV streaming).

Prints ONE JSON line. Safe to run on CPU (tiny shapes, smoke only).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _probe_common import finalize  # noqa: E402

# stdout carries exactly ONE JSON line; package logs go to stderr

RESULT = {"metric": "fpdt_longctx_max_seq", "value": 0, "unit": "tokens",
          "vs_baseline": 0.0, "detail": {}}


def peak_hbm_bytes(dev):
    try:
        stats = dev.memory_stats()
        return int(stats.get("peak_bytes_in_use", 0))
    except Exception:
        return 0


def main():
    import jax

    import jax.numpy as jnp
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    from deepspeed_tpu.sequence.fpdt import fpdt_attention

    backend = jax.default_backend()
    RESULT["detail"]["backend"] = backend
    dev = jax.devices()[0]
    on_tpu = backend == "tpu"
    # [B=1, S, H, D] bf16; GQA-narrow KV (4 kv heads) like the bench model
    H, Hkv, D = 8, 4, 128
    if on_tpu:
        seqs = [128 * 1024, 256 * 1024, 512 * 1024, 1024 * 1024]
        chunk_tokens = 8192
    else:
        seqs = [4096]
        chunk_tokens = 1024
    budget_s = float(os.environ.get("DSTPU_LONGCTX_BUDGET_S", 1800))
    t_start = time.perf_counter()

    def loss_fn(q, k, v, chunks):
        o = fpdt_attention(q, k, v, chunks=chunks, causal=True,
                           offload_kv=on_tpu)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    rows = {}
    RESULT["detail"]["rows"] = rows
    best = 0
    for S in seqs:
        if time.perf_counter() - t_start > budget_s:
            rows[str(S)] = "skipped: budget exhausted"
            continue
        chunks = max(2, S // chunk_tokens)
        try:
            key = jax.random.PRNGKey(0)
            kq, kk, kv_ = jax.random.split(key, 3)
            q = jax.random.normal(kq, (1, S, H, D), jnp.bfloat16)
            k = jax.random.normal(kk, (1, S, Hkv, D), jnp.bfloat16)
            v = jax.random.normal(kv_, (1, S, Hkv, D), jnp.bfloat16)
            grad = jax.jit(jax.grad(loss_fn, argnums=(0, 1, 2)),
                           static_argnums=(3,))
            jax.block_until_ready(grad(q, k, v, chunks))  # compile
            t0 = time.perf_counter()
            jax.block_until_ready(grad(q, k, v, chunks))
            dt = time.perf_counter() - t0
            # causal attention fwd flops = 2 matmuls * 2*B*H*(S^2/2)*D;
            # bwd ~= 2x fwd (recompute excluded from the 6N-style account)
            flops = 3 * (2 * H * (S ** 2) * D)
            from bench import peak_flops_per_chip

            peak = peak_flops_per_chip(jax)
            rows[str(S)] = {
                "step_s": round(dt, 3),
                "attn_mfu": round(flops / dt / peak, 4),
                "peak_hbm_gb": round(peak_hbm_bytes(dev) / 2**30, 2),
                "chunks": chunks,
            }
            best = S
            sys.stderr.write(f"[longctx] S={S}: {rows[str(S)]}\n")
        except Exception as e:
            rows[str(S)] = f"error: {str(e)[-200:]}"
            sys.stderr.write(f"[longctx] S={S} failed: {str(e)[-300:]}\n")
            break  # OOM at S means 2S would also fail
    RESULT["value"] = best
    # baseline: reference FPDT reaches 2M tokens on 4 GPUs => 512K/device
    RESULT["vs_baseline"] = round(best / (512 * 1024), 4)
    # explicit ok: hitting the OOM frontier after ≥1 passing size IS a
    # successful run (value = max proven S); only an immediate first-row
    # failure (best == 0) means the probe found nothing
    return finalize(RESULT, ok=best > 0)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # report in the JSON line, then fail
        RESULT["detail"]["error"] = str(e)[-2000:]
        finalize(RESULT, ok=False)
        raise
