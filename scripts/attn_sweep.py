#!/usr/bin/env python
"""Flash-attention block-size sweep (VERDICT r4 item 6: attention MFU is
the gap between headline 0.58 and the 0.7+ matmul ceiling).

Measures the Pallas flash kernel fwd+bwd at hd=128 over a block × seq ×
kv_heads matrix (plus an s=8192 forward row and an hd=64 contrast row),
picks the block size with the best mean train-MFU PER GQA GROUP, and —
when it beats the current default by >3% on the real chip — persists it to
`.dstpu_tuned.json` at the repo root:

- ``flash_block``: the MHA (kv_heads == nq) q/kv block, read by
  ``ops/pallas/flash_attention._block`` as its default;
- ``flash_block_g<g>``: the per-group q block for the native-GQA kernels
  at query/kv ratio g (``_block_gqa`` reads these directly — the autotune
  key gained the kv_heads dimension with ISSUE 14's native-GQA kernels).

The next run on this checkout then runs tuned. GQA rows measure
with ``attention.gqa_native`` armed (narrow K/V through the kernel).

Flops accounting: causal fwd = 2·B·H·S²·D (two matmuls, causal half);
bwd = 2.5× fwd (five matmuls) → fwd+bwd = 3.5× fwd. ONE JSON line.
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _probe_common import finalize  # noqa: E402

RESULT = {"metric": "flash_attn_fwdbwd_mfu_best", "value": 0.0,
          "unit": "fraction_of_peak", "vs_baseline": None, "detail": {}}


def main():
    import jax

    import jax.numpy as jnp
    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()

    import importlib

    # the ops package re-exports the `attention` dispatcher under the same
    # name, shadowing the submodule on attribute access
    attn_mod = importlib.import_module("deepspeed_tpu.ops.attention")
    from bench import peak_flops_per_chip
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    backend = jax.default_backend()
    on_tpu = backend == "tpu"
    RESULT["detail"]["backend"] = backend
    peak = peak_flops_per_chip(jax)
    B, H = (8, 8) if on_tpu else (1, 2)
    blocks = (256, 512, 1024) if on_tpu else (128,)
    seqs = (2048, 4096) if on_tpu else (256,)
    # kv_heads dimension (ISSUE 14): the MHA row plus the native-GQA
    # ratios the serving/training models actually use
    kv_heads = tuple(sorted(x for x in {1, 4, 8, H} if H % x == 0))
    rows = {}
    RESULT["detail"]["rows"] = rows
    budget_s = float(os.environ.get("DSTPU_ATTN_BUDGET_S", 1500))
    t_start = time.perf_counter()

    def measure(blk, S, D, mode, kvh=None):
        """One config → (ms, mfu). Chained reps inside one jit so the
        per-dispatch host latency is excluded.
        ``kvh < H`` measures the native-GQA kernel on narrow K/V."""
        from jax import lax

        kvh = H if kvh is None else kvh
        os.environ["DSTPU_FLASH_BLOCK"] = str(blk)
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, kvh, D),
                              jnp.bfloat16)
        fwd_flops = 2 * B * H * S * S * D
        if mode == "fwd":
            flops = fwd_flops

            def op(k, q):
                return fa.flash_attention(q, k, k, causal=True)
        else:
            flops = int(3.5 * fwd_flops)

            def loss(q, k):
                o = fa.flash_attention(q, k, k, causal=True)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            def op(k, q):
                # dq has q's shape → scan-chainable carry
                return jax.grad(lambda q: loss(q, k))(q)

        reps, steps = (10, 3) if on_tpu else (2, 1)

        def chained(k, q0):
            def body(carry, _):
                return op(k, carry), ()

            out, _ = lax.scan(body, q0, None, length=reps)
            return out

        prev = attn_mod.configure_gqa_native(kvh != H)
        try:
            f = jax.jit(chained)
            out = f(k, q)
            jax.block_until_ready(out)  # compile
            t0 = time.perf_counter()
            for _ in range(steps):
                out = f(k, q)
            jax.block_until_ready(out)
        finally:
            attn_mod.configure_gqa_native(prev)
        dt = (time.perf_counter() - t0) / (steps * reps)
        return round(dt * 1e3, 3), round(flops / dt / peak, 4)

    # per_group_mfu[g][blk] = mean fwdbwd mfu over seqs (g = H // kvh;
    # blk is the DSTPU_FLASH_BLOCK value — total kernel rows)
    per_group_mfu = {H // kvh: {} for kvh in kv_heads}
    for blk in blocks:
        for kvh in kv_heads:
            g = H // kvh
            vals = []
            for S in seqs:
                label = f"blk{blk}_s{S}_hd128_kv{kvh}_fwdbwd"
                if time.perf_counter() - t_start > budget_s:
                    rows[label] = "skipped: budget exhausted"
                    continue
                try:
                    ms, mfu = measure(blk, S, 128, "fwdbwd", kvh=kvh)
                    rows[label] = {"ms": ms, "mfu": mfu}
                    vals.append(mfu)
                    sys.stderr.write(
                        f"[attn] blk={blk} S={S} kv={kvh}: mfu={mfu}\n")
                except Exception as e:
                    rows[label] = f"error: {str(e)[-200:]}"
            if vals:
                per_group_mfu[g][blk] = sum(vals) / len(vals)

    mha = per_group_mfu.get(1, {})
    if mha:
        best_blk = max(mha, key=mha.get)
        RESULT["detail"]["best_block"] = best_blk
        RESULT["detail"]["per_block_mean_mfu"] = {
            str(b): round(v, 4) for b, v in mha.items()}
        RESULT["detail"]["per_group_mean_mfu"] = {
            str(g): {str(b): round(v, 4) for b, v in m.items()}
            for g, m in per_group_mfu.items() if m}
        RESULT["value"] = round(mha[best_blk], 4)
        # contrast rows at the winning block (budget-guarded)
        for label, S, D, mode in (("s8192_hd128_fwd", 8192, 128, "fwd"),
                                  ("s2048_hd64_fwdbwd", 2048, 64, "fwdbwd")):
            if not on_tpu or time.perf_counter() - t_start > budget_s:
                continue
            try:
                ms, mfu = measure(best_blk, S, D, mode)
                rows[f"blk{best_blk}_{label}"] = {"ms": ms, "mfu": mfu}
            except Exception as e:
                rows[f"blk{best_blk}_{label}"] = f"error: {str(e)[-200:]}"
        # persist the winners for the kernel's defaults — real-chip data
        # only. Compared against the CURRENTLY persisted value (or the
        # compiled-in default) so a later sweep can also revert a stale
        # tuning; the file is deliberately committable (the target hardware
        # IS v5e — the driver bench should run tuned). Path resolution and
        # the atomic tmp+rename write live in tuning/persist.py (shared
        # with the online tuner): a SIGTERM mid-write must never leave a
        # partial file that readers silently ignore forever.
        from deepspeed_tpu.tuning.persist import load_tuned, update_tuned

        tuned = dict(load_tuned())
        wrote = []
        current = int(tuned.get("flash_block", 512))
        cur_mfu = mha.get(current)
        if on_tpu and best_blk != current and (
                cur_mfu is None  # current value wasn't even measurable
                or mha[best_blk] > cur_mfu * 1.03):
            tuned["flash_block"] = best_blk
            wrote.append("flash_block")
        for g, m in per_group_mfu.items():
            if g == 1 or not m:
                continue
            best_total = max(m, key=m.get)
            # the tuned key stores the PER-GROUP q block the native kernel
            # reads directly (_block_gqa): total kernel rows / g
            best_bq = max(8, (best_total // g) // 8 * 8)
            cur_bq = int(tuned.get(f"flash_block_g{g}", 0))
            cur_total_mfu = m.get(cur_bq * g) if cur_bq else None
            if on_tpu and best_bq != cur_bq and (
                    cur_total_mfu is None
                    or m[best_total] > cur_total_mfu * 1.03):
                tuned[f"flash_block_g{g}"] = best_bq
                wrote.append(f"flash_block_g{g}")
        if wrote:
            update_tuned({k: tuned[k] for k in wrote})
            RESULT["detail"]["tuned_written"] = {
                k: tuned[k] for k in wrote}
    os.environ.pop("DSTPU_FLASH_BLOCK", None)
    return finalize(RESULT)


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:  # report in the JSON line, then fail
        RESULT["detail"]["error"] = str(e)[-2000:]
        finalize(RESULT, ok=False)
        raise
