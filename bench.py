#!/usr/bin/env python
"""Headline benchmark: Llama-style causal-LM training step throughput + MFU.

Prints ONE JSON line: {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}.

Baseline (BASELINE.md): the reference's ZeRO-3 north-star is >=45% MFU; we
report our measured model-flops-utilization against that target.

Runs on the TPU or not at all: without one it exits non-zero before it
measures anything, and a phase that fails ends the run non-zero. ``--cpu``
asks for the CPU explicitly (the schema test): tiny sizes, the headline
loop and the decode child only, labelled ``backend: "cpu"``, and every
field that would be a time or a rate is null — a CPU timing is never
printed under a device metric's name.
"""

import json
import os
import sys
import time
import traceback

_HERE = os.path.dirname(os.path.abspath(__file__))
if _HERE not in sys.path:
    sys.path.insert(0, _HERE)

RESULT = {
    "metric": "llama_zero3_train_mfu",
    "value": None,  # null until measured on the chip
    "unit": "fraction_of_peak",
    "vs_baseline": None,
    "detail": {},
}


def emit(ok: bool, err: str = ""):
    if err:
        RESULT["detail"]["error"] = err[-2000:]
    RESULT["detail"]["ok"] = ok
    print(json.dumps(RESULT))


def init_backend(cpu: bool):
    """Import jax on the TPU — or, with ``--cpu``, on the CPU the caller
    asked for. Anything else (no chip, a chip another process holds) is an
    error: this program never chooses the CPU by itself."""
    if cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # before jax; children inherit
    import jax

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    platform = jax.devices()[0].platform
    if platform != ("cpu" if cpu else "tpu"):
        raise RuntimeError(
            f"bench.py needs a TPU and JAX reports platform {platform!r} "
            f"(an explicit CPU schema run: --cpu)")
    enable_compile_cache()
    RESULT["detail"]["backend"] = platform
    RESULT["detail"]["n_chips"] = len(jax.devices())
    return jax


def peak_flops_per_chip(jax) -> float:
    """bf16 peak of the local chip from the one published table; a device
    the table does not list raises."""
    from deepspeed_tpu.utils.peaks import device_peaks

    return device_peaks(jax.devices()[0]).bf16_flops


def model_flops_per_token(mcfg, seqlen: int) -> float:
    """Model flops per token: 6*N (fwd+bwd matmuls) + the causal-attention
    term 12*L*H*S. Shared by the headline and shape-row MFU so the two
    numbers stay comparable."""
    return (6 * mcfg.num_params
            + 12 * mcfg.num_layers * mcfg.hidden_size * seqlen)


def bench_model_config(on_tpu: bool, remat: bool = False):
    """ONE model for both the train-MFU and decode benches — keep these in
    sync or the decode number describes a different model."""
    from deepspeed_tpu.models import llama

    if not on_tpu:
        return llama.LlamaConfig.tiny()
    # 235M-param Llama (head_dim=128: MXU-native; hd=64 costs ~25% MFU)
    return llama.LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=3584,
        num_layers=12, num_heads=8, num_kv_heads=4, max_seq_len=2048,
        rope_theta=500000.0, remat=remat)


def bench_shape_rows(jax, budget_s: float = None) -> dict:
    """MFU at the north-star shapes (VERDICT r2: prove the 8B-class rows):
    few-layer Llama train steps at h=1024/2048/4096, hd=64 vs hd=128 — the
    headline config must not be the only (flattering) row. Runs inside a
    wall-clock budget; rows that don't fit are reported as 'skipped'."""
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama

    if budget_s is None:
        budget_s = float(os.environ.get("DSTPU_BENCH_SHAPE_BUDGET_S", 1500))
    t_start = time.perf_counter()
    # (label, hidden, inter, layers, heads, kv, head_dim)
    configs = [
        ("h1024_hd64", 1024, 3584, 12, 16, 8, 64),
        ("h1024_hd128", 1024, 3584, 12, 8, 4, 128),
        ("h2048_hd128", 2048, 7168, 6, 16, 8, 128),
        ("h4096_hd128", 4096, 14336, 2, 32, 8, 128),  # Llama-3-8B layer
    ]
    rows = {}
    n_chips = max(1, len(jax.devices()))
    batch = int(os.environ.get("DSTPU_BENCH_SHAPE_BATCH", 4 * n_chips))
    seqlen = int(os.environ.get("DSTPU_BENCH_SHAPE_SEQLEN", 2048))
    steps = int(os.environ.get("DSTPU_BENCH_SHAPE_STEPS", 8))
    peak = peak_flops_per_chip(jax)
    engine = None
    for label, h, inter, L, nh, nkv, hd in configs:
        if time.perf_counter() - t_start > budget_s:
            rows[label] = "skipped: shape budget exhausted"
            continue
        engine = None  # free the previous row's params/opt state first
        mesh_lib.set_mesh(None)
        mcfg = llama.LlamaConfig(
            vocab_size=32000, hidden_size=h, intermediate_size=inter,
            num_layers=L, num_heads=nh, num_kv_heads=nkv, head_dim=hd,
            max_seq_len=seqlen, rope_theta=500000.0, remat=True)
        spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
        engine, _, _, _ = dst.initialize(model=spec, config={
            "train_batch_size": batch,
            "bf16": {"enabled": True},
            "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
            "zero_optimization": {"stage": 3},
            "steps_per_print": 0,
        })
        rng = np.random.default_rng(0)
        toks = {"tokens": rng.integers(
            0, mcfg.vocab_size, (batch, seqlen + 1), dtype=np.int32)}
        jax.block_until_ready(engine.train_batch(toks).loss)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(steps):
            out = engine.train_batch(toks)
        jax.block_until_ready(out.loss)
        dt = (time.perf_counter() - t0) / steps
        tps_per_chip = batch * seqlen / dt / n_chips
        flops_tok = model_flops_per_token(mcfg, seqlen)
        rows[label] = {"mfu": round(tps_per_chip * flops_tok / peak, 4),
                       "tok_per_sec_per_chip": round(tps_per_chip, 1),
                       "params_m": round(mcfg.num_params / 1e6, 1),
                       "step_s": round(dt, 3)}
        sys.stderr.write(f"[bench] shape {label}: {rows[label]}\n")
    return rows


def bench_attention_probe(jax) -> dict:
    """Standalone attention MFU at hd=128 with the 512-wide flash block —
    the PERF.md open item ("not yet re-measured standalone"; expected ~2×
    the hd=64 rows). fwd and fwd+bwd, amortized inside one jit (same recipe
    as scripts/attn_sweep.py; flops: causal fwd = 2·B·H·S²·D, fwd+bwd =
    3.5×).

    GQA sweep (ISSUE 14; docs/performance.md "Native GQA attention"):
    kv_heads ∈ {1, 4, 8, nq} ∩ divisors(nq) at the same shape, widened vs
    ``attention.gqa_native`` narrow kernels, with per-step attention KV HBM
    bytes accounted (bytes of the K/V operands the kernels stream; the
    widened path's are nq/nkv× larger in fwd AND bwd). The native rows
    additionally assert — by counting ``ops.attention.repeat_kv`` widening
    calls at trace time — that no q-width KV copy exists, so
    ``kv_bytes_saved`` is measured program structure, not an assumption.
    ``Train/attn/{kv_bytes_saved,gqa_ratio}`` gauges ride a TelemetryHub."""
    import jax.numpy as jnp
    from jax import lax

    import importlib

    # the ops package re-exports the `attention` dispatcher under the same
    # name, shadowing the submodule on attribute access
    attn_mod = importlib.import_module("deepspeed_tpu.ops.attention")
    from deepspeed_tpu.ops.pallas import flash_attention as fa

    on_tpu = "tpu" in str(RESULT["detail"].get("backend", ""))
    peak = peak_flops_per_chip(jax) if on_tpu else None
    B, H, D = (8, 8, 128) if on_tpu else (1, 2, 128)
    S = 2048 if on_tpu else 256
    blk = 512 if on_tpu else 128
    rows = {"shape": f"B{B}_H{H}_S{S}_hd{D}_bq{blk}"}
    old_blk = os.environ.get("DSTPU_FLASH_BLOCK")
    os.environ["DSTPU_FLASH_BLOCK"] = str(blk)

    def measure(q, k, v, mode):
        """(ms, mfu) for one config — chained reps inside one jit."""
        fwd_flops = 2 * B * H * S * S * D
        if mode == "fwd":
            flops = fwd_flops

            def op(k, v, q):
                return fa.flash_attention(q, k, v, causal=True)
        else:
            flops = int(3.5 * fwd_flops)

            def loss(q, k, v):
                o = fa.flash_attention(q, k, v, causal=True)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            def op(k, v, q):
                return jax.grad(lambda q: loss(q, k, v))(q)

        reps, steps = (10, 3) if on_tpu else (2, 1)

        def chained(k, v, q0):
            def body(carry, _):
                return op(k, v, carry), ()

            out, _ = lax.scan(body, q0, None, length=reps)
            return out

        f = jax.jit(chained)
        jax.block_until_ready(f(k, v, q))  # compile
        t0 = time.perf_counter()
        for _ in range(steps):
            out = f(k, v, q)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / (steps * reps)
        # utilization only against a published peak (absent off the chip)
        return round(dt * 1e3, 3), (round(flops / dt / peak, 4)
                                    if peak else None)

    try:
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D),
                              jnp.bfloat16)
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D),
                              jnp.bfloat16)
        for mode in ("fwd", "fwdbwd"):
            ms, mfu = measure(q, k, k, mode)
            rows[mode] = {"ms": ms, "mfu": mfu}

        # --- GQA sweep: same q, kv-head-narrow K/V, widened vs native ---
        gqa = {}
        rows["gqa"] = gqa
        elem = 2  # bf16 K/V
        passes = {"fwd": 1, "fwdbwd": 3}  # fwd + dq + dkv each stream K/V
        real_repeat = attn_mod.repeat_kv
        best = None
        for kvh in sorted(x for x in {1, 4, 8, H} if H % x == 0 and x <= H):
            kn = jax.random.normal(jax.random.PRNGKey(2), (B, S, kvh, D),
                                   jnp.bfloat16)
            vn = jax.random.normal(jax.random.PRNGKey(3), (B, S, kvh, D),
                                   jnp.bfloat16)
            row = {"ratio": H // kvh}
            for native in (False, True):
                prev = attn_mod.configure_gqa_native(native)
                widens = [0]

                def counting_repeat(x, nq):
                    if x.shape[-2] != nq:
                        widens[0] += 1
                    return real_repeat(x, nq)

                attn_mod.repeat_kv = counting_repeat
                try:
                    sub = {}
                    for mode in ("fwd", "fwdbwd"):
                        widens[0] = 0
                        ms, mfu = measure(q, kn, vn, mode)
                        kvh_eff = kvh if native and kvh != H else H
                        sub[mode] = {
                            "ms": ms, "mfu": mfu,
                            "kv_bytes": 2 * B * S * kvh_eff * D * elem
                            * passes[mode],
                            "widen_calls": widens[0]}
                    if native and kvh != H:
                        # measured program structure: the narrow path must
                        # contain ZERO q-width KV widenings
                        assert sub["fwd"]["widen_calls"] == 0 and \
                            sub["fwdbwd"]["widen_calls"] == 0, \
                            f"native kv{kvh}: widen leaked {sub}"
                    row["native" if native else "widened"] = sub
                finally:
                    attn_mod.repeat_kv = real_repeat
                    attn_mod.configure_gqa_native(prev)
            saved = (row["widened"]["fwdbwd"]["kv_bytes"]
                     - row["native"]["fwdbwd"]["kv_bytes"])
            row["kv_bytes_saved_fwdbwd"] = saved
            gqa[f"kv{kvh}"] = row
            if kvh != H and (best is None or saved > best[0]):
                best = (saved, H // kvh)
        if best is not None:
            # Train/attn/* gauges (closed TRAIN_SERIES registry)
            from deepspeed_tpu.telemetry.hub import TelemetryHub

            hub = TelemetryHub(None)
            hub.train_event("attn/kv_bytes_saved", float(best[0]))
            hub.train_event("attn/gqa_ratio", float(best[1]))
    finally:
        if old_blk is None:
            os.environ.pop("DSTPU_FLASH_BLOCK", None)
        else:
            os.environ["DSTPU_FLASH_BLOCK"] = old_blk
    return rows


# every policy the sweep measures — mirrors telemetry.schema.REMAT_POLICIES
# minus the offload/no-batch-dim variants (not step-time-relevant on the
# bench shape; offload needs real pinned host memory to mean anything)
REMAT_SWEEP_POLICIES = ("none", "full", "dots_saveable", "save_attn_out",
                        "save_big_matmuls")


def _remat_engine(jax, on_tpu, policy, overlap=False, mcfg=None):
    import jax.numpy as jnp

    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama

    import dataclasses

    mesh_lib.set_mesh(None)
    mcfg = dataclasses.replace(mcfg or bench_model_config(on_tpu),
                               remat=policy != "none", remat_policy=policy)
    config = {
        "train_batch_size": 8 * max(1, len(jax.devices())),
        "bf16": {"enabled": True},
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
        "zero_optimization": {"stage": 3},
        "steps_per_print": 0,
    }
    if overlap:
        config["comms_overlap"] = {"enabled": True, "layer_prefetch": True}
    spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
    engine, _, _, _ = dst.initialize(model=spec, config=config)
    return engine, mcfg


def _block_saved_bytes(mcfg, policy) -> object:
    """Trace-time saved-residual bytes of ONE transformer block under the
    policy (exact, device-free) — the honest per-policy memory number the
    allocator can't give (its peak is a process-global running max)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import llama
    from deepspeed_tpu.ops.rotary import rope_frequencies
    from deepspeed_tpu.runtime.activation_checkpointing import (
        checkpointing as ac)

    params = llama.init(mcfg, jax.random.PRNGKey(0), dtype=jnp.bfloat16)
    layer0 = jax.tree.map(lambda a: a[0], params["layers"])
    cos, sin = rope_frequencies(mcfg.head_size, mcfg.max_seq_len,
                                mcfg.rope_theta)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (1, min(256, mcfg.max_seq_len), mcfg.hidden_size)), jnp.bfloat16)

    def blk(x):
        return jnp.sum(
            llama._block(mcfg, x, layer0, cos, sin, None).astype(jnp.float32)
            ** 2)

    return ac.saved_bytes(blk, x, policy=policy)


def bench_remat_sweep(jax, on_tpu, steps=None) -> dict:
    """Per-remat-policy HBM-vs-step-time sweep (the measured, not asserted,
    memory/speed trade): step time on the bench config, compiled temp bytes
    (memory_analysis — the activation footprint remat actually moves),
    MemoryTelemetry allocator/live-bytes snapshot, and exact per-block
    saved-residual bytes. Rows land in the headline JSON and as
    ``Train/remat/*`` gauges through the engine's TelemetryHub."""
    import numpy as np

    from deepspeed_tpu.telemetry.memory import MemoryTelemetry

    budget_s = float(os.environ.get("DSTPU_BENCH_REMAT_BUDGET_S",
                                    900 if on_tpu else 240))
    t_start = time.perf_counter()
    if steps is None:
        steps = 8 if on_tpu else 3
    seqlen = 2048 if on_tpu else 128
    rows = {}
    for policy in REMAT_SWEEP_POLICIES:
        if time.perf_counter() - t_start > budget_s:
            rows[policy] = "skipped: remat sweep budget exhausted"
            continue
        engine, mcfg = _remat_engine(jax, on_tpu, policy)
        rng = np.random.default_rng(0)
        toks = {"tokens": rng.integers(
            0, mcfg.vocab_size,
            (engine.train_batch_size(), seqlen + 1), dtype=np.int32)}
        jax.block_until_ready(engine.train_batch(toks).loss)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(steps):
            out = engine.train_batch(toks)
        jax.block_until_ready(out.loss)
        dt = (time.perf_counter() - t0) / steps
        row = {"step_s": round(dt, 4)}
        batch = engine._shard_batch(toks, with_gas_dim=True)
        mem = engine._train_step.lower(
            engine.state, batch,
            engine._lr_override).compile().memory_analysis()
        row["temp_bytes"] = int(mem.temp_size_in_bytes)
        snap = MemoryTelemetry().snapshot()
        row["hbm_in_use"] = int(snap["bytes_in_use"])
        row["hbm_peak"] = int(snap["peak_bytes"])
        saved = _block_saved_bytes(mcfg, policy)
        if saved is not None:
            row["block_saved_bytes"] = int(saved)
        rows[policy] = row
        hub = getattr(engine, "telemetry", None)
        if hub is not None:
            hub.train_event(f"remat/step_ms_{policy}", dt * 1e3)
            if saved is not None:
                hub.train_event(f"remat/saved_bytes_{policy}",
                                float(saved))
            hub.train_event(f"remat/peak_bytes_{policy}",
                            float(row.get("temp_bytes",
                                          row["hbm_peak"])))
        sys.stderr.write(f"[bench] remat {policy}: {rows[policy]}\n")
    return rows


def bench_overlap_remat(jax, on_tpu, steps=None) -> dict:
    """The combined fine-grained-overlap + selective-remat config vs the
    pre-PR default (full remat, no overlap) on the SAME model/step budget —
    the acceptance comparison. On the CPU proxy the win comes from skipping
    the big-matmul recompute; on silicon the layer_prefetch all-gather
    overlap stacks on top."""
    import numpy as np

    from deepspeed_tpu.comm import overlap as ov
    from deepspeed_tpu.models import llama

    if on_tpu:
        base_cfg, seqlen = bench_model_config(True), 2048
        steps = steps or 10
    else:
        # CPU proxy: wide enough (h=512) that the skipped big-matmul
        # recompute dominates the per-layer prefetch slice overhead — the
        # tiny 2-layer headline config is timing-noise-bound here
        # (measured: save_big_matmuls + prefetch beats full remat ~5% in
        # every interleaved window at this shape)
        base_cfg = llama.LlamaConfig(
            vocab_size=256, hidden_size=512, intermediate_size=1024,
            num_layers=4, num_heads=8, num_kv_heads=4, max_seq_len=512,
            rope_theta=10000.0)
        seqlen, steps = 256, steps or 3
    variants = (("baseline_full_remat", "full", False),
                ("overlap_selective_remat", "save_big_matmuls", True))
    out = {}
    engines = {}
    for label, policy, overlap in variants:
        engine, mcfg = _remat_engine(jax, on_tpu, policy,
                                     overlap=overlap, mcfg=base_cfg)
        rng = np.random.default_rng(0)
        toks = {"tokens": rng.integers(
            0, mcfg.vocab_size,
            (engine.train_batch_size(), seqlen + 1), dtype=np.int32)}
        jax.block_until_ready(engine.train_batch(toks).loss)  # compile + warm
        engines[label] = (engine, toks)
    # interleaved best-of-3 windows: the two programs are near-identical
    # and the proxy host is noisy, so A/B/A/B windows + min cancel load
    # swings a sequential measurement would alias into the comparison
    best = {label: None for label, _, _ in variants}
    for _ in range(3):
        for label, _, _ in variants:
            engine, toks = engines[label]
            t0 = time.perf_counter()
            for _ in range(steps):
                o = engine.train_batch(toks)
            jax.block_until_ready(o.loss)
            dt = (time.perf_counter() - t0) / steps
            if best[label] is None or dt < best[label]:
                best[label] = dt
            out[label] = {"step_s": round(best[label], 4),
                          "final_loss": round(float(o.loss), 4)}
    ov.reset_layer_prefetch()
    base = out["baseline_full_remat"]["step_s"]
    tuned = out["overlap_selective_remat"]["step_s"]
    if tuned > 0:
        out["speedup"] = round(base / tuned, 3)
    return out


def _bench_result_from_file(path: str):
    """Extract the bench RESULT object from any BENCH artifact shape: a raw
    bench stdout capture (the JSON line is last), a bare result object,
    or a round wrapper ``{"n", "cmd", "rc", "tail"}`` with the JSON
    line embedded in ``tail``."""
    def scan_lines(text):
        for line in reversed(text.strip().splitlines()):
            line = line.strip()
            if line.startswith("{") and '"metric"' in line:
                try:
                    d = json.loads(line)
                except ValueError:
                    continue
                if isinstance(d, dict) and "metric" in d and "detail" in d:
                    return d
        return None

    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return None
    try:
        doc = json.loads(text)
    except ValueError:
        return scan_lines(text)
    if isinstance(doc, dict) and "metric" in doc and "detail" in doc:
        return doc
    if isinstance(doc, dict) and "tail" in doc:
        return scan_lines(str(doc["tail"]))
    return None


def find_newest_bench_artifact(base_dir: str = None):
    """Newest checked-in round artifact (``BENCH_r<NN>.json`` with the
    highest round number) — the reference the regression mode compares a
    fresh run against. Returns a path or None. ``DSTPU_BENCH_REF_DIR``
    overrides the search directory (tests, out-of-tree comparisons)."""
    import glob
    import re

    here = base_dir or os.environ.get("DSTPU_BENCH_REF_DIR") \
        or os.path.dirname(os.path.abspath(__file__))
    best_path, best_n = None, -1
    for p in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = re.search(r"BENCH_r(\d+)\.json$", p)
        if m and int(m.group(1)) > best_n:
            best_path, best_n = p, int(m.group(1))
    return best_path


def compare_step_time(fresh: dict, ref: dict, pct: float) -> dict:
    """Pure compare: fresh vs reference ``detail.step_time_s``, matched by
    backend class (a CPU run is never judged against a TPU one). ``fail`` =
    fresh step time more than ``pct`` percent above the reference."""
    def is_tpu(d):
        return "tpu" in str(d.get("detail", {}).get("backend", ""))

    def step_s(d):
        try:
            return float(d["detail"]["step_time_s"])
        except (KeyError, TypeError, ValueError):
            return 0.0

    row = {"threshold_pct": pct}
    if is_tpu(fresh) != is_tpu(ref):
        row["status"] = ("skipped: backend mismatch (fresh="
                         f"{fresh.get('detail', {}).get('backend')} ref="
                         f"{ref.get('detail', {}).get('backend')})")
        return row
    fs, rs = step_s(fresh), step_s(ref)
    if fs <= 0 or rs <= 0:
        row["status"] = "skipped: missing step_time_s"
        return row
    row.update({"fresh_step_s": round(fs, 4), "ref_step_s": round(rs, 4),
                "delta_pct": round((fs / rs - 1.0) * 100, 1),
                "fail": fs > rs * (1.0 + pct / 100.0)})
    row["status"] = "regressed" if row["fail"] else "ok"
    return row


def step_time_regression(base_dir: str = None, fresh: dict = None) -> dict:
    """Regression row vs the newest ``BENCH_r*.json``. Non-fatal by design:
    this documents the trajectory inside the artifact (and powers the
    ``--regression-only`` probe); it never poisons ``detail.ok``."""
    pct = float(os.environ.get("DSTPU_BENCH_REGRESSION_PCT", 20))
    ref_path = find_newest_bench_artifact(base_dir)
    if ref_path is None:
        return {"status": "skipped: no BENCH_r*.json reference"}
    ref = _bench_result_from_file(ref_path)
    if ref is None:
        return {"status": "skipped: unparseable reference "
                          + os.path.basename(ref_path)}
    row = compare_step_time(fresh or RESULT, ref, pct)
    row["reference_artifact"] = os.path.basename(ref_path)
    return row


def regression_only(fresh_path: str) -> int:
    """``bench.py --regression-only <fresh.json>``: compare an EXISTING
    capture against the newest
    ``BENCH_r*.json`` without re-running anything. Prints one JSON line;
    exit 1 on a confirmed >threshold step-time regression."""
    fresh = _bench_result_from_file(fresh_path)
    if fresh is None:
        row = {"status": f"skipped: unparseable fresh capture {fresh_path}"}
    else:
        row = step_time_regression(fresh=fresh)
    print(json.dumps({"metric": "bench_step_time_regression",
                      "value": row.get("delta_pct", 0.0),
                      "unit": "pct_step_time_delta",
                      "detail": row}))
    return 1 if row.get("fail") else 0


def bench_quantized_comm(jax, on_tpu) -> dict:
    """ZeRO++ trio wire-volume probe (quantized & hierarchical collectives,
    docs/performance.md): trace-time CommsTelemetry byte accounting for —

    (a) the stage-2 param all-gather, qwZ off vs on: quantized wire bytes vs
        the fp32 equivalent of the same payload (the >=3.5x acceptance
        number comes from algo accounting, not from an assertion);
    (b) gas-composed DP volume: plain stage-2 per-micro reduction vs
        deferred-GAS + qgZ int8 grads + qwZ int8 weight gather.

    Tiny model, one real step per config — the records are per-trace, so
    this costs seconds on CPU and TPU alike."""
    import numpy as np
    import jax.numpy as jnp

    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import comm as ds_comm
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama

    mcfg = llama.LlamaConfig.tiny(vocab_size=512, max_seq_len=64,
                                  use_pipeline=False)
    n_dev = max(1, len(jax.devices()))

    def run(zero, co=None, gas=1):
        mesh_lib.set_mesh(None)
        tel = ds_comm.get_telemetry()
        tel.reset()
        config = {
            "train_batch_size": 2 * n_dev * gas,
            "gradient_accumulation_steps": gas,
            "bf16": {"enabled": True},
            "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
            "zero_optimization": {"stage": 2, **zero},
            "comms_logger": {"enabled": True},
            "steps_per_print": 0,
        }
        if co:
            config["comms_overlap"] = co
        spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
        engine, _, _, _ = dst.initialize(model=spec, config=config)
        tokens = np.random.default_rng(0).integers(
            0, mcfg.vocab_size, (engine.train_batch_size(), 33),
            dtype=np.int32)
        engine.train_batch({"tokens": tokens})
        summ = tel.summary()
        gather = {k: v for k, v in summ.items()
                  if k.startswith("all_gather_params")}
        return {
            "gather_wire_bytes": int(sum(s["bytes"] for s in
                                         gather.values())),
            "gather_fp32_equiv": int(sum(s["fp32_equiv_bytes"] for s in
                                         gather.values())),
            "total_algo_bytes": int(tel.total_algo_bytes()),
        }

    base = run({})
    qwz = run({"zero_quantized_weights": True})
    gas = 2
    dp_base = run({}, gas=gas)
    dp_q = run({"zero_quantized_weights": True,
                "zero_quantized_gradients": True},
               co={"enabled": True, "deferred_gradient_reduce": True,
                   "loco": True, "coalesce_buckets": False}, gas=gas)
    out = {
        "ok": True,
        "allgather": {
            "fp32_equiv_bytes": qwz["gather_fp32_equiv"],
            "wire_bytes_base": base["gather_wire_bytes"],
            "wire_bytes_qwz": qwz["gather_wire_bytes"],
            # wire reduction of the weight gather vs an fp32 wire
            "qwz_reduction_vs_fp32": round(
                qwz["gather_fp32_equiv"]
                / max(qwz["gather_wire_bytes"], 1), 2),
        },
        "dp_volume": {
            "gas": gas,
            "algo_bytes_base": dp_base["total_algo_bytes"],
            "algo_bytes_qgz_qwz_deferred": dp_q["total_algo_bytes"],
            "reduction": round(dp_base["total_algo_bytes"]
                               / max(dp_q["total_algo_bytes"], 1), 2),
        },
    }
    return out


def run_quant_comm(jax, on_tpu) -> dict:
    """:func:`bench_quantized_comm`, but on a single-device backend there is
    no gather boundary to record — rerun the probe in a child on an
    8-virtual-device CPU mesh (it counts bytes at trace time and needs no
    chip; ``JAX_PLATFORMS=cpu`` keeps it off the one this process holds).
    Multi-device backends run in-process."""
    if len(jax.devices()) > 1:
        return bench_quantized_comm(jax, on_tpu)
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    out = subprocess.run([sys.executable, __file__, "--quant-comm-only"],
                         capture_output=True, text=True, timeout=560,
                         env=env)
    tail = [l for l in out.stdout.strip().splitlines()
            if l.startswith("QUANT_COMM=")]
    if out.returncode != 0 or not tail:
        raise RuntimeError(f"quant-comm child rc={out.returncode}: "
                           f"{out.stderr[-500:]}")
    child = json.loads(tail[-1][len("QUANT_COMM="):])
    child["devices"] = "8-virtual-cpu (single-device parent)"
    return child


def quant_comm_only():
    """Child entry for :func:`run_quant_comm` (env forces the 8-device
    virtual CPU mesh before jax initializes)."""
    import jax

    print("QUANT_COMM=" + json.dumps(bench_quantized_comm(jax, False)))


def bench_tiered_mem(jax, on_tpu, steps: int = None) -> dict:
    """``detail.tiered_mem`` — the tiered-memory acceptance probe
    (docs/memory.md): (a) optimizer host-offload step time vs the in-HBM
    baseline on the SAME model, with the store's measured transfer-overlap
    fraction (``Memory/tier/overlap_frac``: the share of transfer wall time
    hidden under compute — the ≥0.5 acceptance) and the device-resident
    byte delta between steps (host-tier opt state leaves the device
    allocator); (b) KV host-spill restore latency: admission of a fully
    spilled prefix (restore path) vs a cold admission of the same prompt.
    Non-fatal: failures return status and never poison the headline."""
    import numpy as np

    import jax.numpy as jnp

    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.telemetry.memory import MemoryTelemetry

    if steps is None:
        steps = 8 if on_tpu else 5
    mcfg = bench_model_config(on_tpu)
    seqlen = 512 if on_tpu else 128
    out: dict = {"ok": True}

    def run(tiered: bool):
        mesh_lib.set_mesh(None)
        config = {
            "train_batch_size": 8 * max(1, len(jax.devices())),
            "bf16": {"enabled": True},
            "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
            "zero_optimization": {"stage": 2},
            "steps_per_print": 0,
        }
        if tiered:
            config["memory"] = {"tiering": {"enabled": True,
                                            "optimizer_tier": "host"}}
        spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
        engine, _, _, _ = dst.initialize(model=spec, config=config)
        rng = np.random.default_rng(0)

        def batch():
            return {"tokens": rng.integers(
                0, mcfg.vocab_size,
                (engine.train_batch_size(), seqlen + 1), dtype=np.int32)}

        jax.block_until_ready(engine.train_batch(batch()).loss)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(steps):
            o = engine.train_batch(batch())
        jax.block_until_ready(o.loss)
        dt = (time.perf_counter() - t0) / steps
        import gc

        gc.collect()  # drop freed buffers before the live-array census
        resident = MemoryTelemetry().snapshot()["bytes_in_use"]
        return engine, dt, resident

    e0, dt0, res0 = run(False)
    opt_bytes = sum(getattr(l, "nbytes", 0)
                    for l in jax.tree.leaves(e0.state.opt_state))
    del e0
    e1, dt1, res1 = run(True)
    store = e1.tiered_store
    out["optimizer_offload"] = {
        "step_time_s_baseline": round(dt0, 4),
        "step_time_s_offload": round(dt1, 4),
        "slowdown": round(dt1 / dt0, 3) if dt0 > 0 else None,
        "opt_state_bytes": int(opt_bytes),
        "device_bytes_between_steps_baseline": int(res0),
        "device_bytes_between_steps_offload": int(res1),
        "device_bytes_delta": int(res0 - res1),
        "host_tier_resident_bytes": store.resident_bytes("host"),
        "overlap_frac": round(store.overlap_frac(), 3),
        "prefetch_hits": int(store.stats["prefetch_hits"]),
        "prefetch_misses": int(store.stats["prefetch_misses"]),
    }
    e1.destroy()
    del e1

    # --- (b) KV host-spill restore latency ---
    from deepspeed_tpu.inference.engine_v2 import build_engine_v2
    from deepspeed_tpu.inference.sampling import SamplingParams

    mesh_lib.set_mesh(None)
    icfg = llama.LlamaConfig.tiny(max_seq_len=256) if not on_tpu else mcfg
    params = llama.init(icfg, jax.random.PRNGKey(0))
    eng = build_engine_v2(
        llama, icfg, params,
        config={"dtype": "float32", "prefill_bucket": 16,
                "prefix_cache": {"enabled": True,
                                 "max_retained_blocks": 2,
                                 "host_spill": True},
                "ragged": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 4,
                           "memory_config_blocks": 64,
                           "block_size": 16}})
    sp = SamplingParams(greedy=True)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, icfg.vocab_size, (64,),
                            dtype=np.int32).tolist() for _ in range(3)]
    for i, p in enumerate(prompts):   # fill, decode, retire → spills
        eng.put(i, p, sp)
        eng.step(sp)
        eng.finish(i)
    # warm the restore path (the spill-write program compiles once)
    eng.put(80, prompts[1], sp)
    eng.step(sp)
    eng.finish(80)
    # cold admission (novel prompt) vs restore admission (spilled prefix)
    cold = rng.integers(0, icfg.vocab_size, (64,), dtype=np.int32).tolist()
    t0 = time.perf_counter()
    eng.put(90, cold, sp)
    eng.step(sp)
    t_cold = time.perf_counter() - t0
    eng.finish(90)
    t0 = time.perf_counter()
    eng.put(91, prompts[0], sp)       # restores spilled blocks
    eng.step(sp)
    t_restore = time.perf_counter() - t0
    eng.finish(91)
    st = eng.state.prefix_stats
    out["kv_spill"] = {
        "spills": int(st["spills"]),
        "restores": int(st["restores"]),
        "restored_tokens": int(st["restored_tokens"]),
        "admit_cold_s": round(t_cold, 4),
        "admit_restore_s": round(t_restore, 4),
        "restore_speedup": (round(t_cold / t_restore, 2)
                            if t_restore > 0 else None),
    }
    return out


def bench_integrity(jax, on_tpu, steps: int = None) -> dict:
    """``detail.integrity`` — fingerprint-plane overhead probe
    (docs/reliability.md "Numerics integrity & SDC"): the SAME model stepped
    with the numerics-integrity plane off vs on at ``check_interval=10``,
    reporting the step-time overhead fraction against the ≤2% acceptance
    budget. Also pins the default-OFF contract observable from here: the off
    run must emit zero ``Reliability/integrity/*`` events. ``ok`` gates on
    the event invariants only — the timing row is evidence, not a pass/fail
    (CPU-lane step times are too noisy for a 2% assertion)."""
    import numpy as np

    import jax.numpy as jnp

    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama

    if steps is None:
        # CPU-lane steps are ~7ms, so the first check round's one-time
        # host-path warmup needs more rounds to amortize out of the mean
        steps = 20 if on_tpu else 30
    mcfg = bench_model_config(on_tpu)
    seqlen = 512 if on_tpu else 128
    check_interval = 10
    steps = max(steps, check_interval)  # at least one check must fire

    def run(enabled: bool):
        mesh_lib.set_mesh(None)
        config = {
            "train_batch_size": 8 * max(1, len(jax.devices())),
            "bf16": {"enabled": True},
            "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
            "zero_optimization": {"stage": 2},
            "steps_per_print": 0,
        }
        if enabled:
            config["reliability"] = {"integrity": {
                "enabled": True, "check_interval": check_interval}}
        spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
        engine, _, _, _ = dst.initialize(model=spec, config=config)
        rng = np.random.default_rng(0)

        def batch():
            return {"tokens": rng.integers(
                0, mcfg.vocab_size,
                (engine.train_batch_size(), seqlen + 1), dtype=np.int32)}

        jax.block_until_ready(engine.train_batch(batch()).loss)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(steps):
            o = engine.train_batch(batch())
        jax.block_until_ready(o.loss)
        dt = (time.perf_counter() - t0) / steps
        counts = {k: int(v) for k, v in
                  dict(getattr(engine.telemetry, "reliability_counts",
                               {}) or {}).items()
                  if k.startswith("Reliability/integrity/")}
        engine.destroy()
        return dt, counts

    dt_off, ev_off = run(False)
    dt_on, ev_on = run(True)
    overhead = dt_on / dt_off - 1.0 if dt_off > 0 else None
    return {
        "ok": not ev_off and ev_on.get("Reliability/integrity/checks",
                                       0) > 0,
        "step_time_s_off": round(dt_off, 4),
        "step_time_s_on": round(dt_on, 4),
        "overhead_frac": (round(overhead, 4)
                          if overhead is not None else None),
        "budget_frac": 0.02,
        "within_budget": (overhead is not None and overhead <= 0.02),
        "check_interval": check_interval,
        "steps": steps,
        "events_off": ev_off,
        "events_on": ev_on,
    }


def bench_tuning(jax, on_tpu, steps: int = None) -> dict:
    """``detail.tuning`` — self-tuning runtime probe (docs/tuning.md):

    (a) **convergence oracle** (deterministic, fake clock): a synthetic
    knob whose score series is a planted function of the applied choice;
    the online tuner must find the planted optimum, persist it, and a
    fresh tuner must reload it with ZERO re-search trials — this row
    gates ``ok``;
    (b) **live-engine structural row**: a real engine with the ``tuning``
    block enabled on ``train.remat_policy`` (planted at the expensive
    ``full`` policy) stepped until the knob search closes — reports the
    measured per-arm scores, accept/revert/veto counters, and that no
    guard veto fired. Timing-dependent (CPU-lane step noise), so it is
    evidence, not a pass/fail."""
    import tempfile

    import numpy as np

    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.telemetry.schema import validate_events
    from deepspeed_tpu.tuning import (OnlineTuner, Tunable,
                                      TunableRegistry, TunerOptions,
                                      load_tuned)

    out = {}
    with tempfile.TemporaryDirectory() as td:
        # -- (a) planted-optimum oracle, fully deterministic -------- #
        path = os.path.join(td, "tuned.json")
        reg = TunableRegistry([Tunable(
            "bench.lanes", "lanes", (1, 2, 4),
            "Serving/sched/goodput_frac", "max", "sched_tick",
            root="sched_config")])
        opts = TunerOptions(enabled=True, steps_per_arm=5,
                            min_samples=3, seed=0, path=path)
        goodput = {1: 0.55, 2: 0.72, 4: 0.91}   # planted: 4 wins

        class _NS:
            lanes = 1

        def drive(tuner, ns, clock_box, nsteps=40):
            for step in range(nsteps):
                clock_box[0] += 1.0
                tuner.observe(
                    "Serving/sched/goodput_frac",
                    goodput[ns.lanes]
                    + 0.004 * ((step * 7) % 5 - 2))  # deterministic noise
                tuner.advance(step)

        ns, clock = _NS(), [0.0]
        tuner = OnlineTuner(reg, opts, boundary="sched_tick",
                            roots={"sched_config": ns},
                            clock=lambda: clock[0])
        drive(tuner, ns, clock)
        schema_problems = validate_events(tuner.events(step=40))
        ns2, clock2 = _NS(), [1000.0]
        fresh = OnlineTuner(reg, opts, boundary="sched_tick",
                            roots={"sched_config": ns2},
                            clock=lambda: clock2[0])
        out["oracle"] = {
            "planted_best": 4, "converged_to": ns.lanes,
            "persisted": load_tuned(path).get("bench.lanes"),
            "reloaded_value": ns2.lanes,
            "reload_trials": fresh.totals["trials"],
            "counts": dict(tuner.totals),
            "schema_problems": schema_problems,
        }
        oracle_ok = (ns.lanes == 4 and ns2.lanes == 4
                     and fresh.totals["trials"] == 0
                     and tuner.totals["vetoes"] == 0
                     and not schema_problems)

        # -- (b) live engine, remat knob planted suboptimal --------- #
        if steps is None:
            steps = 24
        mesh_lib.set_mesh(None)
        mcfg = bench_model_config(on_tpu)
        config = {
            "train_batch_size": 8 * max(1, len(jax.devices())),
            "bf16": {"enabled": True},
            "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
            "zero_optimization": {"stage": 2},
            "activation_checkpointing": {"policy": "full"},  # planted
            "steps_per_print": 0,
            "tuning": {"enabled": True,
                       "knobs": ["train.remat_policy"],
                       "steps_per_arm": 5, "min_samples": 3,
                       "max_dwell_factor": 2, "seed": 0,
                       "path": os.path.join(td, "engine_tuned.json")},
        }
        import jax.numpy as jnp

        spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
        engine, _, _, _ = dst.initialize(model=spec, config=config)
        rng = np.random.default_rng(0)
        seqlen = 512 if on_tpu else 128

        def batch():
            return {"tokens": rng.integers(
                0, mcfg.vocab_size,
                (engine.train_batch_size(), seqlen + 1), dtype=np.int32)}

        for _ in range(steps):
            o = engine.train_batch(batch())
        float(o.loss)
        s = engine.tuning.summary()
        knob = s["knobs"]["train.remat_policy"]
        out["engine"] = {
            "planted": "full", "final_policy": knob["value"],
            "phase": knob["phase"], "counts": knob["counts"],
            "arm_scores_ms": {k: round(v * 1.0, 3)
                              for k, v in knob["results"].items()},
            "steps": steps,
        }
        engine.destroy()
    out["ok"] = oracle_ok and out["engine"]["counts"]["vetoes"] == 0
    return out


def bench_long_context(jax, on_tpu) -> dict:
    """``detail.long_context`` — million-token-context memory probe
    (docs/performance.md "Million-token context"): (a) compiled-peak temp
    bytes of the full train step, dense logits vs ``sequence.tiled_loss``,
    at a context length where the dense [B, S, V] logits blow a fixed
    byte budget the tiled step fits inside — and the tiled step actually
    TRAINS at that length; (b) the tiled step's peak must scale ~linearly
    in S (the FPDT-pin convention: ratio ≲ shards, never ×V); (c) ring
    schedule evidence: zigzag per-rank causal block-pair counts are
    balanced where contiguous ones skew P:1, and the measured per-hop
    KV-transfer overlap fraction (``Comm/ring/overlap_frac``) is nonzero
    with pipelining ON and zero serialized. Non-fatal: failures return
    status and never poison the headline."""
    import numpy as np

    import jax.numpy as jnp

    import deepspeed_tpu as dst
    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.models import llama
    from deepspeed_tpu.sequence.ring import (measure_ring_overlap,
                                             ring_block_pair_counts)

    # logits-dominated shape: a big vocab makes the dense [B, S, V]
    # head the peak, while layers stay tiny enough for the CPU lane
    vocab = 65536 if not on_tpu else 131072
    s_small, s_big = (512, 2048) if not on_tpu else (4096, 16384)
    budget_mb = float(os.environ.get("DSTPU_BENCH_LONGCTX_BUDGET_MB",
                                     512 if not on_tpu else 4096))
    mcfg = llama.LlamaConfig(
        vocab_size=vocab, hidden_size=64, intermediate_size=128,
        num_layers=2, num_heads=2, num_kv_heads=2,
        max_seq_len=s_big + 1, remat=True)
    out: dict = {"ok": True, "budget_mb": budget_mb,
                 "vocab": vocab, "seq_len": s_big}

    def mk_engine(seqlen, tiled):
        mesh_lib.set_mesh(None)
        config = {
            "train_batch_size": max(1, len(jax.devices())),
            "bf16": {"enabled": True},
            "optimizer": {"type": "adamw", "params": {"lr": 3e-4}},
            "zero_optimization": {"stage": 2},
            "steps_per_print": 0,
        }
        if tiled:
            config["sequence"] = {"tiled_loss": True,
                                  "tiled_loss_shards": 16,
                                  "ring": {"layout": "zigzag",
                                           "overlap": True}}
        spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
        engine, _, _, _ = dst.initialize(model=spec, config=config)
        return engine

    def temp_peak_mb(seqlen, tiled):
        """Compiled-peak temp bytes of the real train step — compile
        only, never executed (the dense step at s_big is the one we
        are proving does NOT fit)."""
        engine = mk_engine(seqlen, tiled)
        rng = np.random.default_rng(0)
        batch = {"tokens": rng.integers(
            0, vocab, (engine.train_batch_size(), seqlen + 1),
            dtype=np.int32)}
        if engine._train_step is None:
            engine._build_train_step()
        sb = engine._shard_batch(batch, with_gas_dim=True)
        with engine.mesh_mgr.activate():
            comp = engine._train_step.lower(
                engine.state, sb, engine._lr_override).compile()
        mb = comp.memory_analysis().temp_size_in_bytes / 2**20
        engine.destroy()
        return mb

    dense_mb = temp_peak_mb(s_big, tiled=False)
    tiled_mb = temp_peak_mb(s_big, tiled=True)
    tiled_small_mb = temp_peak_mb(s_small, tiled=True)
    scale = s_big / s_small
    ratio = tiled_mb / max(tiled_small_mb, 1e-9)
    out["compiled_peak"] = {
        "dense_mb": round(dense_mb, 1),
        "tiled_mb": round(tiled_mb, 1),
        "dense_over_budget": dense_mb > budget_mb,
        "tiled_within_budget": tiled_mb <= budget_mb,
        "tiled_mb_at_quarter_seq": round(tiled_small_mb, 1),
        "tiled_scaling_ratio": round(ratio, 2),
        # linear ≈ scale; a dense head would add the ×(V/shards) cliff
        "tiled_scaling_linear": ratio < 2 * scale,
    }

    # the length the dense step cannot budget-fit must actually train
    engine = mk_engine(s_big, tiled=True)
    rng = np.random.default_rng(1)

    def batch():
        return {"tokens": rng.integers(
            0, vocab, (engine.train_batch_size(), s_big + 1),
            dtype=np.int32)}

    losses = [float(engine.train_batch(batch()).loss) for _ in range(2)]
    out["trains_at_dense_oom_len"] = {
        "losses": [round(l, 4) for l in losses],
        "finite": all(np.isfinite(losses)),
    }
    engine.destroy()

    # (c) ring schedule evidence — pure schedule math + the host-level
    # per-hop overlap measurement (writes Comm/ring/overlap_frac)
    p = 8
    zz = ring_block_pair_counts(p, "zigzag", causal=True)
    ct = ring_block_pair_counts(p, "contiguous", causal=True)
    ov_on = measure_ring_overlap(overlap=True, seq=2048)
    ov_off = measure_ring_overlap(overlap=False, seq=2048)
    out["ring"] = {
        "p_size": p,
        "zigzag_pair_counts": zz,
        "contiguous_pair_counts": ct,
        "zigzag_balanced": len(set(zz)) == 1,
        "contiguous_skew": max(ct) / max(min(ct), 1),
        "overlap_frac_on": round(ov_on["overlap_frac"], 3),
        "overlap_frac_off": round(ov_off["overlap_frac"], 3),
        "overlap_measured": ov_on["overlap_frac"] > 0.0,
    }
    out["ok"] = (out["compiled_peak"]["dense_over_budget"]
                 and out["compiled_peak"]["tiled_within_budget"]
                 and out["compiled_peak"]["tiled_scaling_linear"]
                 and out["trains_at_dense_oom_len"]["finite"]
                 and out["ring"]["zigzag_balanced"]
                 and out["ring"]["overlap_measured"])
    return out


def main(cpu: bool = False):
    jax = init_backend(cpu)
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dst
    from deepspeed_tpu.models import llama

    on_tpu = not cpu

    def timed(x):
        """A time or a rate: a number on the chip, null on the CPU."""
        return x if on_tpu else None

    # one process holds the chip, so decode runs here, before the trainer
    RESULT["detail"]["decode_tok_per_sec"] = timed(
        bench_decode(jax, bench_model_config(on_tpu)))
    mcfg = bench_model_config(on_tpu, remat=True)
    if on_tpu:
        batch, seqlen, steps, warmup = 8, 2048, 20, 3
    else:
        batch, seqlen, steps, warmup = 8, 128, 5, 1

    config = {
        "train_batch_size": batch * max(1, len(jax.devices())),
        "bf16": {"enabled": True},
        "optimizer": {"type": "adamw", "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "zero_optimization": {"stage": 3},
        "gradient_clipping": 1.0,
        "steps_per_print": 0,
        # trace-time comm accounting (free at run time): the per-step
        # collective count + algorithmic bytes land in detail so comm-volume
        # regressions are visible from the headline artifact
        "comms_logger": {"enabled": True},
    }
    sys.stderr.write(f"[bench] t={time.perf_counter():.0f} building engine\n")
    spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
    engine, _, _, _ = dst.initialize(model=spec, config=config)

    rng = np.random.default_rng(0)

    def make_batch(i):
        return {"tokens": rng.integers(0, mcfg.vocab_size,
                                       (engine.train_batch_size(), seqlen + 1),
                                       dtype=np.int32)}

    sys.stderr.write(f"[bench] t={time.perf_counter():.0f} engine ready, warmup\n")
    for i in range(warmup):
        out = engine.train_batch(make_batch(i))
        jax.block_until_ready(out.loss)
        sys.stderr.write(f"[bench] t={time.perf_counter():.0f} warmup {i} done loss={float(out.loss):.3f}\n")

    t0 = time.perf_counter()
    for i in range(steps):
        out = engine.train_batch(make_batch(warmup + i))
    jax.block_until_ready(out.loss)  # drains the async dispatch queue
    dt = time.perf_counter() - t0
    final_loss = float(out.loss)

    n_chips = len(jax.devices())
    tokens_per_step = engine.train_batch_size() * seqlen
    tokens_per_sec_per_chip = tokens_per_step * steps / dt / n_chips
    n_params = mcfg.num_params
    flops_per_token = model_flops_per_token(mcfg, seqlen)
    if on_tpu:  # utilization exists against a published peak or not at all
        mfu = tokens_per_sec_per_chip * flops_per_token \
            / peak_flops_per_chip(jax)
        RESULT["value"] = round(mfu, 4)
        RESULT["vs_baseline"] = round(mfu / 0.45, 4)
    else:
        RESULT["value"] = RESULT["vs_baseline"] = None
    RESULT["detail"].update({
        "tokens_per_sec_per_chip": timed(round(tokens_per_sec_per_chip, 1)),
        "step_time_s": timed(round(dt / steps, 4)),
        "params": n_params,
        "batch": engine.train_batch_size(),
        "seqlen": seqlen,
        "final_loss": final_loss,
    })
    from deepspeed_tpu.comm import comm as ds_comm

    tel = ds_comm.get_telemetry()
    if tel.records:
        total_algo = tel.total_algo_bytes()
        RESULT["detail"]["comm_per_step"] = {
            "collectives": int(sum(s["count"]
                                   for s in tel.summary().values())),
            "algo_bytes": int(total_algo),
            "busbw_gbps": timed(round(total_algo / (dt / steps) / 1e9, 2)),
        }
    if cpu:  # the schema run ends here: every probe below prints timings
        emit(ok=True)
        return
    # 8B-class shape rows (each is a multi-minute compile; the persistent
    # cache makes re-runs cheap)
    del engine  # free the headline engine's state before the sweep
    RESULT["detail"]["shape_mfu"] = bench_shape_rows(jax)

    # standalone attention MFU at hd=128/bq=512, the per-remat-policy
    # HBM-vs-step-time sweep, and the combined overlap+selective-remat vs
    # full-remat comparison. Skippable for narrow-budget runs via
    # DSTPU_BENCH_REMAT=0.
    if os.environ.get("DSTPU_BENCH_REMAT", "1") not in ("", "0"):
        RESULT["detail"]["attn_probe"] = bench_attention_probe(jax)
        RESULT["detail"]["remat_sweep"] = bench_remat_sweep(jax, on_tpu)
        RESULT["detail"]["overlap_remat"] = bench_overlap_remat(jax, on_tpu)

    # ZeRO++ trio wire-volume accounting (qwZ all-gather compression, gas-
    # composed qgZ+qwZ DP volume) — trace-time byte records, seconds to run.
    # Skippable via DSTPU_BENCH_QCOMM=0.
    if os.environ.get("DSTPU_BENCH_QCOMM", "1") not in ("", "0"):
        RESULT["detail"]["quant_comm"] = run_quant_comm(jax, on_tpu)

    # tiered-memory acceptance probe (docs/memory.md): optimizer host-
    # offload step time + measured transfer-overlap fraction vs the in-HBM
    # baseline, and KV host-spill restore latency. Skippable via
    # DSTPU_BENCH_TIERED=0.
    if os.environ.get("DSTPU_BENCH_TIERED", "1") not in ("", "0"):
        RESULT["detail"]["tiered_mem"] = bench_tiered_mem(jax, on_tpu)

    # numerics-integrity plane overhead probe (docs/reliability.md "Numerics
    # integrity & SDC"): step time with cross-replica fingerprints off vs on
    # at check_interval=10 against the ≤2% budget, plus the default-OFF
    # zero-events pin. Skippable via DSTPU_BENCH_INTEGRITY=0.
    if os.environ.get("DSTPU_BENCH_INTEGRITY", "1") not in ("", "0"):
        RESULT["detail"]["integrity"] = bench_integrity(jax, on_tpu)

    # million-token-context memory probe (docs/performance.md "Million-token
    # context"): dense-logits vs tiled-loss compiled peaks against a byte
    # budget, the tiled step training at the dense-over-budget length, and
    # the ring zigzag-balance + measured overlap evidence. Skippable via
    # DSTPU_BENCH_LONGCTX=0.
    if os.environ.get("DSTPU_BENCH_LONGCTX", "1") not in ("", "0"):
        RESULT["detail"]["long_context"] = bench_long_context(jax, on_tpu)

    # self-tuning runtime probe (docs/tuning.md): deterministic planted-
    # optimum convergence + persist/reload oracle (gates the row's ok), and
    # a live-engine remat-knob search with guard counters. Skippable via
    # DSTPU_BENCH_TUNING=0.
    if os.environ.get("DSTPU_BENCH_TUNING", "1") not in ("", "0"):
        RESULT["detail"]["tuning"] = bench_tuning(jax, on_tpu)

    # step-time regression vs the newest BENCH_r*.json beside this file,
    # if there is one — informational here (the gating form is
    # --regression-only). Skippable via DSTPU_BENCH_REGRESSION=0.
    if os.environ.get("DSTPU_BENCH_REGRESSION", "1") not in ("", "0"):
        RESULT["detail"]["regression"] = step_time_regression()

    emit(ok=True)


def bench_decode(jax, mcfg, batch: int = 16, prompt_len: int = None,
                 decode_steps: int = None) -> float:
    """Continuous-batching decode throughput (paged Pallas kernel path) —
    tokens/sec across the batch at steady state. Sizes scale from the model's
    max_seq_len so the ``--cpu`` tiny config fits its block tables."""
    import numpy as np

    if prompt_len is None:
        prompt_len = min(128, mcfg.max_seq_len // 4)
    if decode_steps is None:
        decode_steps = min(64, mcfg.max_seq_len // 2 - prompt_len - 1)

    from deepspeed_tpu.comm import mesh as mesh_lib
    from deepspeed_tpu.inference.engine_v2 import build_engine_v2
    from deepspeed_tpu.inference.sampling import SamplingParams
    from deepspeed_tpu.models import llama

    mesh_lib.set_mesh(None)
    params = llama.init(mcfg, jax.random.PRNGKey(0))
    eng = build_engine_v2(
        llama, mcfg, params,
        config={"dtype": "bfloat16", "prefill_bucket": prompt_len,
                "ragged": {"max_tracked_sequences": batch,
                           "max_ragged_batch_size": batch,
                           "memory_config_blocks": batch * 24,
                           "block_size": 32}})
    rng = np.random.default_rng(0)
    sp = SamplingParams(greedy=True)
    for uid in range(batch):
        eng.put(uid, rng.integers(0, mcfg.vocab_size, (prompt_len,),
                                  dtype=np.int32).tolist(), sp)
    # fused quantum (step_many): one host sync per `q` tokens
    q = max(1, min(8, decode_steps))
    eng.step_many(q, sp)  # compile + warm
    done = 0
    t0 = time.perf_counter()
    while done < batch * decode_steps:
        out = eng.step_many(q, sp)  # host-int return: call is synchronized
        produced = sum(len(v) for v in out.values())
        if produced == 0:
            break  # context capacity reached — never count no-op calls
        done += produced
    dt = time.perf_counter() - t0
    return round(done / dt, 1)


if __name__ == "__main__":
    if "--quant-comm-only" in sys.argv:
        quant_comm_only()
        sys.exit(0)
    if "--regression-only" in sys.argv:
        idx = sys.argv.index("--regression-only")
        if idx + 1 >= len(sys.argv):
            print("usage: bench.py --regression-only <fresh_bench.json>",
                  file=sys.stderr)
            sys.exit(2)
        sys.exit(regression_only(sys.argv[idx + 1]))
    try:
        main(cpu="--cpu" in sys.argv)
    except Exception:
        # the JSON line reports the failure; the exit code is the failure
        emit(ok=False, err=traceback.format_exc())
        sys.exit(1)
