#!/usr/bin/env python
"""Long-context example: FPDT chunked attention for training, SplitFuse
chunked prefill for serving.

Training: ``attention_impl="fpdt"`` (single-chip chunked flash attention
with optional host-KV streaming) or ``"ulysses_fpdt"`` (the Ulysses a2a +
chunked composition — the reference's FPDT) via the model config.

Serving: ``split_prefill_chunk`` streams a long prompt into the KV cache
one chunk per step, so live decodes never stall for a whole prompt.

    python examples/long_context.py [--seq 1024] [--steps 4]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--offload-kv", action="store_true",
                    help="park K/V in host memory between chunks")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dst
    from deepspeed_tpu.models import llama

    # ---- training with chunked (FPDT) attention -------------------------
    if args.seq % 4:
        args.seq += 4 - args.seq % 4  # fpdt needs seq % fpdt_chunks == 0
        print(f"(rounded --seq up to {args.seq}: divisible by fpdt_chunks=4)")
    mcfg = llama.LlamaConfig.tiny(
        max_seq_len=args.seq, attention_impl="fpdt", fpdt_chunks=4,
        fpdt_offload_kv=args.offload_kv)
    spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
    engine, _, _, _ = dst.initialize(model=spec, config={
        "train_batch_size": 2,
        "bf16": {"enabled": True},
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "steps_per_print": 0})
    rng = np.random.default_rng(0)
    toks = {"tokens": rng.integers(0, mcfg.vocab_size,
                                   (2, args.seq + 1), dtype=np.int32)}
    t0 = time.perf_counter()
    for _ in range(args.steps):
        out = engine.train_batch(toks)
    loss = float(out.loss)
    print(f"fpdt train: {args.steps} steps at S={args.seq} "
          f"({(time.perf_counter() - t0) / args.steps:.2f}s/step), "
          f"final loss {loss:.3f}")

    # ---- serving a long prompt with SplitFuse chunked prefill -----------
    from deepspeed_tpu.inference.engine_v2 import build_engine_v2
    from deepspeed_tpu.inference.sampling import SamplingParams

    scfg = llama.LlamaConfig.tiny(max_seq_len=max(256, args.seq))
    eng = build_engine_v2(
        llama, scfg, llama.init(scfg, jax.random.PRNGKey(0)),
        config={"dtype": "float32", "prefill_bucket": 32,
                "split_prefill_chunk": 32,
                "ragged": {"max_tracked_sequences": 4,
                           "max_ragged_batch_size": 4,
                           "memory_config_blocks": 128, "block_size": 16}})
    sp = SamplingParams(greedy=True)
    eng.put(0, rng.integers(0, scfg.vocab_size, (8,)).tolist(), sp)  # live
    long_prompt = rng.integers(0, scfg.vocab_size,
                               (min(100, scfg.max_seq_len - 16),))
    eng.put_split(1, long_prompt.tolist(), sp)
    steps = 0
    while 1 not in eng.state.seqs or not eng.state.seqs[1].generated:
        out = eng.step(sp)
        assert 0 in out, "live decode starved during split prefill"
        steps += 1
    print(f"splitfuse serve: {len(long_prompt)}-token prompt streamed in "
          f"over {steps} steps; live decode got a token every step")


if __name__ == "__main__":
    main()
