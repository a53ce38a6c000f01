#!/usr/bin/env python
"""Minimal end-to-end training example: Llama-class causal LM, ZeRO-3,
bf16, cosine schedule, checkpointing. Run on any backend:

    python examples/train_llama.py                 # real chips
    JAX_PLATFORMS=cpu python examples/train_llama.py --tiny   # laptop smoke

The config dict is key-compatible with reference DeepSpeed JSON configs —
point --config at an existing ds_config.json to reuse it directly.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=None, help="ds_config.json path")
    ap.add_argument("--tiny", action="store_true", help="CPU-smoke model")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt", default=None, help="checkpoint dir")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dst
    from deepspeed_tpu.models import llama

    mcfg = llama.LlamaConfig.tiny() if args.tiny else llama.LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=3584,
        num_layers=12, num_heads=8, num_kv_heads=4, max_seq_len=2048,
        remat=True)
    config = args.config or {
        "train_batch_size": 8,
        "bf16": {"enabled": True},
        "optimizer": {"type": "adamw",
                      "params": {"lr": 3e-4, "weight_decay": 0.1}},
        "scheduler": {"type": "WarmupCosineLR",
                      "params": {"warmup_num_steps": 5,
                                 "total_num_steps": args.steps}},
        "zero_optimization": {"stage": 3},
        "gradient_clipping": 1.0,
        "steps_per_print": 5,
    }
    spec = llama.model_spec(mcfg, compute_dtype=jnp.bfloat16)
    engine, _, _, _ = dst.initialize(model=spec, config=config)

    rng = np.random.default_rng(0)
    seq = min(256, mcfg.max_seq_len)
    for step in range(args.steps):
        batch = {"tokens": rng.integers(
            0, mcfg.vocab_size, (engine.train_batch_size(), seq + 1),
            dtype=np.int32)}
        out = engine.train_batch(batch)
    print(f"final loss {float(out.loss):.4f} after {args.steps} steps "
          f"({mcfg.num_params/1e6:.1f}M params)")
    if args.ckpt:
        path = engine.save_checkpoint(args.ckpt)
        print(f"checkpoint: {path}")


if __name__ == "__main__":
    main()
