#!/usr/bin/env python
"""Model-compression walkthrough: snip_momentum structured pruning + QAT
fake-quant during training, then post-training weight quantization — the
reference's `init_compression`/`redundancy_clean` flow as pure pytree
transforms (reference: deepspeed/compression/compress.py, constants.py).

    JAX_PLATFORMS=cpu python examples/compress_model.py --tiny
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true", help="CPU-smoke model")
    ap.add_argument("--steps", type=int, default=12)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu as dst
    from deepspeed_tpu.compression import (CompressionScheduler,
                                           init_compression,
                                           quantize_weights_ptq)
    from deepspeed_tpu.models import llama

    mcfg = llama.LlamaConfig.tiny() if args.tiny else llama.LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=3584,
        num_layers=12, num_heads=8, num_kv_heads=4, max_seq_len=2048)

    # reference-style compression config block (ds_config "compression_training")
    compression_config = {
        "weight_quantization": {"enabled": True, "bits": 8,
                                "schedule_offset": 4},
        "sparse_pruning": {"enabled": True, "method": "snip_momentum",
                           "dense_ratio": 0.75, "block_pattern": "4x1",
                           "schedule_offset": 2,
                           "schedule_offset_end": args.steps - 2,
                           "schedule_offset_stride": 2,
                           "excluded_modules": ["embed", "norm"]},
    }

    spec = llama.model_spec(mcfg, compute_dtype=jnp.float32)
    engine, _, _, _ = dst.initialize(model=spec, config={
        "train_batch_size": 4,
        "optimizer": {"type": "adamw", "params": {"lr": 1e-3}},
        "zero_optimization": {"stage": 2},
        "steps_per_print": 0,
    })

    # construction-time methods (layer reduction, when configured) apply to
    # the real param tree; the returned plan drives the training-time ones
    raw = llama.init(mcfg, jax.random.PRNGKey(0))
    raw, plan = init_compression(raw, compression_config)
    sched = CompressionScheduler(plan)

    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(0, mcfg.vocab_size, (4, 33),
                                    dtype=np.int32)}
    for step in range(args.steps):
        out = engine.train_batch(batch)
        print(f"step {step}: loss={float(out.loss):.4f}")

    # The compression transforms are pure pytree functions the scheduler
    # drives: feed each step's (params, grads) into observe_gradients — the
    # snip_momentum saliency is |w * dL/dw|, so it needs REAL gradients. In
    # a training loop you pass each step's fresh grads; params and batch are
    # fixed in this demo, so ONE probe gradient serves every step (the loop
    # below only advances the pruning schedule):
    def loss_fn(p):
        logits = llama.apply(mcfg, p, jnp.asarray(batch["tokens"][:, :-1]))
        tgt = jnp.asarray(batch["tokens"][:, 1:])
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(lp, tgt[..., None], -1))

    probe_grads = jax.jit(jax.grad(loss_fn))(raw)
    for step in range(args.steps):
        sched.observe_gradients(raw, probe_grads, step)
    pruned = sched.transform(raw, step=args.steps)
    total = kept = 0
    for leaf in jax.tree.leaves(pruned):
        if hasattr(leaf, "size") and leaf.ndim >= 2:
            total += leaf.size
            kept += int((np.asarray(leaf) != 0).sum())
    print(f"pruned+QAT params: {kept}/{total} nonzero "
          f"({1 - kept / max(total, 1):.1%} sparse)")

    ptq = quantize_weights_ptq(raw, bits=8)
    print("PTQ int8 roundtrip max drift:",
          float(max(jnp.max(jnp.abs(a - b)) for a, b in zip(
              jax.tree.leaves(raw), jax.tree.leaves(ptq)))))
    print("COMPRESS_EXAMPLE_OK")


if __name__ == "__main__":
    main()
