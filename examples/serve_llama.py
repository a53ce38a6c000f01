#!/usr/bin/env python
"""Minimal serving example: continuous batching (v2 engine) with the fused
decode quantum. Loads an HF checkpoint directory if given, else random
weights on the tiny config.

    python examples/serve_llama.py [--checkpoint /path/to/hf-llama]
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--max-new-tokens", type=int, default=32)
    args = ap.parse_args()

    import jax
    import numpy as np

    from deepspeed_tpu.inference.engine_v2 import (build_engine_v2,
                                                   build_hf_engine)
    from deepspeed_tpu.models import llama

    if args.checkpoint:
        eng = build_hf_engine(args.checkpoint,
                              config={"dtype": "bfloat16"})
        vocab = eng.family.cfg.vocab_size
    else:
        mcfg = llama.LlamaConfig.tiny()
        eng = build_engine_v2(
            llama, mcfg, llama.init(mcfg, jax.random.PRNGKey(0)),
            config={"dtype": "float32", "prefill_bucket": 16,
                    "ragged": {"max_tracked_sequences": 4,
                               "max_ragged_batch_size": 4,
                               "memory_config_blocks": 64,
                               "block_size": 16}})
        vocab = mcfg.vocab_size

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
               for n in (12, 7, 15)]
    t0 = time.perf_counter()
    outs = eng.generate(prompts, max_new_tokens=args.max_new_tokens,
                        steps_per_sync=8)
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    print(f"{total} tokens in {dt:.2f}s ({total/dt:.1f} tok/s)")
    for i, o in enumerate(outs):
        print(f"prompt {i}: {o[:10]}{'...' if len(o) > 10 else ''}")


if __name__ == "__main__":
    main()
