"""Model FLOP/s utilization of training: operations the forward and
backward passes require per token (``costs.train_flops_per_token``;
recomputation not counted) times the window's rate per chip, over the
chip's published bf16 peak."""

from benchmark.harness import costs


def read(ctx):
    if ctx.get("peaks") is None or ctx.get("rate") is None:
        return None
    flops = costs.train_flops_per_token(ctx["cell"].model, ctx["seq_len"])
    return 100.0 * flops * ctx["rate"] / ctx["peaks"].bf16_flops
