"""The expert banks' share of their roofline, in percent: the least time the
chip could take for the banks of every model call inside the traced window
(``costs_moe.bank_floor_s`` of the call's ``moe_rows_routed``, times the
layers of the configuration as it is run) over the device time of the
operations under the ``moe_experts`` scope, which holds the bank's matmuls
AND the combine back to tokens. Serve cells: one chip."""

from benchmark.harness import costs_moe
from benchmark.harness import program_spans as ps
from benchmark.readers import moe_padded_row_share


def read(ctx, spans, scope="moe_experts"):
    rows = moe_padded_row_share.calls(ctx, spans)
    program = ps.load(ctx)
    if not rows or ctx.get("peaks") is None or not program.ops:
        return None
    model = ctx["cell"].model
    floor_s = model["num_hidden_layers"] * sum(
        costs_moe.bank_floor_s(model, routed, ctx["peaks"])
        for routed, _ in rows)
    ops = next(iter(program.ops.values()))
    seconds = ps.scope_seconds(ops, ctx["trace"].window()).get(scope, 0.0)
    return 100.0 * floor_s / seconds if seconds else None
