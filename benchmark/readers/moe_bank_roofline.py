"""The grouped expert matmul's share of its roofline, in percent, for a
configuration whose ``intermediate_size`` is NOT one expert's width (a
published dense width no sparse layer has; one expert's is under
``width_key``): the least time the chip could take for the banks of every
model call inside the traced window - ``costs_moe.bank_floor_s`` of the
call's ``moe_rows_routed``, the function the older cells' bank shares are
counted by, handed the configuration with ``width_key``'s value in
``intermediate_size``'s place, times the layers as run - over the device
time of the events of the kernel named ``kernel`` (the profiler names a
Mosaic event by its HLO instruction, ``<kernel>.N``). Experts touched are
reckoned over the router's whole width under uniform routing
(``costs_moe.experts_touched``). A program whose spans carry no row counts,
or whose trace holds no such kernel, reports nothing. Serve cells: one
chip."""

from benchmark.harness import costs_moe
from benchmark.readers import moe_padded_row_share
from benchmark.readers.nemotron_h_roofline import kernel_seconds


def read(ctx, spans, kernel, width_key):
    rows = moe_padded_row_share.calls(ctx, spans)
    if not rows or ctx.get("peaks") is None:
        return None
    seconds = kernel_seconds(ctx, kernel)
    if not seconds:
        return None
    model = ctx["cell"].model
    one = {**model, "intermediate_size": model[width_key]}
    floor_s = model["num_hidden_layers"] * sum(
        costs_moe.bank_floor_s(one, routed, ctx["peaks"])
        for routed, _ in rows)
    return 100.0 * floor_s / seconds
