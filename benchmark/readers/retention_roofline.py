"""A retention kernel's share of its roofline, in percent, over the device
time of the kernel's own events in the traced window (the profiler names a
Mosaic event by its HLO instruction, ``<kernel>.N``). ``decode``: the
single-token update - every ``decode_step`` span says how many live rows'
state ONE layer advances (``retention_rows``); each row's state is read once
and written once a layer (``costs_retention.decode_update_floor_bytes``),
times the configuration's layers, over the HBM peak. ``chunk``: the chunked
form - every ``decode_step`` and ``prefill_chunk`` span says the chunk's
rows (``retention_chunk_rows``); a call's floor is the larger of its linear
form's operations over the bf16 peak and one read and one write of the
sequence's state over the HBM peak (``costs_retention.chunk_floor_s``),
times the layers. Both count the SYMMETRIC state whatever the kernel
streams, so neither can read over 100 % unless the count is wrong. A
program whose spans carry no such argument, or whose trace holds no such
kernel, reports nothing."""

from benchmark.harness import costs_retention as costs
from benchmark.harness import program_spans as ps
from benchmark.readers.nemotron_h_roofline import kernel_seconds

ARGS = {"decode": ("retention_rows", ("decode_step",)),
        "chunk": ("retention_chunk_rows", ("decode_step", "prefill_chunk"))}


def read(ctx, kernel, what):
    program = ps.load(ctx)
    if program is None or ctx.get("peaks") is None:
        return None
    seconds = kernel_seconds(ctx, kernel)
    window = ctx["trace"].window()
    arg, spans = ARGS[what]
    counts = [s.arg(arg) for name in spans
              for s in ps.named(program.spans, name, window)]
    counts = [c for c in counts if c]
    if not seconds or not counts:
        return None
    cell, peaks = ctx["cell"], ctx["peaks"]
    layers = cell.model["num_hidden_layers"]
    if what == "decode":
        floor_s = layers * costs.decode_update_floor_bytes(
            cell.model, cell.role, sum(counts)) / peaks.hbm_bytes_per_s
    else:
        floor_s = layers * sum(costs.chunk_floor_s(cell.model, cell.role, c,
                                                   peaks) for c in counts)
    return 100.0 * floor_s / seconds
