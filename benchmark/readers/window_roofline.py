"""The paged attention kernels' share of their roofline over a stack of
window AND full layers, in percent. ``what`` ``decode``: the bytes the decode
rows' walks must read in every call of the traced window - ``(window layers
x min(context, window) + full layers x context) x 4096 B`` a row
(``costs_window.decode_kv_bytes``) - over the HBM peak; ``prefill``: the
chunks' useful attention operations (``costs_window.chunk_attn_flops``) over
the bf16 peak; each over the device time of the events of the kernel named
``kernel`` (the profiler names a Mosaic event by its HLO instruction,
``<kernel>.N``). No kernel is new: these are the old kernels' shares at a
geometry (group 16) and a mix of walks (two tables in one program) no other
cell has. A floor counts what the model needs, so a kernel that visits and
masks reads low. A program whose spans carry no kinds, or whose trace holds
no such kernel, reports nothing. Serve cells: one chip."""

import re

from benchmark.harness import costs_window
from benchmark.harness import trace as tr
from benchmark.readers import window_calls


def read(ctx, what, kernel):
    calls = window_calls.calls(ctx)
    if not calls or ctx.get("peaks") is None:
        return None
    trace = ctx["trace"]
    window = trace.window()
    mine = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    ops = next(iter(trace.devices.values()), [])
    seconds = tr.total(tr.busy_intervals(
        ops, window, lambda o: bool(mine.match(o.name)))) / 1e9
    if not seconds:
        return None
    model, peaks = ctx["cell"].model, ctx["peaks"]
    if what == "decode":
        floor = sum(costs_window.decode_kv_bytes(
            model, c["full"], c["window"]) for c in calls
            if c["kind"] == "decode") / peaks.hbm_bytes_per_s
    else:
        floor = sum(costs_window.chunk_attn_flops(
            model, c["ctx"], c["tokens"]) for c in calls
            if c["kind"] == "chunk") / peaks.bf16_flops
    return 100.0 * floor / seconds if floor else None
