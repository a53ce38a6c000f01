"""The paged walk's share of its roofline over a latent (MLA) pool, in
percent. ``what`` ``decode``: the bytes the decode rows' walks must read in
every call of the traced window - ``layers x context x 1152 B`` a row
(``costs_mla.decode_kv_bytes``: the 576 numbers a token keeps, not the lanes
the pool pads them with) - over the HBM peak; ``prefill``: the chunks'
useful attention operations in the absorbed form (``costs_mla.
chunk_attn_flops``: 64 heads x 2 x (576 + 512) a (row, key) pair the mask
keeps) over the bf16 peak; each over the device time of the events of the
kernel named ``kernel`` (the profiler names a Mosaic event by its HLO
instruction, ``<kernel>.N``). The kernels are the one walk every serve cell
has, at a geometry no other cell has: one KV head, a group of 64, keys 640
lanes and values the first 512 of the same page. A floor counts what the
model needs, so a kernel that visits and masks reads low. A program whose
spans carry no latent counts, or whose trace holds no such kernel, reports
nothing. Serve cells: one chip."""

import re

from benchmark.harness import costs_mla
from benchmark.harness import trace as tr
from benchmark.readers import latent_calls


def read(ctx, what, kernel):
    calls = latent_calls.calls(ctx)
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
        floor = sum(costs_mla.decode_kv_bytes(model, c["tokens"])
                    for c in calls if c["kind"] == "decode") \
            / peaks.hbm_bytes_per_s
    else:
        floor = sum(costs_mla.chunk_attn_flops(model, c["ctx"], c["rows"])
                    for c in calls if c["kind"] == "chunk") \
            / peaks.bf16_flops
    return 100.0 * floor / seconds if floor else None
