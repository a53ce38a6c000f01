"""The single-token state update's share of the HBM roofline, in percent:
the bytes the kernel named ``kernel`` must move - every ``decode_step`` span
inside the traced window says how many rows' state ONE Mamba layer of the
call advances (``ssm_rows``: the active slots); each row's state is read
once and written once a layer (``costs_ssm.decode_update_floor_bytes``),
times the configuration's Mamba layers - over the HBM peak, over the device
time of the kernel's events in the window (the profiler names a Mosaic event
by its HLO instruction, ``<kernel>.N``). It cannot read over 100 % unless
the kernel skips a live row. A program whose spans carry no ``ssm_rows``, or
whose trace holds no such kernel, reports nothing."""

import re

from benchmark.harness import costs_ssm
from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr


def read(ctx, kernel, span="decode_step", arg="ssm_rows"):
    program = ps.load(ctx)
    if program is None or ctx.get("peaks") is None:
        return None
    trace = ctx["trace"]
    window = trace.window()
    mine = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    ops = next(iter(trace.devices.values()), [])   # serve cells: one chip
    seconds = tr.total(tr.busy_intervals(
        ops, window, lambda o: bool(mine.match(o.name)))) / 1e9
    rows = [s.arg(arg) for s in ps.named(program.spans, span, window)]
    rows = [r for r in rows if r is not None]
    if not seconds or not rows:
        return None
    cell = ctx["cell"]
    layers = costs_ssm.layer_counts(cell.model)["mamba"]
    floor_s = layers * costs_ssm.decode_update_floor_bytes(
        cell.model, cell.role, sum(rows)) / ctx["peaks"].hbm_bytes_per_s
    return 100.0 * floor_s / seconds
