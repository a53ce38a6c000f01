"""A Nemotron-H kernel's share of its roofline, in percent, over the device
time of the kernel's own events in the traced window (the profiler names a
Mosaic event by its HLO instruction, ``<kernel>.N``). ``ssm``: the grouped
single-token state update - every ``decode_step`` span says how many rows'
state ONE Mamba layer advances (``ssm_rows``); each row's state is read once
and written once a layer (``costs_nemotron_h.decode_update_floor_bytes``),
times the configuration's Mamba layers, over the HBM peak. ``experts``: the
two-matrix grouped matmul - every model call's span says its token rows
(``moe_rows_routed``, a shape fact: ``costs_nemotron_h.call_tokens`` undoes
it); how many of them choose each held expert of each sparse layer is
MEASURED, over the rows of this run's probes
(``reference/nemotron_h.py routed_shares``: the program reports no count of
its own, ROADMAP housekeeping 12), and the floor is the larger of the
experts those rows reach, in bytes, and the routed rows' operations
(``costs_nemotron_h.bank_floor_s``), layer by layer. Neither can read over
100 % unless the count is wrong. A program whose spans carry no such
argument, or whose trace holds no such kernel, reports nothing; nor does a
run that routed no probe."""

import re

from benchmark.harness import costs_nemotron_h as costs
from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr
from benchmark.reference import nemotron_h as reference


def kernel_seconds(ctx, kernel: str) -> float:
    """Device seconds of the events named ``kernel`` in the window (serve
    cells: one chip)."""
    trace = ctx["trace"]
    mine = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    ops = next(iter(trace.devices.values()), [])
    return tr.total(tr.busy_intervals(
        ops, trace.window(), lambda o: bool(mine.match(o.name)))) / 1e9


def read(ctx, kernel, what, spans, arg):
    program = ps.load(ctx)
    if program is None or ctx.get("peaks") is None:
        return None
    seconds = kernel_seconds(ctx, kernel)
    window = ctx["trace"].window()
    counts = [s.arg(arg) for name in spans
              for s in ps.named(program.spans, name, window)]
    counts = [c for c in counts if c]
    if not seconds or not counts:
        return None
    cell, peaks = ctx["cell"], ctx["peaks"]
    layers = costs.layer_counts(cell.model)
    if what == "ssm":
        floor_s = layers["mamba"] * costs.decode_update_floor_bytes(
            cell.model, cell.role, sum(counts)) / peaks.hbm_bytes_per_s
    else:
        shares = reference.routed_shares()      # [sparse layers, held]
        if shares is None or len(shares) != layers["experts"]:
            return None
        floor_s = sum(
            costs.bank_floor_s(cell.model, costs.call_tokens(cell.model, c),
                               peaks, layer)
            for c in counts for layer in shares)
    return 100.0 * floor_s / seconds
