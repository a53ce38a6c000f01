"""A recurrent-state op's share of its roofline, in percent, over its device
time in the traced window, for ANY state family: the metric's file names the
family's counting module under ``benchmark/harness`` (``costs``: it gives
``layers(model)``, ``decode_update_floor_bytes(model, role, rows)`` and
``chunk_floor_s(model, role, rows, peaks)``), the span argument that says
the rows ONE layer advances (``arg``) and the spans that carry it
(``spans``), so a later family brings a counting module and JSON files, and
no reader. The op's time is its own kernel's events (``kernel``: the
profiler names a Mosaic event by its HLO instruction, ``<kernel>.N``) or,
where the op is several operations, the device time under its named scope
(``scope``, one of ``known``: ``scope_share_known``).

``what`` = ``decode``: the single-token update - each live row's state is
read once and written once a layer, times the layers, over the HBM peak.
``chunk``: the chunked form - a call's floor is the larger of the
recurrence's operations over the bf16 peak and one read and one write of the
sequence's state over the HBM peak, times the layers. A program whose spans
carry no such argument, or whose trace holds no such kernel or scope,
reports nothing."""

import importlib

from benchmark.harness import program_spans as ps
from benchmark.readers.nemotron_h_roofline import kernel_seconds
from benchmark.readers.scope_share_known import seconds_by_scope


def read(ctx, what, costs, arg, spans, kernel=None, scope=None, known=()):
    program = ps.load(ctx)
    if program is None or ctx.get("peaks") is None:
        return None
    window = ctx["trace"].window()
    seconds = kernel_seconds(ctx, kernel) if kernel \
        else seconds_by_scope(program, window, known).get(scope, 0.0)
    counts = [s.arg(arg) for name in spans
              for s in ps.named(program.spans, name, window)]
    counts = [c for c in counts if c]
    if not seconds or not counts:
        return None
    costs = importlib.import_module(f"benchmark.harness.{costs}")
    cell, peaks = ctx["cell"], ctx["peaks"]
    layers = costs.layers(cell.model)
    if what == "decode":
        floor_s = layers * costs.decode_update_floor_bytes(
            cell.model, cell.role, sum(counts)) / peaks.hbm_bytes_per_s
    else:
        floor_s = layers * sum(costs.chunk_floor_s(cell.model, cell.role, c,
                                                   peaks) for c in counts)
    return 100.0 * floor_s / seconds
