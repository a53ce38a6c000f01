"""A memory-bound kernel's share of the HBM roofline, in percent: the keys
and values the kernel named ``kernel`` must read - the ``kv_tokens`` of
every ``sched_tick`` inside the traced window (the context the tick's decode
batch attends over) times ``costs.kv_bytes_per_token`` (K and V, every layer,
from the configuration as served) - over the HBM peak, over the device time
of the kernel's events in the window. The profiler names a Mosaic event by
its HLO instruction, ``<kernel>.N``."""

import re

from benchmark.harness import costs
from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr


def read(ctx, kernel, span="sched_tick", arg="kv_tokens"):
    program = ps.load(ctx)
    if program is None or ctx.get("peaks") is None:
        return None
    trace = ctx["trace"]
    window = trace.window()
    mine = re.compile(rf"^{re.escape(kernel)}(\.\d+)?$")
    ops = next(iter(trace.devices.values()), [])   # serve cells: one chip
    seconds = tr.total(tr.busy_intervals(
        ops, window, lambda o: bool(mine.match(o.name)))) / 1e9
    tokens = [s.arg(arg) for s in ps.named(program.spans, span, window)]
    tokens = [t for t in tokens if t is not None]
    if not seconds or not tokens:
        return None
    floor_s = sum(tokens) * costs.kv_bytes_per_token(ctx["cell"].model) \
        / ctx["peaks"].hbm_bytes_per_s
    return 100.0 * floor_s / seconds
