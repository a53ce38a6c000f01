"""Device idle time, as a share of the traced window in percent, that falls
under one group of the program's serving spans (``program_spans.IDLE_GROUPS``:
``admit``, ``dispatch``, ``emit``), averaged over the chips. The group
``unattributed`` is the idle time under none of them: the benchmark's own
``submit`` / ``harvest`` and what the spans miss. The four shares add up to
the device's idle share."""

from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr


def read(ctx, group):
    program = ps.load(ctx)
    trace = ctx.get("trace")
    if program is None or not trace.devices:
        return None
    window = trace.window()
    shares = []
    for ops in trace.devices.values():
        idle = tr.gaps(tr.busy_intervals(ops, window), window)
        split = ps.split_idle(idle, program.spans)
        shares.append(split[group] / (window[1] - window[0]))
    return 100.0 * sum(shares) / len(shares)
