"""Device idle time, as a share of the traced window in percent, that falls
inside the ``sched_tick`` spans that hold an ``engine_drain`` span - a tick
that drains pays the exposed read AND the exposed launch after it -,
averaged over the chips. No more than the device's idle share by
construction; the difference is idle time no drain explains (the window's
first launch among it: the profiler's start leaves the device idle). Nothing
on a program whose ticks carry no ``drains`` argument.

A tick is an interval on the host's clock and idle time one on the
device's, which the profiler sets a millisecond or two early by session
(``program_chain.pair_runs``). The exposed read begins where the program in
flight ends, milliseconds into its tick, and the exposed launch ends before
the tick's harvest does, so both stay inside their tick either way; the
ticks are taken as they are."""

from benchmark.harness import program_chain as pc
from benchmark.harness import program_spans as ps
from benchmark.harness import trace as tr


def read(ctx):
    program = ps.load(ctx)
    trace = ctx.get("trace")
    if program is None or not trace.devices:
        return None
    ticks = pc.drained_ticks(program.spans)
    if ticks is None:
        return None
    window = trace.window()
    ticks = tr.union(tr.clip(ticks, window))
    shares = []
    for ops in trace.devices.values():
        idle = tr.gaps(tr.busy_intervals(ops, window), window)
        shares.append(tr.total(tr.intersect(idle, ticks))
                      / (window[1] - window[0]))
    return 100.0 * sum(shares) / len(shares)
