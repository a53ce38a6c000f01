"""Median over the traced ticks of the benchmark's own ``tick`` span minus
the device time inside it, in ms: what the scheduler and the engine's host
code cost a tick."""

import statistics

from benchmark.harness import trace as tr


def read(ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    lo, hi = trace.window()
    ticks = [(a, b) for name, a, b in trace.host
             if name == "tick" and a >= lo and b <= hi]
    if not ticks:
        return None
    busy = tr.device_seconds_within(trace, ticks)
    return statistics.median(((b - a) / 1e9 - d) * 1e3
                             for (a, b), d in zip(ticks, busy))
