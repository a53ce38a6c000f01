"""Median, in ms, over the decode-shaped launches of the traced window, of
how long a launched program waited in the device's queue - which is how far
ahead of the device the host runs: the start of the program's run on the
device less the end of its ``engine_dispatch``, over and above what the
launches that found the device idle read there (the median of theirs, the
whole trace's). About the program's time less the host's need where every
tick launches ahead; about zero in a tick that drained first.

A BUDGET, not a goodness: what a ``perf_opt`` may take out of the device
program before the host is exposed again. A change that shortens the
program spends it, and the number falls with the rate RISING; it is
``better: higher`` only against a host that got slower under the same
program.

The launch spans (``spans``) and the runs of their module (``pattern``, on
the ``XLA Modules`` line) are joined through ``seq`` and order
(``program_chain.pair_runs``); where that join is not certain, the program
numbers no launch, or no launch of the trace found the device idle (the
profiler's start empties the pipeline, so a session's first launch does),
the reader reports nothing. A run's start is on the device's clock and a
dispatch's end on the host's, which the profiler aligns to a millisecond
or two by session: the skew is the same in every lead and drops out of the
difference, and with it the way to an idle device (0.6-1.2 ms on the chip).
What is left is the spread of the idle launches' own leads, +-0.15 ms."""

import statistics

from benchmark.harness import program_chain as pc
from benchmark.harness import program_spans as ps


def read(ctx, spans, pattern, slack_ms=4.0, idle_gap_ms=0.1):
    program = ps.load(ctx)
    if program is None:
        return None
    trace = ctx["trace"]
    leads = pc.queue_leads(program.spans, trace, spans, pattern, slack_ms,
                           idle_gap_ms)
    idle = [lead for _, _, lead, was_idle in leads or () if was_idle]
    lo, hi = trace.window()
    inside = [lead for _, launch, lead, _ in leads or ()
              if launch.start >= lo and launch.end <= hi]
    if not idle or not inside:
        return None
    return statistics.median(inside) - statistics.median(idle)
