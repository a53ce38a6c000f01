"""Median over the traced window's ``sched_tick`` spans, in ms, of what the
HOST did in a tick: the span's duration less the ``engine_wait`` time under
it (blocked on the device is not work) or, with ``group``, one part of that -
the self time of the tick's spans of ``program_chain.HOST_GROUPS[group]``
(``dispatch``: ``engine_prep`` + ``engine_dispatch``; ``emit``:
``engine_emit`` + ``sched_harvest`` + ``sched_retire``; ``admit``:
``sched_expire`` + ``sched_admit`` + ``sched_preempt_guard``). Since PR 35
the device runs under the whole tick, so the benchmark's ``tick`` span less
the device time inside it (``sched_host_ms_p50``) no longer says this."""

import statistics

from benchmark.harness import program_chain as pc
from benchmark.harness import program_spans as ps


def read(ctx, group=None):
    program = ps.load(ctx)
    if program is None:
        return None
    rows = pc.host_ms_by_tick(program.spans, ctx["trace"].window())
    if not rows:
        return None
    return statistics.median(row[group or "busy"] for row in rows)
