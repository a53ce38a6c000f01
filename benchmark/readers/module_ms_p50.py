"""Median device time, in ms, of the runs of one jitted program inside the
traced window (the ``XLA Modules`` line), found by a pattern on its name:
``jit_decode`` is the decode step, ``jit_chunk_prefill`` one SplitFuse
chunk."""

import statistics

from benchmark.harness import trace as tr


def read(ctx, pattern):
    if ctx.get("trace") is None:
        return None
    runs = tr.module_durations(ctx["trace"], pattern)
    return statistics.median(runs) * 1e3 if runs else None
