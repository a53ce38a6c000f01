"""Median, in ms, over the spans of one name inside the traced window, of
the span's duration less its children of the names in ``minus``:
``train_step`` less ``train_sync`` is the host time of a training step that
the drain at its end cannot overlap."""

import statistics

from benchmark.harness import program_spans as ps


def read(ctx, span, minus=()):
    program = ps.load(ctx)
    if program is None:
        return None
    window = ctx["trace"].window()
    values = []
    for i, s in enumerate(program.spans):
        if s.name == span and s.start >= window[0] and s.end <= window[1]:
            less = sum(c.end - c.start for c in ps.children(program.spans, i)
                       if c.name in minus)
            values.append((s.end - s.start - less) / 1e6)
    return statistics.median(values) if values else None
