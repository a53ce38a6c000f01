"""The plain mean of a number the program put on its own spans, over the
spans of that name inside the traced window (``span_arg`` reports in
percent; this is a count a span). A program whose spans do not carry the
argument reports nothing."""

import statistics

from benchmark.harness import program_spans as ps


def read(ctx, span, arg):
    program = ps.load(ctx)
    if program is None:
        return None
    values = [s.arg(arg) for s in
              ps.named(program.spans, span, ctx["trace"].window())]
    values = [v for v in values if v is not None]
    return statistics.mean(values) if values else None
