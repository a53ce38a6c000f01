"""A number the program put on its own spans (the ``sched_tick`` span
carries what the tick did), over the spans of that name inside the traced
window: ``mean`` of the argument - divided, where ``per`` names a key path
into the cell's role, by that setting (decode sequences per slot) - or
``share_positive``, the share of spans whose argument is above zero. In
percent."""

import statistics

from benchmark.harness import program_spans as ps


def read(ctx, span, arg, how="mean", per=None):
    program = ps.load(ctx)
    if program is None:
        return None
    values = [s.arg(arg) for s in
              ps.named(program.spans, span, ctx["trace"].window())]
    values = [v for v in values if v is not None]
    if not values:
        return None
    if how == "share_positive":
        return 100.0 * sum(v > 0 for v in values) / len(values)
    setting = ctx["cell"].role
    for key in per or ():
        setting = setting[key]
    return 100.0 * statistics.mean(values) / (setting if per else 1.0)
