"""Rows the expert banks computed that no token was routed to, as a share of
all the rows they computed, in percent: 1 - routed / computed over the model
calls inside the traced window. Both counts are shape facts the program puts
on each call's span (``moe_rows_routed`` = tokens x experts per token,
``moe_rows_computed`` = experts x capacity, for one layer: the ratio is the
same over all of them). A dense family's spans, and the spans of a program
from before these arguments, carry neither: nothing is reported."""

from benchmark.harness import program_spans as ps


def calls(ctx, spans):
    """``(routed, computed)`` rows of each call span named in ``spans`` that
    lies inside the traced window and carries both counts."""
    program = ps.load(ctx)
    if program is None:
        return []
    window = ctx["trace"].window()
    out = []
    for name in spans:
        for s in ps.named(program.spans, name, window):
            routed, computed = s.arg("moe_rows_routed"), \
                s.arg("moe_rows_computed")
            if routed is not None and computed:
                out.append((routed, computed))
    return out


def read(ctx, spans):
    rows = calls(ctx, spans)
    if not rows:
        return None
    return 100.0 * (1.0 - sum(r for r, _ in rows) / sum(c for _, c in rows))
