"""The CCA mixing's share of its roofline, in percent: the least time the
chip could take for the bytes the mechanism must move
(``costs_cca.call_floor_bytes`` of every model call inside the traced window,
from the ``cca_rows`` and ``cca_tail_rows`` its span says of ONE layer, times
the layers as run, over the HBM peak) over the device time of the operations
whose innermost named scope is ``scope``. The mixing is memory-bound by two
orders (its grouped convolution is 0.4 MFLOP a row). A program whose spans
carry no such counts, or whose trace names no such scope, reports nothing.
Serve cells: one chip."""

from benchmark.harness import costs_cca
from benchmark.harness import program_spans as ps
from benchmark.readers.scope_share_known import seconds_by_scope


def read(ctx, scope, spans):
    program = ps.load(ctx)
    if program is None or not program.ops or ctx.get("peaks") is None:
        return None
    window = ctx["trace"].window()
    seconds = seconds_by_scope(program, window, (scope,)).get(scope, 0.0)
    calls = [(s.arg("cca_rows"), s.arg("cca_tail_rows")) for name in spans
             for s in ps.named(program.spans, name, window)]
    calls = [(rows, tails or 0.0) for rows, tails in calls if rows]
    if not seconds or not calls:
        return None
    model = ctx["cell"].model
    floor_s = model["num_hidden_layers"] * sum(
        costs_cca.call_floor_bytes(model, rows, tails)
        for rows, tails in calls) / ctx["peaks"].hbm_bytes_per_s
    return 100.0 * floor_s / seconds
