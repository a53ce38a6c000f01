"""A kernel of the learned selection's share of its roofline, in percent:
the least time the chip could take for ``what`` (``index``: the index scores;
``attn``: attention over the selected tokens - ``costs_sparse``) of every
model call inside the traced window (``sparse_calls``), times the layers of
the configuration as it is run, over the device time of the events of the
kernels named in ``kernels`` (the profiler names a Mosaic event by its HLO
instruction, ``<kernel>.N``). The floors count what the model needs, so a
kernel that walks the whole context and masks reads low. A program whose
spans carry no selection, or whose trace holds no such kernel, reports
nothing. Serve cells: one chip."""

import re

from benchmark.harness import costs_sparse
from benchmark.harness import trace as tr
from benchmark.readers import sparse_calls


def read(ctx, what, kernels):
    calls = sparse_calls.calls(ctx)
    if not calls or ctx.get("peaks") is None:
        return None
    trace = ctx["trace"]
    window = trace.window()
    mine = re.compile(
        rf"^({'|'.join(re.escape(k) for k in kernels)})(\.\d+)?$")
    ops = next(iter(trace.devices.values()), [])
    seconds = tr.total(tr.busy_intervals(
        ops, window, lambda o: bool(mine.match(o.name)))) / 1e9
    if not seconds:
        return None
    model, peaks = ctx["cell"].model, ctx["peaks"]
    if what == "index":
        floor = sum(costs_sparse.index_floor_s(
            model, c["ctx_scored"], c["keys_read"], peaks) for c in calls)
    else:
        floor = sum(costs_sparse.attn_floor_s(
            model, c["kv_selected"], c["kv_read"], peaks) for c in calls)
    return 100.0 * model["num_hidden_layers"] * floor / seconds
