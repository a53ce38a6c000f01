"""Bytes a decode step must read (weights touched + live keys and values,
from shapes: ``costs.decode_step_bytes``) over the HBM peak, as a share of
the median device time of the decode program, in percent: how close decode
is to the memory roofline. The batch and the live context are the means
over the measured window's ticks that decoded."""

import statistics

from benchmark.harness import costs
from benchmark.harness import trace as tr


def read(ctx, pattern="jit_decode"):
    if ctx.get("trace") is None or ctx.get("peaks") is None:
        return None
    runs = tr.module_durations(ctx["trace"], pattern)
    series = ctx["series"]
    start, end = ctx["window"]
    ticks = [i for i in range(start + 1, end + 1) if series["decoding"][i]]
    if not runs or not ticks:
        return None
    batch = statistics.mean(series["decoding"][i] for i in ticks)
    kv = statistics.mean(series["kv_tokens"][i] for i in ticks)
    floor_s = costs.decode_step_bytes(ctx["cell"].model, round(batch), kv) \
        / ctx["peaks"].hbm_bytes_per_s
    return 100.0 * floor_s / statistics.median(runs)
