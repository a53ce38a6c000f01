"""Submit to first token, median in ms over every request that got its
first token inside the measured window. Recorded; judges nothing (a window
sees some tens of such requests)."""

from benchmark.harness import stats


def read(ctx):
    ttft = ctx["series"].get("ttft_s")
    return stats.percentile(ttft, 50) * 1e3 if ttft else None
