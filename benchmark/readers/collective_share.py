"""Time in all-gather / all-reduce / reduce-scatter (and the other
collectives) over the traced window, in percent; with ``exposed`` only the
part during which no other operation runs on that device."""

from benchmark.harness import trace as tr


def read(ctx, exposed=False):
    if ctx.get("trace") is None or ctx["cell"].chips < 2:
        return None
    share, bare = tr.collective_shares(ctx["trace"])
    return 100.0 * (bare if exposed else share)
