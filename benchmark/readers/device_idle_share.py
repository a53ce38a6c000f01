"""1 - union of the device's operation intervals over the traced window, in
percent, averaged over the chips."""

from benchmark.harness import trace as tr


def read(ctx):
    if ctx.get("trace") is None:
        return None
    return 100.0 * tr.idle_share(ctx["trace"])
