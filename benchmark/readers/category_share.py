"""Device time in operations of one category (``mosaic``: Pallas kernels
Mosaic compiled, ``tpu_custom_call``) over device busy time, in percent."""

from benchmark.harness import trace as tr


def read(ctx, category):
    if ctx.get("trace") is None:
        return None
    return 100.0 * tr.category_share_of_busy(ctx["trace"], category)
