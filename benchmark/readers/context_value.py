"""A number the traffic kind already took over the measured window (a
count, or a rate over whole units), by its key in the run's context."""


def read(ctx, key, scale=1.0):
    value = ctx.get(key)
    return None if value is None else value * scale
