"""Device idle share over the traced window, mean over the chips."""


def read(ctx):
    return None if ctx.trace is None else 100.0 * ctx.trace.idle_share
