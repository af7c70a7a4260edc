"""device_idle_pct.train: share of the traced window in which a chip ran no
operation, averaged over the chips, in the training cells."""


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s())
