"""device_idle_pct.chan: share of the traced window in which the chip ran
no operation, in the channel-plane cells."""


def read(ctx):
    if not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s())
