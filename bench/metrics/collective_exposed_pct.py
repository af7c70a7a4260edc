"""collective_exposed_pct: share of the collectives' device time in which no
other operation runs on the same chip."""


def read(ctx):
    coll = ctx.trace.collective_s()
    if coll <= 0:
        return None
    return 100.0 * ctx.trace.collective_exposed_s() / coll
