"""collective_ms: device milliseconds per D-PSGD round in collective
operations (all-gather, reduce-scatter, all-reduce, collective-permute,
all-to-all and their async forms), averaged over the chips, inside the
``train`` spans of the traced window."""


def read(ctx):
    calls = ctx.trace.spans_named("train")
    coll = ctx.trace.collective_in("train")
    if not calls or coll <= 0:
        return None
    return 1e3 * coll / (len(calls) * ctx.info["rounds_per_call"])
