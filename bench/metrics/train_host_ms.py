"""train_host_ms: milliseconds per ``train_model_on_traces`` call in which
the chips ran nothing: batch building, init and replication, eval,
compaction and dispatch on the host."""


def read(ctx):
    spans = ctx.trace.spans_named("train")
    if not spans or not ctx.trace.ops:
        return None
    total = sum(b - a for a, b in spans)
    return 1e3 * (total - ctx.trace.busy_in("train")) / len(spans)
