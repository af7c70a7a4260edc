"""train_device_ms: device milliseconds per ``train_model_on_traces`` call
(averaged over the chips), from the profiler trace inside the ``train``
spans."""


def read(ctx):
    spans = ctx.trace.spans_named("train")
    busy = ctx.trace.busy_in("train")
    if not spans or busy <= 0:
        return None
    return 1e3 * busy / len(spans)
