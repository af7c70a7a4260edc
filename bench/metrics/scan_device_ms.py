"""scan_device_ms: device milliseconds per trace inside the
``precompute_trace_scan`` span, from the profiler trace."""


def read(ctx):
    spans = ctx.trace.spans_named("scan")
    busy = ctx.trace.busy_in("scan")
    if not spans or busy <= 0:
        return None
    return 1e3 * busy / len(spans)
