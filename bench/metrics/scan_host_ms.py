"""scan_host_ms: milliseconds per trace of the ``precompute_trace_scan``
span in which the device ran nothing: the host's Eq. 4 mixing matrices,
effective densities and round records, and dispatch."""


def read(ctx):
    spans = ctx.trace.spans_named("scan")
    if not spans or not ctx.trace.ops:
        return None
    total = sum(b - a for a, b in spans)
    return 1e3 * (total - ctx.trace.busy_in("scan")) / len(spans)
