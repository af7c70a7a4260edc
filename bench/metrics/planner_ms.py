"""planner_ms: host milliseconds per plan (Algorithm 2, run by the
``WirelessSimulator`` constructor), from the benchmark's ``plan`` spans in
the traced window."""


def read(ctx):
    spans = ctx.trace.spans_named("plan")
    if not spans:
        return None
    return 1e3 * sum(b - a for a, b in spans) / len(spans)
