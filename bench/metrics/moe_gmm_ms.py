"""moe_gmm_ms: device milliseconds per D-PSGD round in the grouped expert
matmul's operations (the kernels named ``moe_gmm``: forward, and the
``moe_gmm_dlhs`` / ``moe_gmm_drhs`` backward products), averaged over the
chips, inside the ``train`` spans of the traced window."""
import numpy as np

KERNEL = "moe_gmm"


def kernel_s(trace) -> float:
    """Device seconds in the kernel's operations inside the ``train``
    spans, averaged over the chips."""
    spans = trace.spans_named("train")
    total = 0.0
    for d in trace.ops.values():
        mine = np.array([k.startswith(KERNEL) for k in d.kinds], bool)
        if not mine.any():
            continue
        sel = mine[d.kind_ids] & ~d.cont
        for a, b in spans:
            total += float(np.clip(np.minimum(d.ends[sel], b)
                                   - np.maximum(d.starts[sel], a), 0.0, None).sum())
    return total / max(len(trace.ops), 1)


def read(ctx):
    calls = ctx.trace.spans_named("train")
    t = kernel_s(ctx.trace)
    if not calls or t <= 0:
        return None
    return 1e3 * t / (len(calls) * ctx.info["rounds_per_call"])
