"""moe_gmm_roofline_pct: the grouped expert matmul's share of its roofline:
the least time its products could take in a round, the larger of FLOPs over
the bf16 peak and bytes over HBM bandwidth (``bench/flops_moe.py``, for the
expected routed pairs of every node, ``bench/peaks.json``), over its device
time in a round (``moe_gmm_ms``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import flops_moe  # noqa: E402
from metrics.moe_gmm_ms import read as moe_gmm_ms  # noqa: E402


def read(ctx):
    ms = moe_gmm_ms(ctx)
    if ms is None or not ctx.peaks:
        return None
    flops, bytes_ = flops_moe.gmm_flops_bytes(
        ctx.config, ctx.info["tokens_per_node_round"])
    nodes_per_chip = ctx.info["nodes"] / len(ctx.devices)
    least_s = nodes_per_chip * max(flops / ctx.peaks["bf16_flops"],
                                   bytes_ / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
