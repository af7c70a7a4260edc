"""train_mfu_pct.moe: the training step's share of the chips' bf16 peak in
the expert-parallel cells: forward and backward matmul FLOPs per token of
what one chip computes (``bench/flops_moe.py``: dense layer, MLA, router,
shared experts, expected held-expert pairs, head slice) times the tokens
trained per second in the traced window, over chips times peak."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import flops_moe  # noqa: E402


def read(ctx):
    if not ctx.peaks or ctx.window_s <= 0 or "router_width" not in ctx.config:
        return None
    per_token = flops_moe.chip_train_flops_per_token(
        ctx.config, ctx.traffic["seq_len"])
    tokens_per_s = ctx.work * ctx.info["tokens_per_node_round"] / ctx.window_s
    peak = len(ctx.devices) * ctx.peaks["bf16_flops"]
    return 100.0 * per_token * tokens_per_s / peak
