"""Whole train step of an ``afmoe`` model of which this chip holds a
share: model FLOPs of the steps completed in the traced window
(``moe_flops.train_step_flops``: 3 x forward of what this chip computes —
matmul weights a token touches, routed rows at their expectation,
attention by visible pairs; nothing recomputed, so remat's second forward
lowers it) over window x chips x the chip's bf16 peak."""

from benchmarks import moe_flops


def read(ctx):
    steps = ctx["measured"].get("steps")
    if not steps or ctx["peaks"] is None:
        return None
    t = ctx["traffic"]
    per_step = moe_flops.train_step_flops(
        ctx["config"], t["per_chip_batch"] * ctx["chips"], t["seq_len"]
    )
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * per_step * steps / (ctx["window_s"] * peak)
