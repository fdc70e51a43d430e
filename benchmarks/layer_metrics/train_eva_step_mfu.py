"""Whole train step of an ``evabyte`` model: model FLOPs of the steps
completed in the traced window (``eva_flops.train_step_flops``: 3 x
forward — matmul weights a byte touches, the head's eight, attention by
the pairs a query sees, local and summarised, and the pooling; nothing
recomputed, so remat's second forward lowers it) over window x chips x
the chip's bf16 peak."""

from benchmarks import eva_flops


def read(ctx):
    steps = ctx["measured"].get("steps")
    if not steps or ctx["peaks"] is None:
        return None
    t = ctx["traffic"]
    per_step = eva_flops.train_step_flops(
        ctx["config"], t["per_chip_batch"] * ctx["chips"], t["seq_len"]
    )
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * per_step * steps / (ctx["window_s"] * peak)
