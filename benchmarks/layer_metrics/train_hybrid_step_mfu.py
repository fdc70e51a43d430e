"""Whole train step of a ``layer_types`` model: model FLOPs of the steps
completed in the traced window (``hybrid_flops.train_step_flops``: 3 x
forward from the configuration's shapes — matmul weights, the scan's four
products, attention counted causal; nothing recomputed, so remat's second
forward lowers it) over window x chips x the chip's bf16 peak."""

from benchmarks import hybrid_flops


def read(ctx):
    steps = ctx["measured"].get("steps")
    if not steps or ctx["peaks"] is None:
        return None
    t = ctx["traffic"]
    per_step = hybrid_flops.train_step_flops(
        ctx["config"], t["per_chip_batch"] * ctx["chips"], t["seq_len"]
    )
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * per_step * steps / (ctx["window_s"] * peak)
