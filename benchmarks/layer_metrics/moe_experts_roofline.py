"""Kernels (``ops/moe.grouped_matmul``): least time the chip could take
for the expert layers' grouped products in the traced steps
(``moe_flops.experts_cost``, from shapes, rows at their expectation: the
larger of FLOPs over the bf16 peak and bytes over the HBM peak) over the
device time under the ``moe_experts`` scope, in per cent.  The count does
not follow the implementation, so it reads the same work whatever later
computes the products; remat's second forward is time without work."""

from benchmarks import moe_flops, moe_scopes


def read(ctx):
    steps = ctx["measured"].get("steps")
    if not steps or ctx["peaks"] is None:
        return None
    # mean over the chips x chips: set against the global batch's work
    seconds = moe_scopes.seconds(ctx, "moe_experts")
    if seconds is None:
        return None
    t = ctx["traffic"]
    cost = moe_flops.experts_cost(
        ctx["config"], t["per_chip_batch"] * ctx["chips"], t["seq_len"]
    )
    least = steps * max(
        cost["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / (ctx["chips"] * seconds)
