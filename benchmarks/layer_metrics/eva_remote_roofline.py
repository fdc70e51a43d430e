"""Kernels (``ops.eva.remote_attention``): the least time the chip could
take for the attention over the summaries in the traced steps
(``eva_flops.remote_cost``, from shapes: the (query, summary) pairs' two
products x 3, the operands and their gradients once each — the larger of
FLOPs over the bf16 peak and bytes over the HBM peak) over the device
time under the ``eva_remote`` scope, in per cent.  The count does not
follow the implementation, so it reads the same work whatever later
computes the op; the kernels' recomputed scores and remat's second
forward are time without work."""

from benchmarks import eva_flops, eva_scopes


def read(ctx):
    t = ctx["traffic"]
    return eva_scopes.roofline_share(ctx, eva_flops.remote_cost(
        ctx["config"], t["per_chip_batch"] * ctx["chips"], t["seq_len"]
    ), "eva_remote")
