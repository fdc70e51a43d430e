"""Kernels (``ops/pallas_attention.py`` under ``eva_local``): the least
time the chip could take for the three flash kernels' work on the
block-local part in the traced steps (``eva_flops.local_cost``: causal
pairs inside each window, every head and layer, the forward counted once
— the larger of FLOPs over the bf16 peak and bytes over the HBM peak)
over the device time of those kernels under that scope, in per cent;
remat's second forward is time without work."""

from benchmarks import eva_flops, eva_scopes


def read(ctx):
    t = ctx["traffic"]
    return eva_scopes.roofline_share(ctx, eva_flops.local_cost(
        ctx["config"], t["per_chip_batch"] * ctx["chips"], t["seq_len"]
    ), "eva_local.kernels")
