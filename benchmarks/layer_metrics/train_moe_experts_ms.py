"""Kernels: device self time a step under ``moe_experts`` — the grouped
products over the held experts' row groups and the activation between
them, forward (remat's second one too) and backward; mean over the
chips."""

from benchmarks import moe_scopes


def read(ctx):
    return moe_scopes.per_step_ms(ctx, "moe_experts")
