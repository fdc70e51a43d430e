"""Whole train step: device self time a step under the expert FFNs' five
scopes — router, dispatch, grouped products, combine, shared expert —
forward (remat's second one too) and backward; mean over the chips."""

from benchmarks import moe_scopes


def read(ctx):
    return moe_scopes.per_step_ms(ctx)
