"""Whole train step: device self time a step of everything under
``/mlp/``; mean over the chips."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(ctx, "bucket_s", "mlp")
