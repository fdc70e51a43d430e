"""Whole train step: device self time a step of everything under
``/attn/`` — projections, the three flash kernels, the copies round them;
mean over the chips."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(ctx, "bucket_s", *scope_reduce.ATTN_BUCKETS)
