"""Whole train step: device self time a step of the output projection
(with the tied embedding's gradient) and the cross entropy — the scopes
``head`` and ``loss``; mean over the chips."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(ctx, "bucket_s", "head", "loss")
