"""Whole train step: device self time a step of the operations with
``transpose(`` in their scope (the backward pass); mean over the chips."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(ctx, "phase_s", "bwd")
