"""Whole train step: device self time a step of the operations that have
a scope without ``transpose(`` in it, outside the update (exchange, clip,
optimizer); mean over the chips.  An operation with no scope at all is
neither forward nor backward (``scope_reduce.UNSCOPED``)."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(ctx, "phase_s", "fwd")
