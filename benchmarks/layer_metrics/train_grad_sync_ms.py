"""Step factory: device self time a step of the gradient exchange —
bucket packing, the collective and unpacking, the scope ``grad_sync``;
mean over the chips.  Silent on one chip, where the exchange compiles to
nothing."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(ctx, "bucket_s", "grad_sync")
