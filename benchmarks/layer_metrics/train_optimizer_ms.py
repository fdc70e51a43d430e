"""Step factory: device self time a step of the operations that the trace
names under the scopes ``optimizer`` and ``grad_clip``; mean over the
chips.  A fusion counts under its root: where XLA fuses a weight's AdamW
update into the matmul that makes its gradient (on a v5e it does, for all
six matrices of a block, PERF.md section 5), that part of the update is
read under the matmul's scope, not here."""

from benchmarks import scope_reduce


def read(ctx):
    return scope_reduce.per_step_ms(ctx, "bucket_s", "optimizer", "grad_clip")
