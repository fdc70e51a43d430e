"""Train loop: mean host time of the jitted step's call as JAX itself
times it from inside (its ``PjitFunction(...)`` host event), over the
calls in the traced window."""

from benchmarks import scope_reduce


def read(ctx):
    reduced = scope_reduce.for_ctx(ctx)
    call = reduced and reduced["step_call"]
    if not call:
        return None
    return 1e3 * call["mean_s"]
