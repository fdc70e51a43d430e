"""Whole train step: device self time a step of what moves rows to the
held experts and back — ``moe_router`` (matmul, scores, top_k, gates),
``moe_dispatch`` (sort, group sizes, row gather) and ``moe_combine`` (the
gate-weighted scatter-add), forward and backward; mean over the chips."""

from benchmarks import moe_scopes


def read(ctx):
    return moe_scopes.per_step_ms(
        ctx, "moe_router", "moe_dispatch", "moe_combine"
    )
