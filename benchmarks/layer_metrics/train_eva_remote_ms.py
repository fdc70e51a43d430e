"""Whole train step: device self time a step of what the summaries cost —
``eva_summaries`` (the pooling), ``eva_remote`` (every query over the
summaries of all earlier windows) and ``eva_merge`` (the two softmaxes
under one normaliser), forward and backward; mean over the chips."""

from benchmarks import eva_scopes


def read(ctx):
    return eva_scopes.per_step_ms(
        ctx, "eva_summaries", "eva_remote", "eva_merge"
    )
