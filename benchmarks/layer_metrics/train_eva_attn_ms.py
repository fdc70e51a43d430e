"""Whole train step: device self time a step under chunk-summarised
attention's four scopes — the block-local part with its flash kernels,
the pooling, the attention over the summaries, the merge — forward
(remat's second one too) and backward; mean over the chips.  The
projections and the rotation round them are not in it."""

from benchmarks import eva_scopes


def read(ctx):
    return eva_scopes.per_step_ms(ctx)
