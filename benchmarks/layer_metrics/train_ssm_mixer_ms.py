"""Whole train step: device self time a step of everything under
``/mamba/`` — the mixer's five parts and what lies between them, forward
(remat's second one too) and backward; mean over the chips."""

from benchmarks import mixer_scopes


def read(ctx):
    return mixer_scopes.per_step_ms(ctx)
