"""Kernels (``ops/ssd.py``): device self time a step under the mixer's
``ssd`` scope — the state-space scan alone, forward (remat's second one
too) and backward; mean over the chips."""

from benchmarks import mixer_scopes


def read(ctx):
    return mixer_scopes.per_step_ms(ctx, "ssd")
