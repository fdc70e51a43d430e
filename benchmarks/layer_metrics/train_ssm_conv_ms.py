"""Kernels (``ops/causal_conv.py``): device self time a step under the
mixer's ``ssm_conv`` scope — the causal convolution, bias and SiLU alone,
forward (remat's second one too) and backward; mean over the chips."""

from benchmarks import mixer_scopes


def read(ctx):
    return mixer_scopes.per_step_ms(ctx, "ssm_conv")
