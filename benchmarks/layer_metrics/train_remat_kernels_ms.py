"""Whole train step: the part of ``train_remat_ms`` spent in the Pallas
forward kernels' second launches (``flash_fwd``, ``ssd_fwd``,
``conv_fwd``, ``moe_gmm`` custom calls under ``rematted_computation``);
mean over the chips.  Custom calls are fused with nothing, so it is
exact.  Silent in a step without remat or without those kernels."""

from benchmarks import remat_scopes


def read(ctx):
    return remat_scopes.per_step_ms(ctx, kernels=True)
