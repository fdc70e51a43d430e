"""Whole train step: device self time a step of the forward that remat
runs a second time inside the backward — every operation whose scope
holds ``rematted_computation``; mean over the chips.  It is a part of
``train_bwd_ms``; a remat policy that keeps more should lower both by the
same amount.  Silent in a step without remat."""

from benchmarks import remat_scopes


def read(ctx):
    return remat_scopes.per_step_ms(ctx)
