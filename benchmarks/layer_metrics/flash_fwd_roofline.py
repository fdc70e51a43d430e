"""Kernels (``ops/pallas_attention.py``): as ``flash_attn_roofline``, for
the ``flash_fwd`` kernel alone."""

from benchmarks import flash_cost


def read(ctx):
    return flash_cost.roofline(ctx, "fwd")
