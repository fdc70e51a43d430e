"""Device: share of the busy time of the traced window whose operations
carry a program scope that falls in a bucket other than ``other`` (mean
over the chips)."""

from benchmarks import scope_reduce


def read(ctx):
    reduced = scope_reduce.for_ctx(ctx)
    if reduced is None or reduced["busy_s"] <= 0:
        return None
    other = reduced["bucket_s"].get(scope_reduce.OTHER, 0.0)
    return 100.0 * (1.0 - other / reduced["busy_s"])
