"""Kernels (``ops/ssd.py``): least time the chip could take for the
scans' work in the traced steps (``hybrid_flops.scan_cost``, from shapes:
the larger of FLOPs over the bf16 peak and bytes over the HBM peak) over
the device time under the ``ssd`` scope, in per cent.  The count does not
follow the implementation, so it reads the same work whatever later
computes the scan."""

from benchmarks import hybrid_flops, mixer_scopes


def read(ctx):
    steps = ctx["measured"].get("steps")
    if not steps or ctx["peaks"] is None:
        return None
    # mean over the chips x chips: set against the global batch's work
    seconds = mixer_scopes.seconds(ctx, "ssd")
    if seconds is None:
        return None
    t = ctx["traffic"]
    cost = hybrid_flops.scan_cost(
        ctx["config"], t["per_chip_batch"] * ctx["chips"], t["seq_len"]
    )
    least = steps * max(
        cost["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / (ctx["chips"] * seconds)
