"""Kernels (``ops/pallas_attention.py``): the least time the chip could
take for the three flash kernels' work in the traced steps — the larger
of FLOPs over the bf16 peak and bytes over the HBM peak, both from the
shapes — over the kernels' summed device time in the trace."""

from benchmarks import flops, trace_reduce

#: how the Pallas kernels' operations are found in the trace (PR 24, by
#: hand): the package gives them no ``name=``; they are the train step's
#: only custom calls (``attn.<n> custom-call``, 3 a layer)
KERNEL_NAMES = ("custom-call",)


def read(ctx):
    trace = ctx["trace"]
    steps = ctx["measured"].get("steps")
    if trace is None or not steps or ctx["peaks"] is None:
        return None
    seconds = trace_reduce.op_seconds(trace, *KERNEL_NAMES)
    if seconds <= 0:
        return None
    t = ctx["traffic"]
    cost = flops.flash_attention_cost(
        ctx["config"], t["per_chip_batch"] * ctx["chips"], t["seq_len"]
    )
    least = steps * max(
        cost["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    # op_seconds sums over the devices; so does the global batch's work
    return 100.0 * least / seconds
