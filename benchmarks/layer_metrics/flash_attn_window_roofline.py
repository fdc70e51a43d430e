"""Kernels (``ops/pallas_attention.py``) in a stack of window and full
attention layers: the least time the chip could take for the three flash
kernels' work in the traced steps (``moe_flops.flash_attention_cost``:
grouped-query heads, the pairs a query can see alone — the window's in a
sliding layer — the forward counted once) over the device time under the
kernels' own names, in per cent."""

from benchmarks import moe_flops, scope_reduce


def read(ctx):
    reduced = scope_reduce.for_ctx(ctx)
    steps = ctx["measured"].get("steps")
    if reduced is None or not steps or ctx["peaks"] is None:
        return None
    # mean over the chips x chips: set against the global batch's work
    seconds = reduced["devices"] * sum(
        reduced["bucket_s"].get(b, 0.0) for b in scope_reduce.KERNEL_BUCKETS
    )
    if seconds <= 0:
        return None
    t = ctx["traffic"]
    cost = moe_flops.flash_attention_cost(
        ctx["config"], t["per_chip_batch"] * ctx["chips"], t["seq_len"]
    )
    least = steps * max(
        cost["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds
