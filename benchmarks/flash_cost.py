"""The three flash kernels' work, one kernel at a time: the split of
``flops.flash_attention_cost`` that its docstring states, and the share
of its roofline that one kernel reaches in a traced run."""

from __future__ import annotations

from benchmarks import flops, scope_reduce

#: kernel -> (causal S x S x D products a head, tensors read or written
#: once, float32 row statistics): forward QK^T, PV; writes lse.  dq
#: recomputes S, dP, dQ; reads lse and delta.  dk/dv recomputes S, dP, dV,
#: dK; reads lse and delta.
PARTS = {
    "fwd": (2, 4, 1),
    "dq": (3, 6, 2),
    "dkv": (4, 7, 2),
}


def flash_kernel_costs(config: dict, batch: int, seq_len: int,
                       dtype_bytes: int = 2) -> dict:
    """``{kernel: {"flops", "bytes"}}`` of one train step, all layers; the
    three sum to ``flops.flash_attention_cost`` exactly."""
    z = flops.sizes(config)
    per_product = 2 * seq_len * seq_len * z["D"] * (seq_len + 1) / (2 * seq_len)
    tensor = batch * seq_len * z["H"] * z["D"] * dtype_bytes
    stats = batch * seq_len * z["H"] * 4
    return {
        kernel: {
            "flops": z["L"] * batch * z["H"] * products * per_product,
            "bytes": z["L"] * (tensors * tensor + rows * stats),
        }
        for kernel, (products, tensors, rows) in PARTS.items()
    }


def roofline(ctx, kernel: str):
    """Least time the chip could take for ``kernel``'s work in the traced
    steps (the larger of FLOPs over the bf16 peak and bytes over the HBM
    peak) over the kernel's device time, in per cent; None where the trace
    does not show the kernel under its own name."""
    reduced = scope_reduce.for_ctx(ctx)
    steps = ctx["measured"].get("steps")
    if reduced is None or not steps or ctx["peaks"] is None:
        return None
    # mean over the chips x chips: set against the global batch's work
    seconds = reduced["devices"] * reduced["bucket_s"].get(
        "attn_kernel." + kernel, 0.0
    )
    if seconds <= 0:
        return None
    t = ctx["traffic"]
    cost = flash_kernel_costs(
        ctx["config"], t["per_chip_batch"] * ctx["chips"], t["seq_len"]
    )[kernel]
    least = steps * max(
        cost["flops"] / ctx["peaks"]["bf16_flops_per_s"],
        cost["bytes"] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least / seconds
