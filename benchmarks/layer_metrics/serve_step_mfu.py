"""Whole serving step: model FLOPs of the prompt tokens prefilled and the
tokens decoded in the traced window — 2 x matmul weights a token, plus
attention over the real context lengths — over window x the chip's bf16
peak."""

from benchmarks import flops


def read(ctx):
    steps = ctx["measured"].get("steps_detail")
    if not steps or ctx["peaks"] is None:
        return None
    chunks = [c for s in steps for c in s["chunks"]]
    contexts = [c for s in steps for c in s["contexts"]]
    work = flops.serve_flops(ctx["config"], chunks, contexts)
    return 100.0 * work / (ctx["window_s"] * ctx["peaks"]["bf16_flops_per_s"])
