"""Serving engine: median over the requests that finished in the window
of (done - first token) / (output tokens - 1).  A per-layer reading in a
cell above the knee, where tails swing with the queue."""

import statistics


def read(ctx):
    tpot = ctx["measured"].get("tpot_ms")
    if not tpot:
        return None
    return statistics.median(tpot)
