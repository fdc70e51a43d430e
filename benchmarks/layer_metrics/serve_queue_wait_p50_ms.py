"""Serving engine (scheduler): median wait between the time a request was
due and its admission to a slot (``Request.admit_s - arrival_s``), over
the requests admitted in the window."""

import statistics


def read(ctx):
    waits = ctx["measured"].get("queue_wait_ms")
    if not waits:
        return None
    return statistics.median(waits)
