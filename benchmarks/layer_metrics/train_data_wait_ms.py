"""Train loop: mean time a step waited for its batch (the runner's span
around ``next(loader)``: host gather plus placement on the mesh)."""


def read(ctx):
    spans = ctx["spans"].durations("data_wait")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
