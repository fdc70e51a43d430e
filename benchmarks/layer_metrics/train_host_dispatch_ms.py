"""Train loop: mean host time of one step's dispatch (the runner's span
around the call of the compiled step), over the window's steps."""


def read(ctx):
    spans = ctx["spans"].durations("dispatch")
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
