"""Step factory: share of the traced window in which a collective ran on
a device while no other operation did (mean over the chips).  Silent
where the trace holds no collective."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or trace["collective_s"] <= 0:
        return None
    return 100.0 * trace["exposed_collective_s"] / trace["window_s"]
