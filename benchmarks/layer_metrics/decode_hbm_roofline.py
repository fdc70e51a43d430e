"""Kernels (the decode program, ``serving/kv_cache.py``): the least bytes
the traced decode steps need — every weight once a step, plus the keys
and values of the REAL contexts of the active slots — over the HBM peak,
against the device time of the decode program in the trace.  The same
work whatever implements it: a program that copies 1024 positions a slot
reads low here, one that reads only what is live reads high."""

from benchmarks import flops

#: the decode program's name on the trace's ``XLA Modules`` line
PROGRAM = "decode_program"


def read(ctx):
    trace = ctx["trace"]
    steps = ctx["measured"].get("steps_detail")
    if trace is None or not steps or ctx["peaks"] is None:
        return None
    seconds = sum(
        s for name, (_, s) in trace["module_s"].items() if PROGRAM in name
    )
    if seconds <= 0:
        return None
    least_bytes = sum(
        flops.decode_step_bytes(ctx["config"], [c + 1 for c in s["contexts"]])
        for s in steps if s["contexts"]
    )
    return 100.0 * least_bytes / ctx["peaks"]["hbm_bytes_per_s"] / seconds
