#!/usr/bin/env python3
"""``readings.py`` for the hybrid training cell: the numbers its limits
are set from, on the chip at the cell's own size, several seeds in one
process:

    python3 benchmarks/readings_hybrid.py --seeds 1,2,3 [--control-seeds 1] [--fault-seeds 1]

For every seed the program's numbers against the plain reference (the
lower readings).  For the first ``--control-seeds`` seeds the control:
the reference put in the program's place and computed in fp8.  For the
first ``--fault-seeds`` seeds each of ``FAULTS``: the program itself with
one term of the model left out, one multiplier wrong or half of the
batch left out of the loss, compiled anew,
driven through the same steps on the same rows and read against the same
reference.  One JSON line per seed on stdout.  The benchmark's own runs
never run this; the CPU tests run ``FAULTS`` at a tiny size.
"""

import argparse
import contextlib
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "granite-4.0-h-micro.train-s4096"


@contextlib.contextmanager
def _no_carry():
    """The chunk-to-chunk state left out: every chunk starts from nought."""
    import jax.numpy as jnp

    from distributeddataparallel_tpu.ops import ssd

    with mock.patch.object(
        ssd, "_carry_states", lambda states, decay: jnp.zeros_like(states)
    ):
        yield {}


@contextlib.contextmanager
def _no_skip():
    """``D * x`` left out of the scan's output."""
    from distributeddataparallel_tpu.ops import ssd

    real = ssd.ssd_chunked
    with mock.patch.object(
        ssd, "ssd_chunked",
        lambda x, dt, A, B, C, D=None, *, chunk: real(
            x, dt, A, B, C, None, chunk=chunk
        ),
    ):
        yield {}


@contextlib.contextmanager
def _no_gate():
    """``silu(z)`` left out of the mixer's gated norm."""
    from distributeddataparallel_tpu.models import transformer

    # RMSNorm under GatedRMSNorm's signature and name: the scale keeps
    # its path, norm/scale
    class Plain(transformer.RMSNorm):
        def __call__(self, y, z):
            return super().__call__(y)

    with mock.patch.object(transformer, "GatedRMSNorm", Plain):
        yield {}


@contextlib.contextmanager
def _half_batch():
    """Half of the batch left out: the loss's mean is taken over the
    first half of the rows alone (one row of the cell's two)."""
    from distributeddataparallel_tpu import ops

    real = ops.lm_cross_entropy
    with mock.patch.object(
        ops, "lm_cross_entropy",
        lambda logits, targets: real(
            logits[: len(logits) // 2], targets[: len(targets) // 2]
        ),
    ):
        yield {}


@contextlib.contextmanager
def _overrides(**overrides):
    yield overrides


#: name -> context manager that yields the model overrides of the fault
FAULTS = {
    "fault_no_chunk_carry": _no_carry,
    "fault_no_skip": _no_skip,
    "fault_no_gate": _no_gate,
    "fault_residual_one": lambda: _overrides(residual_multiplier=1.0),
    "fault_scale_eighth": lambda: _overrides(attention_multiplier=0.125),
    "fault_half_batch": _half_batch,
}


def faulty_env(env: dict, overrides: dict) -> dict:
    """``env`` for a session of its own: nothing compiled is shared, and
    the mix's model overrides carry the fault's."""
    traffic = dict(env["traffic"])
    traffic["model_overrides"] = {**traffic["model_overrides"], **overrides}
    return {**env, "traffic": traffic, "shared": {}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--fault-seeds", type=int, default=1)
    args = ap.parse_args(argv)

    from benchmarks import harness, readings
    from benchmarks.reference import granite_hybrid

    cell = harness.load_cell(args.workload)
    harness.place_compile_cache()
    devices = harness.acquire_devices(cell["chips"])
    kind = harness.load_module("kinds", cell["traffic"]["kind"])
    shared: dict = {}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        env = {
            "cell": cell, "config": cell["config"],
            "traffic": cell["traffic"], "devices": devices, "seed": seed,
            "root": harness.ROOT, "spans": harness.Spans(), "window_s": 0.0,
            "shared": shared,
            "mark": lambda what: print(f"[{seed}] {what}", file=sys.stderr,
                                       flush=True),
        }
        session = kind.setup(env)
        session.release()
        ref = session.reference()

        def numbers(program):
            out = {k: v for k, v, _ in kind.compare(program, ref, {})}
            out["detail"] = readings.detail(program, ref)
            return out

        row = {"seed": seed, "program": numbers(session.program),
               "ref_loss": ref["loss"], "program_loss": session.program["loss"]}
        if i < args.control_seeds:
            row["control_fp8"] = numbers(readings.as_program(
                session.reference(quant=granite_hybrid.fake_fp8)
            ))
        if i < args.fault_seeds:
            for name, fault in FAULTS.items():
                with fault() as overrides:
                    broken = kind.setup(faulty_env(env, overrides))
                broken.release()
                row[name] = numbers(broken.program)
                # a call cut short has kept what it had read
                env["mark"](f"{name}: " + json.dumps(
                    {k: v for k, v in row[name].items() if k != "detail"}
                ))
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
