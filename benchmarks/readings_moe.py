#!/usr/bin/env python3
"""``readings_hybrid.py`` for the expert-layer training cell: the numbers
its limits are set from, on the chip at the cell's own size, several
seeds in one process:

    python3 benchmarks/readings_moe.py --seeds 1,2,3 [--control-seeds 1] [--fault-seeds 1] [--faults a,b]

For every seed the program's numbers against the plain reference (the
lower readings) and the experts' load in both.  For the first
``--control-seeds`` seeds the control: the reference put in the program's
place and computed in fp8.  For the first ``--fault-seeds`` seeds each of
``FAULTS`` (or those named): the program itself with one term of the
model left out or one constant wrong, compiled anew, driven through the
same steps on the same rows and read against the same reference.  One
JSON line per seed on stdout.  The benchmark's own runs never run this;
the CPU tests run ``FAULTS`` at a tiny size.
"""

import argparse
import contextlib
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "trinity-mini.train-s8192"


@contextlib.contextmanager
def _no_window():
    """The window left out: sliding layers see every earlier key."""
    from distributeddataparallel_tpu.models import transformer

    real = transformer.attention
    with mock.patch.object(
        transformer, "attention",
        lambda *a, window=None, **k: real(*a, **k),
    ):
        yield {}


@contextlib.contextmanager
def _rope_on_full():
    """RoPE put on the full layer too."""
    from distributeddataparallel_tpu.models import transformer

    with mock.patch.object(transformer, "FULL", "no layer's kind"):
        yield {}


@contextlib.contextmanager
def _no_output_gate():
    """sigmoid(x W_gate) left out of the attention's result."""
    from distributeddataparallel_tpu.models import transformer

    with mock.patch.object(
        transformer, "_output_gate", lambda out, gate: out
    ):
        yield {}


@contextlib.contextmanager
def _bias_in_gate():
    """The selection's bias added into the gate weight."""
    import jax
    import jax.numpy as jnp

    from distributeddataparallel_tpu.models import transformer

    def select(scores, bias, k):
        _, idx = jax.lax.top_k(scores + bias, k)
        return jnp.take_along_axis(scores + bias, idx, axis=-1), idx

    with mock.patch.object(transformer, "_select", select):
        yield {}


@contextlib.contextmanager
def _capacity_one():
    """Rows past a capacity of 1.0 x the mean load dropped: a (token,
    choice) whose expert already has T K / E earlier ones counts nought."""
    import jax
    import jax.numpy as jnp

    from distributeddataparallel_tpu.ops import moe

    real = moe.dropless

    def capped(xt, gates, idx, num_experts, *share):
        T, K = idx.shape
        chosen = jax.nn.one_hot(idx.reshape(-1), num_experts, dtype=jnp.int32)
        place = jnp.sum((jnp.cumsum(chosen, axis=0) - 1) * chosen, axis=-1)
        kept = (place < T * K // num_experts).reshape(T, K)
        return real(xt, jnp.where(kept, gates, 0.0), idx, num_experts, *share)

    with mock.patch.object(moe, "dropless", capped):
        yield {}


@contextlib.contextmanager
def _no_shared_expert():
    """The shared expert's result left out."""
    from distributeddataparallel_tpu.models import transformer

    class Silent(transformer.MLP):  # keeps its leaves under mlp/shared
        def __call__(self, x):
            y = super().__call__(x)
            return y * 0 if self.name == "shared" else y

    with mock.patch.object(transformer, "MLP", Silent):
        yield {}


@contextlib.contextmanager
def _no_head_norms():
    """q and k left as projected: their RMSNorms do nothing."""
    from distributeddataparallel_tpu.models import transformer

    class Plain(transformer.RMSNorm):  # keeps its scale under q_norm, k_norm
        def __call__(self, x):
            y = super().__call__(x)
            return x if self.name in ("q_norm", "k_norm") else y

    with mock.patch.object(transformer, "RMSNorm", Plain):
        yield {}


@contextlib.contextmanager
def _overrides(**overrides):
    yield overrides


#: name -> context manager that yields the model overrides of the fault
FAULTS = {
    "fault_no_window": _no_window,
    "fault_rope_on_full": _rope_on_full,
    "fault_no_output_gate": _no_output_gate,
    "fault_route_scale_one": lambda: _overrides(moe_route_scale=1.0),
    "fault_bias_in_gate": _bias_in_gate,
    "fault_capacity_one": _capacity_one,
    "fault_no_route_norm": lambda: _overrides(moe_route_norm=False),
    "fault_no_shared_expert": _no_shared_expert,
    "fault_top_4": lambda: _overrides(moe_top_k=4),
    "fault_no_head_norms": _no_head_norms,
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--faults", default=",".join(FAULTS))
    args = ap.parse_args(argv)

    from benchmarks import harness, readings
    from benchmarks.readings_hybrid import faulty_env
    from benchmarks.reference import afmoe

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
               "ref_loss": ref["loss"], "program_loss": session.program["loss"],
               "program_load": session.program["load"],
               "ref_load": ref["load"]}
        if i < args.control_seeds:
            row["control_fp8"] = numbers(readings.as_program(
                session.reference(quant=afmoe.fake_fp8)
            ))
        if i < args.fault_seeds:
            for name in args.faults.split(","):
                with FAULTS[name]() as overrides:
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
    sys.exit(main(sys.argv[1:]))
