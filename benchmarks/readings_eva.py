#!/usr/bin/env python3
"""``readings_moe.py`` for the chunk-summarised attention cell: the
numbers its limits are set from, on the chip at the cell's own size,
several seeds in one process:

    python3 benchmarks/readings_eva.py --seeds 1,2,3 [--control-seeds 1] [--fault-seeds 1] [--faults a,b]

For every seed the program's numbers against the plain reference (the
lower readings).  For the first ``--control-seeds`` seeds the control: the
reference put in the program's place and computed in fp8.  For the first
``--fault-seeds`` seeds each of ``FAULTS`` and ``NEIGHBOURS`` (or those
named): the program itself with one term of the model left out or one
rule wrong, compiled anew, driven through the same steps on the same rows
and read against the same reference.  One JSON line per seed on stdout.
The benchmark's own runs never run this; the CPU tests run ``FAULTS`` at a
tiny size.
"""

import argparse
import contextlib
import json
import os
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELL = "evabyte.train-s16384"


@contextlib.contextmanager
def _no_summaries():
    """The summarised term left out: block-local attention alone."""
    import jax.numpy as jnp

    from distributeddataparallel_tpu.ops import eva

    def nothing(q, ksum, vsum, stair, **kw):
        return jnp.zeros_like(q), jnp.full(q.shape[:3], -1e30, jnp.float32)

    with mock.patch.object(eva, "remote_attention", nothing):
        yield {}


@contextlib.contextmanager
def _own_window_leak():
    """A query sees its own window's chunks: the summaries moved up one
    window, so that window w's queries get those of windows 1..w."""
    import jax.numpy as jnp

    from distributeddataparallel_tpu.ops import eva

    real = eva.remote_attention

    def leaky(q, ksum, vsum, stair, **kw):
        up = lambda x: jnp.roll(x, -stair[1], axis=1)  # noqa: E731
        return real(q, up(ksum), up(vsum), stair, **kw)

    with mock.patch.object(eva, "remote_attention", leaky):
        yield {}


def _pooling_without(which: int):
    """``chunk_summaries`` with ``phi`` (3) or ``mu`` (4) nought."""
    @contextlib.contextmanager
    def fault():
        from distributeddataparallel_tpu.ops import eva

        real = eva.chunk_summaries

        def without(*args):
            args = list(args)
            args[which - 1] = args[which - 1] * 0
            return real(*args)

        with mock.patch.object(eva, "chunk_summaries", without):
            yield {}

    return fault


@contextlib.contextmanager
def _sliding_band():
    """A sliding band of ``window`` keys in place of block-local windows."""
    from distributeddataparallel_tpu.ops import eva

    def band(q, k, v, window, *, scale, impl="auto"):
        return eva.attention(q, k, v, causal=True, impl=impl, scale=scale,
                             window=window, return_lse=True)

    with mock.patch.object(eva, "local_attention", band):
        yield {}


@contextlib.contextmanager
def _no_unit_offset():
    """The norms scale by their learned vector alone, not by 1 + it."""
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from distributeddataparallel_tpu.models import transformer

    class Bare(transformer.RMSNorm):  # keeps its leaf, ``offset``
        @nn.compact
        def __call__(self, x):
            dtype = self.out_dtype or x.dtype
            x = x.astype(jnp.float32)
            w = self.param("offset", nn.initializers.zeros, (x.shape[-1],))
            x = x * jax.lax.rsqrt(
                jnp.mean(x * x, axis=-1, keepdims=True) + self.epsilon)
            return (x * w).astype(dtype)

    with mock.patch.object(transformer, "RMSNorm", Bare):
        yield {}


@contextlib.contextmanager
def _first_head_only():
    """Heads 1-7 left out of the loss: the next byte alone is scored."""
    from distributeddataparallel_tpu import ops

    real = ops.multi_token_cross_entropy
    with mock.patch.object(
        ops, "multi_token_cross_entropy",
        lambda logits, ids: real(logits[:, :, :1], ids),
    ):
        yield {}


@contextlib.contextmanager
def _overrides(**overrides):
    yield overrides


#: name -> context manager that yields the model overrides of the fault
FAULTS = {
    "fault_no_summaries": _no_summaries,
    "fault_own_window_leak": _own_window_leak,
    "fault_mean_pooling": _pooling_without(3),
    "fault_no_mu": _pooling_without(4),
    "fault_sliding_band": _sliding_band,
    "fault_no_unit_offset": _no_unit_offset,
    "fault_first_head_only": _first_head_only,
    "fault_no_rope": lambda: _overrides(positional="none"),
}
#: not faults to separate: the same mathematics one rounding away, read
#: beside the control to say where they land
NEIGHBOURS = {
    "neighbour_bf16_residual": lambda: _overrides(fp32_residual=False),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default=CELL)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=1)
    ap.add_argument("--fault-seeds", type=int, default=1)
    ap.add_argument("--faults", default=",".join([*FAULTS, *NEIGHBOURS]))
    args = ap.parse_args(argv)

    from benchmarks import harness, readings
    from benchmarks.readings_hybrid import faulty_env
    from benchmarks.reference import evabyte

    cases = {**FAULTS, **NEIGHBOURS}
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
                session.reference(quant=evabyte.fake_fp8)
            ))
        if i < args.fault_seeds:
            for name in filter(None, args.faults.split(",")):
                with cases[name]() as overrides:
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
