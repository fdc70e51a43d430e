#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, on the chip
at the cell's own size, several seeds in one process:

    python3 benchmarks/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 3] [--seconds 12]

For every seed: the program's numbers against the plain reference (the
lower readings).  For the first ``--control-seeds`` seeds also the
control — the reference put in the program's place and computed in fp8,
one precision step below the bfloat16 that the cells state — and, for a
training cell, each fault read the same way: half of the batch left out
(the mean taken over the rest) and, on several chips, the exchange left
out (one replica's rows alone).  ``--reference-only`` reads a training
cell's control and faults alone, at its global batch, on one chip.
One JSON line per seed (or per case and seed) on stdout.
The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def as_program(out: dict) -> dict:
    """A reference-style result in the program's shape (one replica)."""
    return {
        "loss": out["loss"],
        "grad_norm": {k: [v] for k, v in out["grad_norm"].items()},
        "update_norm": {k: [v] for k, v in out["update_norm"].items()},
    }


def detail(program: dict, ref: dict) -> dict:
    """Beyond the worst leaf: which leaf it is, the worst among the leaves
    whose reference gradient is not nought to rounding, the median leaf's
    gap, and each step's loss gap — to see which number separates."""
    import statistics

    gmed = statistics.median(ref["grad_norm"].values())
    live = [k for k, g in ref["grad_norm"].items() if g >= 1e-3 * gmed]
    out = {"loss_gap_by_step": [
        abs(p - r) / abs(r) for p, r in zip(program["loss"], ref["loss"])
    ], "dead_leaves": len(ref["grad_norm"]) - len(live)}
    for what in ("grad_norm", "update_norm"):
        med = statistics.median(ref[what].values())
        gaps = {
            k: max(abs(x - r) for x in program[what][k]) / max(r, med)
            for k, r in ref[what].items()
        }
        worst = max(gaps, key=gaps.get)
        live_gaps = sorted(gaps[k] for k in live)
        out[what] = {
            "worst_leaf": worst, "worst": gaps[worst],
            "worst_live": live_gaps[-1],
            "worst_live_leaf": max(live, key=lambda k: gaps[k]),
            "p90_live": live_gaps[int(0.9 * len(live_gaps))],
            "median_live": statistics.median(live_gaps),
            "rel_to_own_norm_worst_live": max(
                max(abs(x - ref[what][k]) for x in program[what][k])
                / ref[what][k] for k in live
            ),
        }
    return out


def read_train(kind, env, controls: bool) -> dict:
    from benchmarks.reference import gpt2

    session = kind.setup(env)
    session.release()
    ref = session.reference()

    def numbers(program):
        out = {k: v for k, v, _ in kind.compare(program, ref, {})}
        out["detail"] = detail(program, ref)
        return out

    row = {"program": numbers(session.program), "ref_loss": ref["loss"],
           "program_loss": session.program["loss"]}
    if controls:
        batches = session.program["batches"]
        row["control_fp8"] = numbers(as_program(
            session.reference(quant=gpt2.fake_fp8)
        ))
        half = [b[: len(b) // 2] for b in batches]
        row["fault_half_batch"] = numbers(as_program(
            session.reference(batches=half)
        ))
        chips = len(env["devices"])
        if chips > 1:
            per = len(batches[0]) // chips
            row["fault_no_exchange"] = numbers(as_program(
                session.reference(batches=[b[:per] for b in batches])
            ))
    return row


def read_train_reference_only(kind, envs: list):
    """The control and the faults of a training cell need no program and
    no second chip: the reference in fp8, on one replica's rows, or on
    half of the rows, against the reference itself, at the cell's own
    global batch — on one chip, whatever the cell holds.  Yields one row
    per case and seed, every seed's control first and the cheapest fault
    last, so that a call cut short has kept what sets the limits."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from distributeddataparallel_tpu import data
    from distributeddataparallel_tpu.models.transformer import TransformerLM

    from benchmarks import harness
    from benchmarks.reference import gpt2

    t = envs[0]["traffic"]
    chips = envs[0]["cell"]["chips"]
    rows = t["per_chip_batch"] * chips
    cfg = kind.model_config(envs[0])
    shapes = kind.param_shapes(TransformerLM(cfg))
    one = SingleDeviceSharding(envs[0]["devices"][0])

    def steps(seed, take, quant=None):
        w0 = harness.flatten(harness.make_weights(
            shapes, seed, cfg.num_layers, jnp.float32, one
        ))
        tokens = data.SyntheticLM(
            num_examples=rows * t["check_steps"], seq_len=t["seq_len"],
            vocab_size=cfg.vocab_size, seed=seed,
        ).tokens
        batches = [b[:take] for b in np.split(tokens, t["check_steps"])]
        return gpt2.train_steps(w0, batches, t["optimizer"], quant=quant)

    cases = [("control_fp8", rows, gpt2.fake_fp8)]
    if chips > 1:
        cases.append(("fault_no_exchange", rows // chips, None))
    cases.append(("fault_half_batch", rows // 2, None))
    refs = {env["seed"]: steps(env["seed"], rows) for env in envs}
    for name, take, quant in cases:
        for env in envs:
            out = as_program(steps(env["seed"], take, quant))
            yield {"seed": env["seed"], name: {
                k: v for k, v, _ in kind.compare(out, refs[env["seed"]], {})
            }}


def read_serve(kind, env, controls: bool) -> dict:
    import jax
    import numpy as np

    from benchmarks.reference import gpt2

    session = kind.setup(env)
    measured = session.measure(env["window_s"])
    session.release()
    sample = session.sample()
    w = session.reference_weights()
    worst = worst_control = 0.0
    served = 0
    with jax.default_device(session.device):
        for prompt, generated in sample:
            gaps, rows = gpt2.served_gaps(w, prompt, generated,
                                          session.pad_to())
            worst = max(worst, float(np.max(gaps)))
            served += len(generated)
            if controls:
                _, low = gpt2.served_gaps(
                    w, prompt, generated, session.pad_to(),
                    quant=gpt2.fake_fp8,
                )
                rows = np.asarray(rows)
                first = np.argmax(np.asarray(low), axis=-1)
                gap = rows.max(axis=-1) - rows[np.arange(len(first)), first]
                worst_control = max(worst_control, float(gap.max()))
    row = {"program": {"served_logit_gap": worst}, "served_tokens": served,
           "requests": len(sample), "attempted": measured["attempted"],
           "failed": measured["failed"]}
    if controls:
        row["control_fp8"] = {"served_logit_gap": worst_control}
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--reference-only", action="store_true",
                    help="training cells: control and faults alone, on "
                         "one chip (see read_train_reference_only)")
    args = ap.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    harness.place_compile_cache()
    devices = harness.acquire_devices(
        1 if args.reference_only else cell["chips"]
    )
    kind = harness.load_module("kinds", cell["traffic"]["kind"])
    read = {"train": read_train, "serve": read_serve}[cell["traffic"]["kind"]]
    shared: dict = {}
    envs = [
        {
            "cell": cell, "config": cell["config"],
            "traffic": cell["traffic"], "devices": devices, "seed": int(s),
            "root": harness.ROOT, "spans": harness.Spans(),
            "window_s": args.seconds, "shared": shared,
        }
        for s in args.seeds.split(",")
    ]
    if args.reference_only:
        rows = read_train_reference_only(kind, envs)
    else:
        rows = (
            {"seed": env["seed"], **read(kind, env, i < args.control_seeds)}
            for i, env in enumerate(envs)
        )
    for row in rows:
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
