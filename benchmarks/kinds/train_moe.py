"""Training cells of an ``afmoe`` model of which the chip holds a share
of the experts: ``kinds/train``'s session whole — the same objects, loop,
window and comparison — with the two things such a configuration
changes: the plain reference (``reference/afmoe.py``, told the
architecture and the share by the configuration's file) and the load
counter — the rows each held expert received, which the expert layer
sows as ``moe_load`` and this kind's ``build`` hands out of the step
among its metrics, so that the three check steps can print it beside the
reference's own count.  It is compared with no limit.
"""

from __future__ import annotations

import os

import numpy as np

from benchmarks import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
train = harness.load_module("kinds", "train", ROOT)

compare = train.compare  # what readings_moe.py uses of a kind


def build(env, mesh) -> dict:
    """``kinds/train.build`` with a loss that makes ``intermediates``
    mutable and returns every expert layer's load, (layers, held experts)
    float32, among the step's metrics."""
    import jax.numpy as jnp

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.ops import accuracy, lm_cross_entropy

    built = train.build(env, mesh)
    model = built["model"]

    def loss_fn(params, batch, rng):
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
        logits, col = model.apply(
            {"params": params}, inputs, mutable=["intermediates"]
        )
        layers = col["intermediates"]
        load = jnp.stack([
            layers[name]["mlp"]["moe_load"][0]
            for name in sorted(layers, key=lambda n: int(n.split("_")[1]))
        ]).astype(jnp.float32)
        return lm_cross_entropy(logits, targets), {
            "accuracy": accuracy(logits, targets), "moe_load": load,
        }

    built["step_fn"] = ddp.make_train_step(loss_fn, mesh=mesh)
    return built


def load_lines(program: list, reference: list, choices: int) -> list:
    """One line a check step and expert layer: the rows each held expert
    received in the program and in the reference, the largest over the
    mean, and the share of the step's (token, choice) pairs held here."""
    out = []
    for step, (got, want) in enumerate(zip(program, reference)):
        for layer, (g, w) in enumerate(zip(got, want)):
            out.append(
                f"load step {step + 1} expert layer {layer}: program {g} "
                f"reference {w} max/mean {max(g) / (sum(g) / len(g)):.3f} "
                f"share of T*K {sum(g) / choices:.4f}"
            )
    return out


class Session(train.Session):
    def _check_steps(self, n: int, mark) -> dict:
        loads = []
        step = self._step

        def recording():
            batch, metrics = step()
            loads.append(
                np.rint(np.asarray(metrics["moe_load"])).astype(int).tolist()
            )
            return batch, metrics

        self._step = recording
        try:
            out = super()._check_steps(n, mark)
        finally:
            del self._step  # the window calls the class's own
        out["load"] = loads
        return out

    def reference(self, quant=None, batches=None) -> dict:
        import jax
        from jax.sharding import SingleDeviceSharding

        from benchmarks.reference import afmoe

        device = self.env["devices"][0]
        with jax.default_device(device):
            # the starting weights are handed over, not kept: the
            # reference moves them to the host (its docstring)
            return afmoe.train_steps(
                harness.flatten(
                    self.initial_weights(SingleDeviceSharding(device))
                ),
                batches if batches is not None else self.program["batches"],
                self.env["traffic"]["optimizer"], self.env["config"],
                quant=quant, progress=self.env.get("mark"),
                devices=self.env["devices"],
            )

    def check(self) -> list:
        reference = self.reference()
        mark = self.env.get("mark", lambda what: None)
        choices = self.tokens_per_step * self.env["config"]["num_experts_per_tok"]
        for line in load_lines(self.program["load"], reference["load"], choices):
            mark(line)
        numbers = compare(
            self.program, reference, self.env["traffic"]["limits"]
        )
        for name, value, limit in numbers:
            if limit is None:
                mark(f"not compared: {name} = {value!r}")
        return [n for n in numbers if n[2] is not None]


def setup(env) -> Session:
    import distributeddataparallel_tpu as ddp

    # this kind's own build, handed in where kinds/train looks for one
    shared = env.setdefault("shared", {})
    if "built" not in shared:
        shared["built"] = build(
            env, ddp.make_mesh(("data",), devices=env["devices"])
        )
    return Session(env)
