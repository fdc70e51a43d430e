"""Training cells of an ``evabyte`` model: ``kinds/train``'s session whole
— the same objects, loop, window and comparison — with the three things
such a configuration changes: the plain reference
(``reference/evabyte.py``, told the architecture by the configuration's
file), the draw of the pooling's two learned vectors a head
(``eva_draws``: of order 1, where ``harness.make_weights``' normal(0,
0.02) would make every chunk's summary its plain mean), and a ``build``
whose loss is the multi-byte one (eight heads a position, each scored on
a byte further ahead).
"""

from __future__ import annotations

import os

from benchmarks import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
train = harness.load_module("kinds", "train", ROOT)

compare = train.compare  # what readings_eva.py uses of a kind


def build(env, mesh) -> dict:
    """``kinds/train.build`` with the multi-byte loss: the rows' ids are
    the inputs and every head's targets; the accuracy is head 0's."""
    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.ops import (
        accuracy,
        multi_token_cross_entropy,
    )

    built = train.build(env, mesh)
    model = built["model"]

    def loss_fn(params, batch, rng):
        ids = batch["tokens"]
        logits = model.apply({"params": params}, ids[:, :-1])
        return multi_token_cross_entropy(logits, ids), {
            "accuracy": accuracy(logits[:, :, 0], ids[:, 1:]),
        }

    built["step_fn"] = ddp.make_train_step(loss_fn, mesh=mesh)
    return built


def eva_draws(weights, seed: int, sharding):
    """``weights`` with ``adaptive_phi`` and ``adaptive_mu_k`` drawn by the
    benchmark's own rule, normal(0, 1) cut at +-1 — of order 1 beside keys
    of order 1, so that a chunk's sixteen pooling weights differ
    severalfold — in place of ``make_weights``' normal(0, 0.02), under
    which they would all be a sixteenth."""
    import jax
    import jax.numpy as jnp

    flat = harness.flatten(weights)
    drawn_by = {
        path: leaf.shape for path, leaf in flat.items()
        if path.rsplit("/", 1)[1] in ("adaptive_phi", "adaptive_mu_k")
    }

    def draw(key):
        return {
            path: jnp.clip(
                jax.random.normal(jax.random.fold_in(key, i), shape), -1.0, 1.0
            )
            for i, (path, shape) in enumerate(drawn_by.items())
        }

    key = jax.random.fold_in(
        jax.random.PRNGKey((seed & 0x7FFFFFFF) ^ 0xE7A), seed >> 31
    )
    drawn = jax.jit(draw, out_shardings=sharding)(key)
    # the large leaves pass through untouched: nothing of their size is copied
    leaves, treedef = jax.tree.flatten(weights)
    return jax.tree.unflatten(
        treedef, [drawn.get(path, leaf) for path, leaf in zip(flat, leaves)]
    )


class Session(train.Session):
    def initial_weights(self, sharding=None):
        sharding = sharding if sharding is not None else self.replicated
        return eva_draws(
            super().initial_weights(sharding), self.seed, sharding
        )

    def reference(self, quant=None, batches=None) -> dict:
        import jax
        from jax.sharding import SingleDeviceSharding

        from benchmarks.reference import evabyte

        device = self.env["devices"][0]
        with jax.default_device(device):
            # the starting weights are handed over, not kept: the
            # reference moves them to the host (its docstring)
            return evabyte.train_steps(
                harness.flatten(
                    self.initial_weights(SingleDeviceSharding(device))
                ),
                batches if batches is not None else self.program["batches"],
                self.env["traffic"]["optimizer"], self.env["config"],
                quant=quant, progress=self.env.get("mark"),
                devices=self.env["devices"],
            )


def setup(env) -> Session:
    import distributeddataparallel_tpu as ddp

    # this kind's own build, handed in where kinds/train looks for one
    shared = env.setdefault("shared", {})
    if "built" not in shared:
        shared["built"] = build(
            env, ddp.make_mesh(("data",), devices=env["devices"])
        )
    return Session(env)
