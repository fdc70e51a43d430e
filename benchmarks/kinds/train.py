"""Training cells: the package's data-parallel trainer, composed as
``dpp.py`` composes it — ``TransformerLM`` + ``TrainState`` +
``broadcast_params`` + ``make_train_step`` fed by ``DataLoader`` over
``SyntheticLM``, steps pushed through ``BoundedDispatch`` — in one
process that holds the cell's chips.

Set-up builds ONE object (the compiled step with its state), drives it
through its first ``check_steps`` steps on the window's own feed, keeps
what the comparison needs of them (per-step loss, per-leaf norms of the
first gradient read back from AdamW's first moment, per-leaf norms of the
parameters' change), and hands the same object to the window.
"""

from __future__ import annotations

import statistics
import time

import numpy as np


def model_config(env):
    import jax.numpy as jnp

    from distributeddataparallel_tpu.models import transformer

    overrides = dict(env["config"]["overrides"])
    overrides.update(env["traffic"].get("model_overrides", {}))
    if "dtype" in overrides:
        overrides["dtype"] = jnp.dtype(overrides["dtype"]).type
    return getattr(transformer, env["config"]["constructor"])(**overrides)


def param_shapes(model):
    """The program's parameter tree as shapes.  Nothing is computed, and
    the shapes depend neither on the length nor on the attention path, so
    it is traced short and without the kernels."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    plain = type(model)(dataclasses.replace(model.cfg, attn_impl="xla"))
    return jax.eval_shape(
        plain.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)
    )["params"]


def per_replica_norms(mesh, tree, minus=None):
    """Per-leaf L2 norms of ``tree`` (minus ``minus``), computed by every
    replica on its own copy: {leaf: [norm on replica 0, 1, ...]}."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from benchmarks.harness import flatten

    def norms(a, b=None):
        fa = flatten(a)
        fb = flatten(b) if b is not None else None
        return jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(
                v.astype(jnp.float32)
                - (fb[k].astype(jnp.float32) if fb is not None else 0.0)
            )))
            for k, v in fa.items()
        ])[None]

    args = (tree,) if minus is None else (tree, minus)
    out = jax.jit(jax.shard_map(
        norms, mesh=mesh, in_specs=tuple(P() for _ in args),
        out_specs=P("data"), check_vma=False,
    ))(*args)
    out = np.asarray(out)  # (replicas, leaves)
    return {k: out[:, i].tolist() for i, k in enumerate(flatten(tree))}


def worst_gap(program: dict, reference: dict, skip=()) -> float:
    """Worst leaf's gap between the program's norm (every replica's) and
    the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    median = statistics.median(reference.values())
    worst = 0.0
    for leaf, ref in reference.items():
        if leaf in skip:
            continue
        for got in program[leaf]:
            gap = abs(got - ref) / max(ref, median)
            worst = max(worst, gap) if gap == gap else float("inf")
    return worst


def compare(program: dict, reference: dict, limits: dict) -> list:
    """The cell's numbers, each with its limit.  Leaves whose reference
    gradient is nought to rounding (under a thousandth of the median
    leaf's — a key's bias under softmax) move under Adam by round-off
    alone and are left out of the update's comparison."""
    loss_gap = max(
        abs(p - r) / abs(r)
        for p, r in zip(program["loss"], reference["loss"])
    )
    if not loss_gap == loss_gap:
        loss_gap = float("inf")
    gmed = statistics.median(reference["grad_norm"].values())
    dead = {k for k, g in reference["grad_norm"].items() if g < 1e-3 * gmed}
    numbers = {
        "loss_gap": loss_gap,
        "grad_norm_gap": worst_gap(
            program["grad_norm"], reference["grad_norm"]
        ),
        "update_norm_gap": worst_gap(
            program["update_norm"], reference["update_norm"], skip=dead
        ),
    }
    # a number with no limit in the mix's file is not compared (PERF.md
    # says why); it is still returned, for the run's log
    return [(k, float(v), limits.get(k)) for k, v in numbers.items()]


def build(env, mesh) -> dict:
    """The program's objects for this cell on ``mesh``: model, optimizer,
    the jitted state constructor and the compiled-on-first-call step —
    shared by the session and by ``aot_fit.py``."""
    import jax
    import optax

    import distributeddataparallel_tpu as ddp
    from distributeddataparallel_tpu.models.transformer import TransformerLM
    from distributeddataparallel_tpu.ops import accuracy, lm_cross_entropy
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = model_config(env)
    model = TransformerLM(cfg)
    opt = env["traffic"]["optimizer"]
    tx = optax.adamw(
        opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
        weight_decay=opt["weight_decay"],
    )

    def loss_fn(params, batch, rng):  # as dpp.py builds it for an LM
        inputs = batch["tokens"][:, :-1]
        targets = batch["tokens"][:, 1:]
        logits = model.apply({"params": params}, inputs)
        loss = lm_cross_entropy(logits, targets)
        return loss, {"accuracy": accuracy(logits, targets)}

    replicated = NamedSharding(mesh, P())
    return {
        "cfg": cfg, "model": model, "shapes": param_shapes(model),
        "replicated": replicated,
        "make_state": jax.jit(
            lambda p: ddp.TrainState.create(
                apply_fn=model.apply, params=p, tx=tx
            ),
            out_shardings=replicated,
        ),
        "step_fn": ddp.make_train_step(loss_fn, mesh=mesh),
    }


class Session:
    def __init__(self, env):
        import jax

        import distributeddataparallel_tpu as ddp
        from distributeddataparallel_tpu import data
        from distributeddataparallel_tpu.data.loader import DataLoader

        t = env["traffic"]
        self.env = env
        self.spans = env["spans"]
        self.seed = env["seed"]
        mark = env.get("mark", lambda what: None)
        self.mesh = ddp.make_mesh(("data",), devices=env["devices"])
        # readings.py reads several seeds in one process: one compiled step
        shared = env.get("shared", {})
        built = shared.get("built") or shared.setdefault(
            "built", build(env, self.mesh)
        )
        self.cfg = built["cfg"]
        self.shapes = built["shapes"]
        self.replicated = built["replicated"]
        self.step_fn = built["step_fn"]
        mark("program objects built")
        state = built["make_state"](self.initial_weights())
        self.state = ddp.broadcast_params(state, self.mesh)
        jax.block_until_ready(self.state)
        mark("weights and train state made on the device")
        self.global_batch = t["per_chip_batch"] * len(env["devices"])
        self.tokens_per_step = self.global_batch * t["seq_len"]
        dataset = data.SyntheticLM(
            num_examples=t["dataset_steps"] * self.global_batch,
            seq_len=t["seq_len"], vocab_size=self.cfg.vocab_size,
            seed=self.seed,
        )
        self.loader = DataLoader(
            dataset, per_replica_batch=t["per_chip_batch"], mesh=self.mesh,
            shuffle=True, seed=self.seed % (2 ** 31),
        )
        self.feed = self._feed()
        self.rng = jax.random.PRNGKey(self.seed % (2 ** 31))
        self.n_steps = 0
        mark("dataset and loader made")
        self.program = self._check_steps(t["check_steps"], mark)
        mark("check steps driven and read")

    def initial_weights(self, sharding=None):
        import jax.numpy as jnp

        from benchmarks.harness import make_weights

        return make_weights(
            self.shapes, self.seed, self.cfg.num_layers, jnp.float32,
            sharding if sharding is not None else self.replicated,
        )

    def _feed(self):
        epoch = 0
        while True:
            self.loader.set_epoch(epoch)
            yield from self.loader
            epoch += 1

    def _step(self):
        """The window's own call: one batch from the feed, one step."""
        import jax

        with self.spans.span("data_wait"):
            batch = next(self.feed)
        with self.spans.span("dispatch"):
            sub = jax.random.fold_in(self.rng, self.n_steps)
            self.state, metrics = self.step_fn(self.state, batch, sub)
        self.n_steps += 1
        return batch, metrics

    def _check_steps(self, n: int, mark) -> dict:
        """Drive the object through its first ``n`` steps and keep what
        the comparison reads of them."""
        out = {"loss": [], "batches": []}
        for i in range(n):
            batch, metrics = self._step()
            out["batches"].append(np.asarray(batch["tokens"]))
            out["loss"].append(float(metrics["loss"]))
            mark(f"step {i + 1} done")
            if i == 0:
                b1 = self.env["traffic"]["optimizer"]["b1"]
                mu = self.state.opt_state[0].mu
                out["grad_norm"] = {
                    k: [x / (1.0 - b1) for x in v]
                    for k, v in per_replica_norms(self.mesh, mu).items()
                }
        w0 = self.initial_weights()
        out["update_norm"] = per_replica_norms(
            self.mesh, self.state.params, w0
        )
        del w0
        rows = np.concatenate(out["batches"])
        if len({r.tobytes() for r in rows}) != len(rows):
            raise RuntimeError("the check steps' rows do not all differ")
        return out

    def measure(self, seconds: float) -> dict:
        import jax

        from distributeddataparallel_tpu.training.warm_start import (
            BoundedDispatch,
        )

        dispatch = BoundedDispatch(self.env["traffic"]["dispatch_depth"])
        self.spans.records.clear()  # the window's spans only
        steps = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            _, metrics = self._step()
            with self.spans.span("settle"):
                for handle, _ in dispatch.push(metrics["loss"]):
                    jax.block_until_ready(handle)
            steps += 1
        for handle, _ in dispatch.drain():
            jax.block_until_ready(handle)
        jax.block_until_ready(self.state)
        window_s = time.perf_counter() - t0
        chips = len(self.env["devices"])
        return {
            "attempted": steps, "failed": 0, "window_s": window_s,
            "steps": steps, "tokens": steps * self.tokens_per_step,
            "end_to_end": {
                "train_tokens_s_chip":
                    steps * self.tokens_per_step / window_s / chips,
            },
        }

    def release(self) -> None:
        self.state = None
        self.step_fn = None
        self.feed = None
        self.loader = None

    def reference(self, quant=None, batches=None) -> dict:
        import jax
        from jax.sharding import SingleDeviceSharding

        from benchmarks.harness import flatten
        from benchmarks.reference import gpt2

        one = SingleDeviceSharding(self.env["devices"][0])
        w0 = flatten(self.initial_weights(one))
        with jax.default_device(self.env["devices"][0]):
            return gpt2.train_steps(
                w0, batches if batches is not None
                else self.program["batches"],
                self.env["traffic"]["optimizer"], quant=quant,
                progress=self.env.get("mark"),
                devices=self.env["devices"],
            )

    def check(self) -> list:
        numbers = compare(
            self.program, self.reference(), self.env["traffic"]["limits"]
        )
        mark = self.env.get("mark", lambda what: None)
        for name, value, limit in numbers:
            if limit is None:
                mark(f"not compared: {name} = {value!r}")
        return [n for n in numbers if n[2] is not None]


def setup(env) -> Session:
    return Session(env)
