"""Training cells of a hybrid Mamba-2 / attention model: ``kinds/train``'s
session whole — the same objects, loop, window and comparison — with the
two things a hybrid configuration changes: the plain reference
(``reference/granite_hybrid.py``, told the architecture by the
configuration's file) and the draw of the mixer's own small parameters
(``mamba_draws``), which ``harness.make_weights``' normal(0, 0.02) would
leave at a memory of two steps and a scan that hardly matters.
"""

from __future__ import annotations

import os

from benchmarks import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
train = harness.load_module("kinds", "train", ROOT)

compare = train.compare  # what readings_hybrid.py uses of a kind


def mamba_draws(weights, seed: int, sharding):
    """``weights`` with the Mamba-2 mixer's own small parameters drawn by
    the program's own initialisers (``models/transformer``: ``A_log``,
    ``dt_bias`` and the convolution's taps and bias, as the public
    Mamba-2 code draws them — decays near 1, ``x``, ``B`` and ``C`` of
    order 1) in place of ``make_weights``' normal(0, 0.02), under which a
    dropped chunk-to-chunk term read like a sound run (PERF.md section
    2).  ``D`` keeps normal(0, 0.02): the scan, not the skip, carries
    ``y``."""
    import jax

    from distributeddataparallel_tpu.models import transformer

    flat = harness.flatten(weights)
    taps = next(
        leaf.shape[0] for path, leaf in flat.items()
        if path.endswith("/conv_kernel")
    )
    inits = {
        "A_log": transformer.a_log_init, "dt_bias": transformer.dt_bias_init,
        "conv_kernel": transformer.conv_init(taps),
        "conv_bias": transformer.conv_init(taps),
    }
    drawn_by = {
        path: (inits[path.rsplit("/", 1)[1]], leaf.shape)
        for path, leaf in flat.items() if path.rsplit("/", 1)[1] in inits
    }

    def draw(key):
        return {
            path: init(jax.random.fold_in(key, i), shape)
            for i, (path, (init, shape)) in enumerate(drawn_by.items())
        }

    key = jax.random.fold_in(
        jax.random.PRNGKey((seed & 0x7FFFFFFF) ^ 0x5D5), seed >> 31
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
        return mamba_draws(
            super().initial_weights(sharding), self.seed, sharding
        )

    def reference(self, quant=None, batches=None) -> dict:
        import jax
        from jax.sharding import SingleDeviceSharding

        from benchmarks.reference import granite_hybrid

        device = self.env["devices"][0]
        with jax.default_device(device):
            # the starting weights are handed over, not kept: the
            # reference moves them to the host (its docstring)
            return granite_hybrid.train_steps(
                harness.flatten(
                    self.initial_weights(SingleDeviceSharding(device))
                ),
                batches if batches is not None else self.program["batches"],
                self.env["traffic"]["optimizer"], self.env["config"],
                quant=quant, progress=self.env.get("mark"),
                devices=self.env["devices"],
            )


def setup(env) -> Session:
    return Session(env)
