#!/usr/bin/env python3
"""``aot_fit.fit_train`` for the cell whose kind builds its own step
(``train_moe``: the loss hands the experts' load out): compile the cell's
step for a described ``v5e:2x2`` without a chip and print its arguments,
temporaries and their sum against the chip's memory.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit_moe.py [<workload> ...] [--seq N]

``--seq`` compiles the same step at another ``seq_len``.  A compile that
passes is not a chip run.
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELLS = ("trinity-mini.train-s8192",)


def fit(env, topo):
    """The compiled step of ``env``'s cell for ``topo``'s first chips."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks import aot_fit, harness

    kind = harness.load_module("kinds", env["traffic"]["kind"])
    chips = env["cell"]["chips"]
    mesh = Mesh(np.asarray(topo.devices[:chips], dtype=object), ("data",))
    built = kind.build(env, mesh)
    rep = NamedSharding(mesh, P())
    state = aot_fit.with_sharding(
        jax.eval_shape(built["make_state"], built["shapes"]), rep
    )
    t = env["traffic"]
    batch = {"tokens": jax.ShapeDtypeStruct(
        (t["per_chip_batch"] * chips, t["seq_len"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, P("data")),
    )}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    return built["step_fn"].lower(state, batch, rng).compile()


def main(argv) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("workloads", nargs="*", default=list(CELLS))
    ap.add_argument("--seq", type=int)
    args = ap.parse_args(argv)

    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    # pallas_attention.supported() asks for the backend; here that is the
    # CPU, and the step is compiled for the described TPU
    jax.default_backend = lambda: "tpu"

    from benchmarks import aot_fit, harness

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    hbm = harness.load_peaks(topo.devices[0].device_kind)["hbm_bytes"]
    for name in args.workloads:
        cell = harness.load_cell(name)
        traffic = dict(cell["traffic"])
        if args.seq:
            traffic["seq_len"] = args.seq
        print(f"{name}: {traffic['per_chip_batch']} x {traffic['seq_len']} "
              "tokens a chip a step", flush=True)
        env = {"cell": cell, "config": cell["config"], "traffic": traffic}
        aot_fit.describe(f"{name}: train step", fit(env, topo), hbm)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
