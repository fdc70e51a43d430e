#!/usr/bin/env python3
"""Compile each cell's programs for a described ``v5e:2x2`` without a
chip and print what the compiler says they need:

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit.py [<workload> ...]

For each program: argument, output and temporary bytes per device, their
sum against the chip's memory, and how many ``tpu_custom_call``s (Pallas
kernels) it holds.  A compile that passes is not a chip run; it says
that the cell fits and which kernels are in it, before chip time is
asked for.  (The persistent cache is off here: an entry written without
a chip cannot be read back.)
"""

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def describe(name: str, compiled, hbm_bytes: float) -> dict:
    mem = compiled.memory_analysis()
    arg, out, tmp = (
        mem.argument_size_in_bytes, mem.output_size_in_bytes,
        mem.temp_size_in_bytes,
    )
    alias = mem.alias_size_in_bytes
    text = compiled.as_text()
    row = {
        "program": name, "argument_bytes": arg, "output_bytes": out,
        "alias_bytes": alias, "temp_bytes": tmp,
        "live_bytes": arg + out - alias + tmp,
        "share_of_chip": (arg + out - alias + tmp) / hbm_bytes,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count(" all-reduce("),
    }
    print(json.dumps(row), flush=True)
    return row


def with_sharding(tree, sharding):
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def fit_train(env, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as np

    from benchmarks import harness

    train = harness.load_module("kinds", "train")
    chips = env["cell"]["chips"]
    mesh = Mesh(np.asarray(topo.devices[:chips], dtype=object), ("data",))
    built = train.build(env, mesh)
    rep = NamedSharding(mesh, P())
    state = with_sharding(
        jax.eval_shape(built["make_state"], built["shapes"]), rep
    )
    t = env["traffic"]
    batch = {"tokens": jax.ShapeDtypeStruct(
        (t["per_chip_batch"] * chips, t["seq_len"] + 1), jnp.int32,
        sharding=NamedSharding(mesh, P("data")),
    )}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=rep)
    compiled = built["step_fn"].lower(state, batch, rng).compile()
    describe(f"{env['cell']['name']}: train step", compiled, env["hbm"])


def fit_serve(env, topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from distributeddataparallel_tpu.serving import engine as engine_mod

    from benchmarks import harness

    serve = harness.load_module("kinds", "serve")
    real_make_pool = engine_mod.make_pool
    # the pool as shapes: nothing of this size is allocated here
    engine_mod.make_pool = lambda *a, **k: jax.eval_shape(
        lambda: real_make_pool(*a, **k)
    )
    try:
        model, ecfg = serve.build(env)
        shapes = serve.param_shapes(model, jnp.bfloat16)
        engine = engine_mod.InferenceEngine(model, shapes, ecfg)
    finally:
        engine_mod.make_pool = real_make_pool
    one = SingleDeviceSharding(topo.devices[0])
    c = ecfg

    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = with_sharding(shapes, one)
    pool = with_sharding(engine.pool, one)
    decode = engine._decode_prog.lower(
        params, pool, sds((c.num_slots, engine.blocks_per_seq)),
        sds((c.num_slots, 1)), sds((c.num_slots,)),
    ).compile()
    describe(f"{env['cell']['name']}: decode program", decode, env["hbm"])
    prefill = engine._prefill_prog.lower(
        params, pool, sds((engine.blocks_per_seq,)),
        sds((c.prefill_chunk,)), sds(()), sds(()),
    ).compile()
    describe(f"{env['cell']['name']}: prefill program", prefill, env["hbm"])


def main(argv) -> int:
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    # pallas_attention.supported() asks for the backend; here that is the
    # CPU, and the programs are compiled for the described TPU
    jax.default_backend = lambda: "tpu"

    from benchmarks import harness

    bench = harness.read_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    names = argv or [w["name"] for w in bench["workloads"]]
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    peaks = harness.load_peaks(topo.devices[0].device_kind)
    for name in names:
        cell = harness.load_cell(name)
        env = {"cell": cell, "config": cell["config"],
               "traffic": cell["traffic"], "hbm": peaks["hbm_bytes"]}
        {"train": fit_train, "serve": fit_serve}[cell["traffic"]["kind"]](
            env, topo
        )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
