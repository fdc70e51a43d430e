#!/usr/bin/env python3
"""``aot_fit.fit_train`` for a cell whose mix names its own kind
(``train_hybrid``, which builds with ``kinds/train``'s ``build``): compile
the cell's step for a described ``v5e:2x2`` without a chip and print its
arguments, temporaries and their sum against the chip's memory.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit_hybrid.py [<workload> ...] [--batch N]

``--batch`` compiles the same step at another ``per_chip_batch`` (the
issue's fall-back from 2 to 1 is decided by this number).  A compile
that passes is not a chip run.
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CELLS = ("granite-4.0-h-micro.train-s4096",)


def main(argv) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser()
    ap.add_argument("workloads", nargs="*", default=list(CELLS))
    ap.add_argument("--batch", type=int)
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
        if args.batch:
            traffic["per_chip_batch"] = args.batch
        print(f"{name}: {traffic['per_chip_batch']} x {traffic['seq_len']} "
              "tokens a chip a step", flush=True)
        aot_fit.fit_train(
            {"cell": cell, "config": cell["config"], "traffic": traffic,
             "hbm": hbm}, topo,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
