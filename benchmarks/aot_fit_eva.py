#!/usr/bin/env python3
"""``aot_fit_moe.py`` for the chunk-summarised attention cell, whose kind
builds its own step too (``train_eva``: the loss is the multi-byte one):
compile the cell's step for a described ``v5e:2x2`` without a chip and
print its arguments, temporaries and their sum against the chip's memory.

    JAX_PLATFORMS=cpu python3 benchmarks/aot_fit_eva.py [<workload> ...] [--seq N]

``--seq`` compiles the same step at another ``seq_len``.  A compile that
passes is not a chip run.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import aot_fit_moe  # noqa: E402

CELLS = ("evabyte.train-s16384",)

#: the compiled step of an ``env``'s cell: any kind that has a ``build``
fit = aot_fit_moe.fit


def main(argv) -> int:
    named = any(not a.startswith("-") and not a.isdigit() for a in argv)
    return aot_fit_moe.main(list(argv) if named else [*CELLS, *argv])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
