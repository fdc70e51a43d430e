#!/usr/bin/env python3
"""Run one benchmark cell once:

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result object; the numbers that
decided ``correct`` are the last lines of standard error.  Exit code 1 and
no result when JAX finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.time()  # set-up counts from here: imports included

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from benchmarks import harness

    sys.exit(harness.main(t_start=T_START))
