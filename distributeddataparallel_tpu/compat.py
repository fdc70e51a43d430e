"""CPU device-count configuration for tests and ``--fake-devices``."""

from __future__ import annotations

import jax


def configure_cpu_devices(n: int) -> None:
    """Run this process on ``n`` virtual CPU devices.

    Must run before the first device query creates the CPU client (the
    callers — conftest, ``dpp.py --fake-devices``, spawned test workers —
    all run it at interpreter startup).
    """
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)
