"""Test harness: force 8 fake CPU devices before JAX backends initialize.

This is the JAX-native analog of torch's fake process group (SURVEY.md §4):
every DP test — psum correctness, sampler semantics, grad-accum boundaries,
the DDP equivalence invariant — runs on an 8-device CPU mesh in one process,
no cluster needed.

``JAX_PLATFORMS=cpu`` in the environment works too; the config update
makes the suite independent of it.
"""

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow'); run "
        "explicitly or via the dedicated CI stage",
    )


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="module", autouse=True)
def _bounded_jit_cache():
    """Drop prior modules' compiled executables at each module start.

    A ~280-test run accumulates hundreds of executables in one process;
    a full-suite run once hit an XLA:CPU runtime abort deep in the
    pipeline module that never reproduces standalone or in the module's
    own run.  Bounding the live cache to ~one module's worth keeps the
    suite's memory/runtime state shaped like the per-module runs that
    are known good, while preserving within-module cache reuse."""
    jax.clear_caches()
    yield
