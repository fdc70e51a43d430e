"""Observability: throughput counters, profiler hooks, all-reduce BW probe.

The reference's entire observability surface is a rank-0 loss print every
100 batches (ref dpp.py:54-55).  This module provides the BASELINE-metric
instrumentation on top of that: img/s/chip and tokens/s/chip counters, a
``jax.profiler`` trace context (XProf/TensorBoard-compatible), and a
gradient all-reduce bandwidth-utilization probe — the north-star metric's
denominator (BASELINE.md "grad all-reduce BW util").

Design rule carried over from the reference critique (SURVEY.md §2d.6):
keep measurement off the hot path.  ``StepTimer`` only forces a device
sync at window boundaries; per-step it just stamps the host clock.
"""

from __future__ import annotations

import time

import jax

# profile_trace moved to the observability subsystem (PR 3); re-exported
# here so existing imports (`from distributeddataparallel_tpu.utils import
# profile_trace`) keep working.
from distributeddataparallel_tpu.observability.profiler import (  # noqa: F401
    profile_trace,
)
from distributeddataparallel_tpu.observability.schema import json_safe


# Readings no longer have a warmup state (the compile step is accounted
# separately), but the key survives for JSONL schema compatibility.
_WARMUP_COMPAT = False


class StepTimer:
    """Windowed throughput meter: items/s and items/s/chip.

    ``tick(items)`` per step; every ``window`` steps it blocks on the
    given array (or skips the sync if none) and emits a reading.

    The FIRST tick is special: it carries compile (or AOT-load) time, so
    it is timed separately — the timer blocks on ``sync``, records the
    wall time as ``compile_s``, and excludes that step from every
    throughput window instead of letting it poison the first reading.
    ``compile_s`` is emitted once, in the first reading after it is
    known.

    Historical note: readings used to flag their first window as
    ``warmup`` and every consumer had to branch on it; splitting the
    compile step out made the flag constant-False and the branches dead,
    so they are gone.  The key itself stays (see ``_WARMUP_COMPAT``) so
    existing JSONL consumers keyed on it don't break.
    """

    def __init__(self, window: int = 50, n_chips: int | None = None):
        self.window = window
        self.n_chips = n_chips or len(jax.devices())
        self.compile_s: float | None = None
        self._first_pending = True
        self._compile_emitted = False
        self._t0 = time.perf_counter()
        self._items = 0
        self._steps = 0
        self._windows = 0

    def reset(self) -> None:
        """Restart the current window — call after off-path work (eval,
        checkpoint save) so its wall time doesn't pollute the reading.
        The compile-step accounting is not reset: compilation happens
        once per process, not once per window."""
        self._t0 = time.perf_counter()
        self._items = 0
        self._steps = 0

    def tick(self, items: int, sync: object = None) -> dict | None:
        """Record one step of `items` processed; returns a reading dict at
        window boundaries, else None."""
        if self._first_pending:
            # The compile step: sync NOW so its wall time is attributed
            # here and nowhere else, then start the first window clean.
            if sync is not None:
                # ddplint: allow[host-sync] — attributes compile wall time
                jax.block_until_ready(sync)
            t1 = time.perf_counter()
            self.compile_s = t1 - self._t0
            self._first_pending = False
            self._t0 = t1
            return None
        self._items += items
        self._steps += 1
        if self._steps < self.window:
            return None
        if sync is not None:
            # ddplint: allow[host-sync] — window boundary only, by design
            jax.block_until_ready(sync)
        t1 = time.perf_counter()
        dt = t1 - self._t0
        reading = {
            "items_per_s": self._items / dt,
            "items_per_s_per_chip": self._items / dt / self.n_chips,
            "steps_per_s": self._steps / dt,
            "window_s": dt,
            "warmup": _WARMUP_COMPAT,
        }
        if self.compile_s is not None and not self._compile_emitted:
            reading["compile_s"] = round(self.compile_s, 3)
            self._compile_emitted = True
        self._t0 = t1
        self._items = 0
        self._steps = 0
        self._windows += 1
        return reading


class FaultCounters:
    """Run-level fault accounting — the observability face of the
    fault-tolerance subsystem (``training.fault_tolerance``).

    Mutated by the resilient checkpointer (IO retries, corrupt-step
    fallbacks), the train loop (skipped non-finite steps), the watchdog,
    and the supervisor; ``summary()`` goes into the end-of-run log so a
    run that survived faults SAYS so — silent recovery hides operational
    signal (a climbing retry count is a failing filesystem).
    """

    def __init__(self):
        self.nonfinite_steps = 0
        self.io_retries = 0
        self.ckpt_fallbacks = 0
        self.watchdog_fires = 0
        self.restarts = 0
        # Silent-data-corruption defense (training.integrity): checks
        # are routine probes (not faults — excluded from ``total`` like
        # warm-start accounting); detections and evictions are faults.
        self.sdc_checks = 0
        self.sdc_detects = 0
        self.sdc_evictions = 0
        # Warm-start accounting (training.warm_start): how this
        # incarnation got its train step — "aot" (loaded executable),
        # "cache-hit" (persistent compile cache), "cold" (full compile),
        # "jit"/"jit-fallback" — and the wall seconds to the first step.
        # Not faults, so excluded from ``total``; surfaced in summary()
        # so a respawn that silently recompiles is visible per attempt.
        self.warm_start_mode: str | None = None
        self.compile_s: float | None = None

    @property
    def total(self) -> int:
        return (
            self.nonfinite_steps + self.io_retries + self.ckpt_fallbacks
            + self.watchdog_fires + self.restarts
            + self.sdc_detects + self.sdc_evictions
        )

    def summary(self) -> dict:
        out = {
            "nonfinite_steps": self.nonfinite_steps,
            "ckpt_io_retries": self.io_retries,
            "ckpt_fallbacks": self.ckpt_fallbacks,
            "watchdog_fires": self.watchdog_fires,
            "restarts": self.restarts,
        }
        if self.sdc_checks or self.sdc_detects or self.sdc_evictions:
            out["sdc_checks"] = self.sdc_checks
            out["sdc_detects"] = self.sdc_detects
            out["sdc_evictions"] = self.sdc_evictions
        if self.warm_start_mode is not None:
            out["warm_start"] = self.warm_start_mode
        if self.compile_s is not None:
            # compile_s may arrive as a numpy scalar or nan (warm-start
            # timing of a failed acquisition); round() keeps those alive,
            # so coerce — this dict goes into the JSONL event log.
            out["first_step_s"] = round(float(self.compile_s), 3)
        return json_safe(out)


# Peak bidirectional ICI bandwidth per chip, bytes/s.  Used as the
# utilization denominator; override per platform.  Public figures:
# v5e 2x(4x100GB/s links)/2 ≈ 186 GB/s usable per chip for all-reduce
# rings; v5p ≈ 3x of that.  These are denominators for a *relative*
# utilization number, not absolute truth — record which one was used.
ICI_PEAK_BYTES_PER_S = {
    "tpu v5 lite": 186e9,
    "tpu v5e": 186e9,
    "tpu v5p": 540e9,
    "tpu v4": 270e9,
    "cpu": 50e9,  # loopback ballpark so the probe stays meaningful in CI
}


def _peak_for(device) -> float | None:
    """Known ICI peak for the device kind, or None (unknown hardware —
    better no utilization number than one against a wrong denominator)."""
    kind = getattr(device, "device_kind", "cpu").lower()
    for key, bw in ICI_PEAK_BYTES_PER_S.items():
        if key in kind:
            return bw
    return None


def allreduce_bandwidth(
    mesh=None,
    *,
    size_mb: float = 64.0,
    iters: int = 10,
    axis_name: str = "data",
) -> dict:
    """Measure gradient all-reduce bandwidth over the mesh's data axis.

    Times a jit'd ``lax.pmean`` of a ``size_mb`` float32 buffer (the shape
    of DDP's bucket all-reduce) and reports **bus bandwidth** in the NCCL
    convention — ``busbw = 2*(N-1)/N * bytes / t`` — which is the number
    comparable against link peaks, plus utilization against the
    platform's ICI peak (None/0 on unknown hardware).  With one device
    the collective is a no-op and utilization reads 0 — the probe is only
    meaningful on a multi-chip axis.
    """
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from distributeddataparallel_tpu.runtime.distributed import make_mesh

    if mesh is None:
        mesh = make_mesh((axis_name,))
    n = mesh.shape[axis_name]
    nbytes = int(size_mb * 1e6)
    x = jnp.ones((nbytes // 4,), jnp.float32)

    fn = jax.jit(
        jax.shard_map(
            lambda x: lax.pmean(x, axis_name),
            mesh=mesh,
            in_specs=P(),
            out_specs=P(),
            check_vma=False,
        )
    )
    out = fn(x)
    # ddplint: allow[host-sync] — bandwidth probe timing fence
    jax.block_until_ready(out)  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    # ddplint: allow[host-sync] — bandwidth probe timing fence
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters

    bus_bytes = 2 * (n - 1) / max(n, 1) * nbytes
    bw = bus_bytes / dt
    peak = _peak_for(mesh.devices.flat[0])
    return {
        "devices": n,
        "payload_mb": size_mb,
        "time_s": dt,
        "bus_bw_gb_s": bw / 1e9,
        "peak_gb_s": peak / 1e9 if peak else None,
        "utilization": bw / peak if (peak and n > 1) else 0.0,
    }


def overlap_probe(
    loss_fn,
    state,
    batch,
    rng=None,
    *,
    mesh,
    iters: int = 8,
    axis_name: str = "data",
    with_model_state: bool = False,
) -> dict:
    """Measure how much of the gradient all-reduce hides under backward.

    DDP's defining perf property is the bucketed all-reduce overlapping
    the remaining backward (SURVEY.md §3.4); the XLA analog is the
    latency-hiding scheduler overlapping the grad psum with the backward
    computation.  This probe quantifies it with three timings:

    - ``step_ms``:    the full DP train step (compute + overlapped comm)
    - ``compute_ms``: the same step with ``grad_sync=False`` (no_sync
                      analog — identical compute, zero grad comm)
    - ``comm_ms``:    a bare all-reduce of the exact gradient pytree

    ``overlap_frac = (compute + comm - step) / comm`` — 1.0 when the
    collective is fully hidden under compute, 0.0 when the step serializes
    them.  On a single-device axis the collective is a no-op and the probe
    reports ``comm_ms ~ 0`` with ``overlap_frac = None``.
    """
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from distributeddataparallel_tpu.training.train_step import make_train_step

    if rng is None:
        rng = jax.random.PRNGKey(0)
    n = mesh.shape[axis_name]

    def timed(fn, *args):
        # ddplint: allow[host-sync] — the fence IS the measurement
        jax.block_until_ready(fn(*args))  # compile + warm
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = fn(*args)
        # ddplint: allow[host-sync] — the fence IS the measurement
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    kwargs = dict(
        mesh=mesh, axis_name=axis_name, donate=False,
        with_model_state=with_model_state,
    )
    full = make_train_step(loss_fn, **kwargs)
    nosync = make_train_step(loss_fn, grad_sync=False, **kwargs)
    step_ms = timed(full, state, batch, rng)
    compute_ms = timed(nosync, state, batch, rng)

    grads_like = jax.tree.map(jnp.zeros_like, state.params)
    comm_fn = jax.jit(
        jax.shard_map(
            lambda t: jax.tree.map(lambda g: lax.pmean(g, axis_name), t),
            mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False,
        )
    )
    comm_ms = timed(comm_fn, grads_like)

    grad_bytes = sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(state.params)
    )
    overlap = None
    if n > 1 and comm_ms > 0:
        overlap = max(0.0, min(1.0, (compute_ms + comm_ms - step_ms) / comm_ms))
    return {
        "devices": n,
        "grad_mb": round(grad_bytes / 1e6, 2),
        "step_ms": round(step_ms, 3),
        "compute_ms": round(compute_ms, 3),
        "comm_ms": round(comm_ms, 3),
        "overlap_frac": None if overlap is None else round(overlap, 4),
    }
