"""Device-memory telemetry: HBM stats, executable memory analysis, and
a live-array high-water mark — sampled at window boundaries only.

Three complementary views of where the bytes went:

- ``device_memory_stats()`` — the runtime allocator's own accounting
  (``device.memory_stats()``: bytes_in_use, peak_bytes_in_use, ...).
  TPU backends report it (its absence there is an error); the CPU
  backend returns None and the caller degrades to the live-array view.
- ``executable_memory_analysis()`` — the compiler's static budget for
  one executable (argument/output/temp/code bytes from
  ``compiled.memory_analysis()``): how much HBM the step NEEDS, known
  before the first real batch.
- ``MemoryTelemetry`` — a runtime high-water-mark probe over
  ``jax.live_arrays()``.  Enumerating live arrays reads host-side
  buffer metadata (shape x dtype), never device values, so sampling
  cannot force a sync — but it IS O(live arrays), which is why the
  probe runs only at throughput-window boundaries, the same cadence
  rule StepTimer's sync follows.  Zero per-step cost.

Module-import rule: stdlib only at module scope (see schema.py); jax is
imported inside the sampling functions.
"""

from __future__ import annotations


def device_memory_stats(devices=None) -> list[dict] | None:
    """Per-device allocator stats for the process-local devices.  None
    only on the CPU backend, which reports none; on an accelerator a
    missing ``memory_stats()`` raises — memory numbers must not silently
    become live-array estimates there.  Keys are normalized to the ones
    every consumer needs; the raw dict is not exposed so a backend
    adding fields can't bloat every event record."""
    import jax

    devices = devices if devices is not None else jax.local_devices()
    out = []
    for d in devices:
        stats = d.memory_stats()
        if not stats:
            if d.platform == "cpu":
                return None
            raise RuntimeError(
                f"{d.platform} device {d.id} ({d.device_kind}) reports no "
                "memory_stats()"
            )
        out.append({
            "device": d.id,
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "peak_bytes_in_use": int(stats.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(stats.get("bytes_limit", 0)),
        })
    return out


def executable_memory_analysis(compiled) -> dict | None:
    """Compiler-side memory budget of one compiled executable
    (``jax.stages.Compiled`` or anything exposing
    ``memory_analysis()``); None when unavailable on the backend."""
    try:
        ma = compiled.memory_analysis()
    # ddplint: allow[broad-except] — optional per backend; degrade to None
    except Exception:
        return None
    if ma is None:
        return None
    fields = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    out = {}
    for f in fields:
        v = getattr(ma, f, None)
        if v is not None:
            out[f.replace("_size_in_bytes", "_bytes")] = int(v)
    return out or None


#: fallback per-chip HBM budget when the backend reports no
#: ``bytes_limit`` (CPU, mesh simulation): one TPU v4 chip's 32 GiB.
#: Mis-sharding checks (SF203) and mesh-sim fit prediction need SOME
#: budget to compare against on backends that have none; v4 is the
#: paper's reference part, and callers can always override.
DEFAULT_HBM_BUDGET_BYTES = 32 * 1024**3


def hbm_budget_bytes(devices=None) -> int:
    """Per-chip HBM budget: the allocator's reported ``bytes_limit``
    (minimum across devices — the tightest chip is the one that OOMs)
    when the backend exposes it, else ``DEFAULT_HBM_BUDGET_BYTES``."""
    stats = device_memory_stats(devices)
    limits = [s["bytes_limit"] for s in stats or [] if s.get("bytes_limit")]
    return min(limits) if limits else DEFAULT_HBM_BUDGET_BYTES


def live_array_bytes() -> tuple[int, int]:
    """(total bytes, array count) across all live jax.Arrays in the
    process.  Host metadata only — never reads a device value."""
    import jax

    total = n = 0
    for a in jax.live_arrays():
        nbytes = getattr(a, "nbytes", None)
        if nbytes:
            total += int(nbytes)
            n += 1
    return total, n


def live_array_bytes_per_device() -> tuple[int, int]:
    """(max per-device live bytes, array count): each array's
    addressable shards are billed to the device that holds them, and
    the busiest device's total is returned.

    THIS is the view that can see sharding: ``live_array_bytes`` sums
    GLOBAL ``nbytes``, under which a P("data")-sharded ZeRO state and a
    replicated one cost the same — global logical bytes don't change
    when the copies do.  Per-device billing is what makes the ZeRO-2/3
    memory win (opt state + params at 1/N per chip) measurable on
    backends without allocator stats.  Still host metadata only: shard
    shape x dtype, never a device value."""
    import math

    import jax

    per: dict = {}
    n = 0
    for a in jax.live_arrays():
        try:
            itemsize = a.dtype.itemsize
            for s in a.addressable_shards:
                dev = getattr(s, "device", None)
                key = getattr(dev, "id", dev)
                per[key] = per.get(key, 0) + int(
                    math.prod(s.data.shape) * itemsize
                )
        # ddplint: allow[broad-except] — committed-to-nothing or
        # donated-away arrays can refuse shard enumeration; bill their
        # global bytes to a pseudo-device rather than drop them
        except Exception:
            per[None] = per.get(None, 0) + int(getattr(a, "nbytes", 0))
        n += 1
    return (max(per.values()) if per else 0), n


class MemoryTelemetry:
    """Window-boundary memory sampler feeding gauges + ``memory`` events.

    ``sample(step)`` is the ONLY recurring entry point and the caller
    contract is the StepTimer rule: call it where the loop already
    drained (throughput-window boundaries), never per step.  Tracks the
    live-array high-water mark across samples — the closest runtime
    analog of "how much HBM did this run actually need" on backends
    without allocator stats.
    """

    def __init__(self, registry=None, events=None, devices=None):
        self.registry = registry
        self.events = events
        self.devices = devices
        self.live_hwm_bytes = 0
        self.live_perdevice_hwm_bytes = 0
        self.device_peak_bytes = 0

    def note_executable(
        self, compiled, *, label: str = "train_step", **facts
    ):
        """Record one executable's compiler memory budget (emits a
        single ``exec_memory`` event, with any other ``facts`` the caller
        read off the same executable); safe to call with anything —
        backends without the API degrade to a no-op."""
        analysis = executable_memory_analysis(compiled)
        if analysis is None:
            return None
        if self.events is not None:
            self.events.emit("exec_memory", label=label, **analysis, **facts)
        if self.registry is not None:
            self.registry.gauge("exec_temp_bytes").set(
                analysis.get("temp_bytes")
            )
        return analysis

    def sample(self, step: int) -> dict:
        """One boundary sample: live-array bytes (+HWM), allocator stats
        when the backend has them.  Pure host metadata reads."""
        live, count = live_array_bytes()
        self.live_hwm_bytes = max(self.live_hwm_bytes, live)
        perdev, _ = live_array_bytes_per_device()
        self.live_perdevice_hwm_bytes = max(
            self.live_perdevice_hwm_bytes, perdev
        )
        out = {
            "step": step,
            "live_bytes": live,
            "live_arrays": count,
            "live_hwm_bytes": self.live_hwm_bytes,
            "live_perdevice_bytes": perdev,
            "live_perdevice_hwm_bytes": self.live_perdevice_hwm_bytes,
        }
        stats = device_memory_stats(self.devices)
        if stats:
            in_use = sum(s["bytes_in_use"] for s in stats)
            peak = max(s["peak_bytes_in_use"] for s in stats)
            self.device_peak_bytes = max(self.device_peak_bytes, peak)
            out["device_bytes_in_use"] = in_use
            out["device_peak_bytes"] = self.device_peak_bytes
            # One entry per device: a replica that never ran shows up
            # as a zero here and nowhere in the max above.
            out["device_peak_bytes_each"] = [
                s["peak_bytes_in_use"] for s in stats
            ]
        if self.registry is not None:
            g = self.registry.gauge
            g("mem_live_bytes").set(live)
            g("mem_live_hwm_bytes").set(self.live_hwm_bytes)
            g("mem_live_perdevice_bytes").set(perdev)
            g("mem_live_perdevice_hwm_bytes").set(
                self.live_perdevice_hwm_bytes
            )
            if stats:
                g("mem_device_bytes_in_use").set(out["device_bytes_in_use"])
                g("mem_device_peak_bytes").set(self.device_peak_bytes)
        if self.events is not None:
            self.events.emit("memory", **out)
        return out
