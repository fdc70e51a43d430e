"""Process-group runtime: the TPU-native analog of ``torch.distributed``.

The reference calls ``dist.init_process_group("nccl", rank=..., world_size=...)``
(ref dpp.py:20-21) and ``dist.destroy_process_group()`` (ref dpp.py:23-24),
with env:// TCPStore rendezvous and one process per GPU.

On TPU the shape of the world is different and this module embraces that:

- One **process per host**, each owning all its local chips
  (``jax.local_devices()``), instead of one process per device.
- Rendezvous is ``jax.distributed.initialize`` — auto-configured on Cloud
  TPU VMs, explicit ``coordinator_address`` elsewhere — replacing the
  reference's TCPStore + MASTER_ADDR/MASTER_PORT env vars (which the
  reference never sets; see SURVEY.md §2d.1 — our init is self-contained).
- There is no user-visible communicator object: collectives are XLA ops
  (``lax.psum`` et al.) compiled into the training step and scheduled over
  ICI/DCN by XLA.

Single-process use (one host, or CPU with
``--xla_force_host_platform_device_count=N`` fake devices) requires no
rendezvous at all; ``init_process_group`` detects this and is a no-op
beyond recording state.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh


@dataclasses.dataclass
class _ProcessGroupState:
    initialized: bool = False
    multi_process: bool = False
    backend: str = "tpu"


_STATE = _ProcessGroupState()

#: ``--device`` values whose ``jax.Device.platform`` is spelled otherwise
_PLATFORM_OF = {"cuda": "gpu"}


def select_device(device: str = "auto", fake_devices: int = 0) -> None:
    """Pick the JAX platform for this process (the ``--device`` flag of
    every entry point).  Must run before a backend initializes.

    A named device is an instruction, not a preference: it replaces
    whatever ``JAX_PLATFORMS`` says, and ``device_summary(device)`` —
    called once the backend is up — refuses any other platform.
    ``auto`` leaves JAX's own choice (``JAX_PLATFORMS`` when set) alone.
    """
    if fake_devices:
        if device not in ("auto", "cpu"):
            raise SystemExit("--fake-devices requires --device cpu")
        from distributeddataparallel_tpu.compat import configure_cpu_devices

        configure_cpu_devices(fake_devices)
    elif device != "auto":
        jax.config.update("jax_platforms", device)


def device_summary(expect: str = "auto") -> dict:
    """``{"platform", "kind", "count"}`` of the devices JAX runs on —
    initializing the backend if need be — or ``SystemExit`` when
    ``expect`` names a platform and JAX found anything else."""
    try:
        devs = jax.devices()
    except RuntimeError as exc:
        if expect == "auto":
            raise
        raise SystemExit(
            f"--device {expect}: JAX found no {expect!r} platform on this "
            f"machine (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
            f"is not consulted): {exc}"
        ) from exc
    found = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if expect != "auto" and found["platform"] != _PLATFORM_OF.get(
        expect, expect
    ):
        raise SystemExit(
            f"--device {expect}: JAX is running on platform "
            f"{found['platform']!r} ({found['kind']} x{found['count']}), "
            f"not {expect!r}"
        )
    return found


def init_process_group(
    backend: str | None = None,
    *,
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    local_device_ids: Sequence[int] | None = None,
) -> None:
    """Initialize the distributed runtime (analog of ref dpp.py:21).

    Unlike the reference — which requires the caller to export
    MASTER_ADDR/MASTER_PORT and crashes otherwise (SURVEY.md §2d.1) — this
    is self-contained:

    - If explicit coordinator args are given, or the environment announces a
      multi-process job (``JAX_COORDINATOR_ADDRESS`` / Cloud TPU metadata),
      run ``jax.distributed.initialize`` for control-plane rendezvous.
    - Otherwise run single-process: all devices are local, no rendezvous.

    ``backend`` is advisory ("tpu", "cpu", "cuda"); the CLI layer picks
    the platform with ``select_device`` before this runs.
    """
    if _STATE.initialized:
        raise RuntimeError(
            "init_process_group called twice; call destroy_process_group first"
        )

    explicit = coordinator_address is not None or num_processes is not None
    # A TPU VM exports CLOUD_TPU_TASK_ID / TPU_WORKER_ID on single hosts
    # too; only a roster of several workers announces a pod, and only a
    # pod may call the argument-less (metadata-driven) rendezvous — on a
    # sealed single host it would wait for peers that do not exist.
    pod = (
        "CLOUD_TPU_TASK_ID" in os.environ
        and len(os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")) > 1
    )
    env_multiproc = (
        os.environ.get("JAX_COORDINATOR_ADDRESS")
        or os.environ.get("JAX_NUM_PROCESSES")
        or pod
    )
    announced = [
        k for k in (
            "CLOUD_TPU_TASK_ID", "TPU_WORKER_ID", "JAX_COORDINATOR_ADDRESS"
        ) if k in os.environ
    ]
    if announced:
        from distributeddataparallel_tpu.utils.logging import get_logger

        get_logger().info(
            "init_process_group: environment sets %s -> %s",
            ", ".join(announced),
            "rendezvous" if explicit or env_multiproc
            else "single process, no rendezvous",
        )

    if explicit or env_multiproc:
        # jax.distributed.initialize does NOT read the JAX_COORDINATOR_*
        # env vars itself (only cluster auto-detection, e.g. Cloud TPU
        # metadata) — resolve the launcher's env contract here so a
        # spawned child needs no explicit arguments.
        if coordinator_address is None:
            coordinator_address = os.environ.get("JAX_COORDINATOR_ADDRESS")
        if num_processes is None and "JAX_NUM_PROCESSES" in os.environ:
            num_processes = int(os.environ["JAX_NUM_PROCESSES"])
        if process_id is None and "JAX_PROCESS_ID" in os.environ:
            process_id = int(os.environ["JAX_PROCESS_ID"])
        kwargs = {}
        if coordinator_address is not None:
            kwargs["coordinator_address"] = coordinator_address
        if num_processes is not None:
            kwargs["num_processes"] = num_processes
        if process_id is not None:
            kwargs["process_id"] = process_id
        if local_device_ids is not None:
            kwargs["local_device_ids"] = list(local_device_ids)
        jax.distributed.initialize(**kwargs)
        _STATE.multi_process = True

    _STATE.initialized = True
    _STATE.backend = backend or jax.default_backend()


def destroy_process_group() -> None:
    """Tear down the distributed runtime (analog of ref dpp.py:23-24)."""
    if _STATE.multi_process:
        jax.distributed.shutdown()
    _STATE.initialized = False
    _STATE.multi_process = False


def reinit_after_resize(
    *,
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Re-establish ``jax.distributed`` after a membership-epoch resize.

    On a real multi-host fleet an elastic resize changes the PROCESS
    world, not just the mesh: the control plane must be torn down and
    re-initialized with the survivors' new (size, id) assignment — the
    rendezvous store agreed on the roster, this turns that agreement
    into a live jax.distributed world.  Arguments default to the
    ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` env the caller (launcher
    resize-respawn, or the hostgang member itself) re-exported for the
    new epoch.

    Single-process (the CPU-simulation gangs): a no-op beyond state —
    there is no control plane to cycle, the resize is an in-process mesh
    rebuild.
    """
    was_multi = _STATE.multi_process
    if _STATE.initialized:
        destroy_process_group()
    if not was_multi and not (
        coordinator_address
        or num_processes
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
    ):
        _STATE.initialized = True
        return
    init_process_group(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def is_initialized() -> bool:
    return _STATE.initialized


def get_rank() -> int:
    """Process index (the analog of the reference's per-process ``rank``).

    Note the unit change: the reference's rank is per *device* (1 proc/GPU,
    ref dpp.py:62); here it is per *host* — devices within a host are
    addressed through the mesh, not through process identity.
    """
    return jax.process_index()


def get_world_size() -> int:
    """Number of processes (hosts), not devices."""
    return jax.process_count()


def local_device_count() -> int:
    return jax.local_device_count()


def global_device_count() -> int:
    return len(jax.devices())


def make_mesh(
    axes: Sequence[str] = ("data",),
    shape: Sequence[int] | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build the device mesh that replaces the reference's process group.

    With the default 1-D ``('data',)`` axis over all addressable devices this
    is the direct analog of the NCCL communicator created at ref dpp.py:21 —
    the set of participants in gradient all-reduce. Multi-axis meshes (e.g.
    ``('data', 'model')``) are supported so the same runtime carries tensor/
    sequence-parallel extensions without redesign.

    ``shape`` defaults to putting all devices on the first axis.
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs)
    if shape is None:
        shape = (n,) + (1,) * (len(axes) - 1)
    shape = tuple(shape)
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh shape {shape} does not cover {n} devices")
    mesh_devices = np.asarray(devs, dtype=object).reshape(shape)
    return Mesh(mesh_devices, tuple(axes))


def topology_fingerprint(mesh: Mesh | None = None) -> dict:
    """Identity of the device world an executable was compiled for.

    The warm-start store (``training.warm_start``) keys serialized
    executables on this: an XLA binary is specific to the platform,
    device kind, device count, process layout, and — when a mesh is
    given — the mesh's axis names and shape.  Everything here is plain
    JSON so keys compare by value across processes.
    """
    devs = (
        list(mesh.devices.flat) if mesh is not None else list(jax.devices())
    )
    fp = {
        "platform": devs[0].platform if devs else jax.default_backend(),
        "device_kind": getattr(devs[0], "device_kind", "?") if devs else "?",
        "n_devices": len(devs),
        "process_count": jax.process_count(),
    }
    if mesh is not None:
        fp["mesh_axes"] = list(mesh.axis_names)
        fp["mesh_shape"] = [int(mesh.shape[a]) for a in mesh.axis_names]
    return fp


def tpu_topology_mesh(topology: str = "v5e:2x4", axis_names=("data",),
                      shape=None):
    """An n-chip TPU Mesh from an AOT topology description — no multi-chip
    hardware required (``jax.experimental.topologies``).  Programs built
    on this mesh can be ``.lower().compile()``d (not run) to inspect what
    the real TPU compiler does at scale."""
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name=topology)
    devs = np.array(topo.devices)
    if shape is None:
        shape = (devs.size,) if len(axis_names) == 1 else None
    return Mesh(devs.reshape(shape), axis_names)


def compiler_stamp() -> dict:
    """Version stamp for AOT-evidence artifacts: which compiler produced
    the program an artifact describes.  Evidence without a stamp can't be
    audited across toolchain bumps."""
    stamp = {"jax": jax.__version__}
    try:
        import jaxlib

        stamp["jaxlib"] = jaxlib.__version__
    except ImportError:  # pragma: no cover - jaxlib always ships with jax
        pass
    try:
        stamp["backend_platform_version"] = jax.extend.backend.get_backend(
        ).platform_version
    except (RuntimeError, AttributeError):
        pass  # AOT-only processes may have no addressable backend
    return stamp


def barrier(name: str = "ddp_tpu_barrier") -> None:
    """Block until all processes reach this point.

    The reference has no explicit barrier (NCCL init is its implicit one);
    this is provided for host-side coordination (e.g. checkpoint writes).
    Single-process: trivially returns.  Multi-process: a true global sync
    over all devices via multihost_utils.
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)
