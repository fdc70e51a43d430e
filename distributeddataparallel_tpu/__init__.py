"""distributeddataparallel_tpu — a TPU-native data-parallel training framework.

A ground-up re-design of the capabilities exercised by the reference
single-file PyTorch DDP trainer (``/root/reference/dpp.py``), built
TPU-first on JAX/XLA:

- ``runtime``  — process/mesh initialization (the ``init_process_group``
  analog: ``jax.distributed.initialize`` + ``jax.sharding.Mesh`` over ICI),
  and a per-host launcher (the ``mp.spawn`` analog).
- ``parallel`` — data-parallel gradient synchronization (the DDP analog:
  ``psum``/``pmean`` inside a jit'd ``shard_map`` step, bucketed variants),
  and a ``DistributedSampler``-semantics index sharder.
- ``models``   — Flax model zoo: SimpleCNN/ResNet-18/50 (ref dpp.py:11-18),
  GPT-2 124M, Llama-class decoder.
- ``data``     — host-side input pipeline: datasets, prefetching loader,
  global-array assembly from per-host shards.
- ``training`` — functional train step factory, train state, trainer loop,
  Orbax checkpointing.
- ``ops``      — losses, ring attention for sequence/context parallelism,
  Pallas kernels.
- ``utils``    — logging, metrics, profiling helpers.

The single CLI entrypoint lives at the repo root as ``dpp.py``, mirroring
the reference's usage (``python dpp.py``) with a ``--device`` selector.
"""

__version__ = "0.1.0"

from distributeddataparallel_tpu.runtime.distributed import (  # noqa: F401
    init_process_group,
    destroy_process_group,
    get_rank,
    get_world_size,
    local_device_count,
    global_device_count,
    is_initialized,
    make_mesh,
    barrier,
)
from distributeddataparallel_tpu.parallel.sampler import DistributedSampler  # noqa: F401
from distributeddataparallel_tpu.parallel.data_parallel import (  # noqa: F401
    DataParallel,
    all_reduce_gradients,
    broadcast_params,
)
from distributeddataparallel_tpu.parallel.powersgd import (  # noqa: F401
    powersgd_state,
    powersgd_wire_bytes,
)
from distributeddataparallel_tpu.parallel.zero import zero_state  # noqa: F401
from distributeddataparallel_tpu.parallel.tensor_parallel import shard_state_tp  # noqa: F401
from distributeddataparallel_tpu.parallel.expert_parallel import shard_state_ep  # noqa: F401
from distributeddataparallel_tpu.parallel.pipeline_parallel import (  # noqa: F401
    make_pp_train_step,
    shard_state_pp,
)
from distributeddataparallel_tpu.parallel.fsdp import (  # noqa: F401
    fsdp_gather_params,
    fsdp_state,
    make_fsdp_eval_step,
    make_fsdp_train_step,
)
from distributeddataparallel_tpu.training.state import TrainState  # noqa: F401
from distributeddataparallel_tpu.training.train_step import make_train_step  # noqa: F401
