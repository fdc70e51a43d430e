from distributeddataparallel_tpu.parallel.sampler import DistributedSampler  # noqa: F401
from distributeddataparallel_tpu.parallel.data_parallel import (  # noqa: F401
    DataParallel,
    all_reduce_gradients,
    broadcast_params,
    bucket_gradients,
)
from distributeddataparallel_tpu.parallel.context_parallel import (  # noqa: F401
    cp_positions,
    make_cp_eval_step,
    make_cp_train_step,
    ring_attention,
    ulysses_attention,
)
from distributeddataparallel_tpu.parallel.powersgd import (  # noqa: F401
    powersgd_state,
    powersgd_state_specs,
    powersgd_sync,
    powersgd_wire_bytes,
)
from distributeddataparallel_tpu.parallel.zero import zero_state  # noqa: F401
from distributeddataparallel_tpu.parallel.tensor_parallel import (  # noqa: F401
    copy_to_tp,
    reduce_from_tp,
    shard_state_tp,
    tp_param_specs,
    tp_state_specs,
)
from distributeddataparallel_tpu.parallel.pipeline_parallel import (  # noqa: F401
    make_pp_eval_step,
    make_pp_train_step,
    pp_param_specs,
    pp_state_specs,
    shard_state_pp,
)
from distributeddataparallel_tpu.parallel.expert_parallel import (  # noqa: F401
    ep_param_specs,
    ep_state_specs,
    shard_state_ep,
)
from distributeddataparallel_tpu.parallel.fsdp import (  # noqa: F401
    fsdp_gather_params,
    fsdp_state,
    make_fsdp_eval_step,
    make_fsdp_train_step,
)
