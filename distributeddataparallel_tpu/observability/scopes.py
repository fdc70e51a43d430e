"""Names the program writes into what it compiles: ``jax.named_scope``
names on the parts of a train step that no Flax module names, and the
names of the Pallas kernels.  They reach the HLO ``op_name`` metadata of
every operation traced under them (``jit(step)/jvp(M)/head/dot_general``)
and, through it, the device trace.

What Flax names itself is relied on, not re-wrapped: ``layer_<i>`` (or
``layers/block`` under ``scan_layers``), ``attn``, ``mlp`` and the norms.
Forward and backward need no scope either: JAX writes ``jvp(`` and
``transpose(jvp(`` into ``op_name``.  Nor does the forward that remat
runs a second time inside the backward: JAX writes ``RECOMPUTE`` there.

Module-import rule: stdlib only (see schema.py).
"""

from __future__ import annotations

#: token + position embedding (``models/transformer.py``)
EMBED = "embed"
#: the output projection, tied ``dot_general`` or ``LMHead``
HEAD = "head"
#: ``ops/losses.py``: the cross entropies
LOSS = "loss"
#: accuracy, and the step's ``pmean`` of loss and aux
METRICS = "metrics"
#: the gradient exchange over the data axis: bucket packing, the
#: collective and unpacking (also the cp-axis and ZeRO exchanges)
GRAD_SYNC = "grad_sync"
GRAD_CLIP = "grad_clip"
#: ``state.apply_gradients`` (and ZeRO's sharded update)
OPTIMIZER = "optimizer"

#: the three ``pallas_call``s of ``ops/pallas_attention.py``
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"

#: the five parts of ``models.transformer.Mamba2Mixer`` (a Flax module
#: named ``mamba``: ``layer_<i>/mamba/<part>/...``); a tuple of their own,
#: read by the benchmark's ``mixer_scopes`` and not by its bucket table
SSM_IN_PROJ = "ssm_in_proj"
SSM_CONV = "ssm_conv"
SSD = "ssd"
SSM_GATE_NORM = "ssm_gate_norm"
SSM_OUT_PROJ = "ssm_out_proj"
#: the two ``pallas_call``s of ``ops/ssd.py``, launched under ``SSD``
SSD_FWD = "ssd_fwd"
SSD_BWD = "ssd_bwd"
#: the two ``pallas_call``s of ``ops/causal_conv.py``, launched under
#: ``SSM_CONV``
CONV_FWD = "conv_fwd"
CONV_BWD = "conv_bwd"

#: the five parts of ``models.transformer.MoEMLP`` where it holds a share
#: of the experts (a Flax module named ``mlp``: ``layer_<i>/mlp/<part>/...``):
#: router matmul, scores, ``top_k`` and gate weights; the sort, the group
#: sizes and the row gather; the grouped products and the activation; the
#: gate-weighted scatter-add; the shared expert.  A tuple of their own, read
#: by the benchmark's ``moe_scopes`` and not by its bucket table (which
#: counts all of it under ``mlp``)
MOE_ROUTER = "moe_router"
MOE_DISPATCH = "moe_dispatch"
MOE_EXPERTS = "moe_experts"
MOE_COMBINE = "moe_combine"
MOE_SHARED = "moe_shared"
#: the two ``pallas_call``s of ``ops/grouped_matmul.py``, launched under
#: ``MOE_EXPERTS``: rows times an expert's matrix (and ``dy`` times its
#: transpose), and the matrices' gradient
MOE_GMM = "moe_gmm"
MOE_TGMM = "moe_tgmm"

#: the four parts of ``ops.eva.eva_attention`` inside the Flax module
#: ``attn`` (``layer_<i>/attn/<part>/...``): exact causal attention inside
#: each window (the three flash kernels run under it, on windows folded
#: into the batch), the pooling of each chunk's keys and values into one
#: summary, every query's attention over the summaries of all earlier
#: windows (the flash kernels again, under the staircase rule), and the
#: merge of the two softmaxes under one normaliser.  A tuple of their own,
#: read by the benchmark's ``eva_scopes`` and not by its bucket table
#: (which counts all of it under ``attn`` and the kernels' own buckets)
EVA_LOCAL = "eva_local"
EVA_SUMMARIES = "eva_summaries"
EVA_REMOTE = "eva_remote"
EVA_MERGE = "eva_merge"

#: JAX's own name, relied on and never written here (as ``jvp(`` and
#: ``transpose(jvp(`` are): it marks every operation that ``nn.remat``
#: (``models/transformer.py``, unrolled blocks and the scanned body) or an
#: explicit ``jax.checkpoint`` (the worst-case branch of
#: ``ops/moe.dropless``) runs a second time inside the backward —
#: ``transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/layer_0/mlp/...``.
#: The first forward and the true backward never carry it.  The program's
#: scopes and the kernels' names nest under it, so the recompute splits by
#: the same names as the rest of the step.  A fusion counts under its
#: root's ``op_name``: a recomputed elementwise operation that XLA fuses
#: into a backward matmul counts as backward, and the reading misses it
#: (backward work fused under a recomputed root counts the other way).
#: Read by the benchmark's ``remat_scopes``.
RECOMPUTE = "rematted_computation"

STEP_SCOPES = (EMBED, HEAD, LOSS, METRICS, GRAD_SYNC, GRAD_CLIP, OPTIMIZER)
KERNEL_NAMES = (FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV)
MIXER_SCOPES = (SSM_IN_PROJ, SSM_CONV, SSD, SSM_GATE_NORM, SSM_OUT_PROJ)
SSD_KERNEL_NAMES = (SSD_FWD, SSD_BWD)
CONV_KERNEL_NAMES = (CONV_FWD, CONV_BWD)
MOE_KERNEL_NAMES = (MOE_GMM, MOE_TGMM)
MOE_SCOPES = (MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS, MOE_COMBINE, MOE_SHARED)
EVA_SCOPES = (EVA_LOCAL, EVA_SUMMARIES, EVA_REMOTE, EVA_MERGE)
