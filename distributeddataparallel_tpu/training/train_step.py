"""The compiled data-parallel train step — the heart of the framework.

One call of the returned function performs what the reference's per-batch
loop body does across ``world_size`` processes (ref dpp.py:47-53):

    zero_grad → forward → loss → backward (+ bucketed NCCL all-reduce
    overlapped with backward) → optimizer.step()

but as a single jit'd SPMD program over the mesh:

- the batch arrives sharded along the ``data`` axis (one shard per mesh
  position — the role DDP gave to a whole process);
- ``jax.value_and_grad`` replaces the autograd engine + hooks;
- ``lax.pmean`` per leaf over the data axis replaces the Reducer's
  bucketed all-reduce (XLA's combiner merges and schedules them); set
  ``bucket_bytes`` to force explicit DDP-style bucket coalescing instead;
- the optax update replaces ``optimizer.step()`` — replicas stay in
  lockstep because they apply identical averaged grads to identical params;
- gradient accumulation (``accum_steps > 1``) reproduces DDP's
  ``no_sync()``: microbatch grads accumulate locally in a ``lax.scan``;
  the all-reduce fires once, on the accumulation boundary.

The step donates the input state, so parameters and optimizer state are
updated in place in device memory (no copy per step).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributeddataparallel_tpu.observability import scopes
from distributeddataparallel_tpu.parallel.data_parallel import (
    all_reduce_gradients,
    comm_schedule_ir,
)
from distributeddataparallel_tpu.training.state import TrainState

Pytree = Any
# loss_fn(params, batch, rng) -> (scalar loss, aux dict)
LossFn = Callable[[Pytree, Pytree, jax.Array], tuple[jax.Array, dict]]


def make_train_step(
    loss_fn: LossFn,
    *,
    mesh: Mesh,
    axis_name: str = "data",
    accum_steps: int = 1,
    bucket_bytes: int | None = None,
    donate: bool = True,
    with_model_state: bool = False,
    zero: bool | int = False,
    grad_sync: bool = True,
    buffer_sync: str = "mean",
    cp_axis: str | None = None,
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    grad_clip: float | None = None,
    presynced: Callable[[tuple], bool] | None = None,
    grad_compress: str | None = None,
    nonfinite_guard: bool = False,
    integrity_every: int | None = None,
):
    """Build the jit'd DP train step.

    Returns ``step(state, batch, rng) -> (state, metrics)`` where ``batch``
    is a pytree whose leaves have a leading per-replica batch dimension
    (global batch = per-replica batch × num replicas, the reference's
    ``32 × world_size`` rule, ref dpp.py:35) and ``metrics`` contains the
    globally averaged ``loss`` plus anything in the loss_fn's aux dict.

    ``rng`` is folded with the replica index so stochastic layers (dropout,
    etc.) decorrelate across replicas while params stay in lockstep.

    With ``with_model_state=True``, the loss_fn signature becomes
    ``loss_fn(params, model_state, batch, rng) -> (loss, (aux, new_state))``
    — for models with non-gradient state such as BatchNorm running stats.
    ``buffer_sync`` picks how replicas keep those buffers consistent:

    - ``"mean"`` (default): average the stats across the data axis each
      step — SyncBN-flavored, uses every replica's batch statistics.
    - ``"broadcast"``: adopt replica 0's buffers everywhere — exactly
      DDP's ``broadcast_buffers=True`` semantics (rank 0's running stats
      win, the other replicas' updates are discarded).  Choose this for
      bit-level parity with the reference's training behavior.

    ``bucket_bytes`` sizes the plain-DP gradient exchange: ``None``
    (default) reduces leaf by leaf, a size coalesces the leaves into
    reverse-order f32 buckets first (``parallel.data_parallel``).
    Composes with ``accum_steps`` (the reduction still fires once per
    boundary) and ``grad_clip``.

    ``grad_compress="bf16"`` is the bf16 comm hook (torch DDP's
    ``bf16_compress_hook`` analog): gradient buckets cross the wire in
    bfloat16 and decompress back after the average — half the f32 wire
    bytes, same exponent range so no loss scaling.  Composes with
    ``bucket_bytes``/``accum_steps``/``grad_clip`` (the clip
    norm sees the decompressed averaged grads, matching torch's
    hook-then-clip order).  For scanned models syncing in-body, set
    ``TransformerConfig.grad_sync_compress`` for the presynced leaves.

    ``grad_compress="powersgd"`` is the low-rank comm hook (torch DDP's
    ``powerSGD_hook`` analog, ``parallel.powersgd``): matrix-shaped
    gradients all-reduce as rank-r factors with per-replica error
    feedback — orders of magnitude fewer wire bytes.  Build the state
    with ``comm_state=powersgd_state(params, n_data, rank)``; the hook
    state (warm Q + residual) updates once per sync boundary and is
    checkpointed with the rest of the state.  Lossy by design: replicas
    stay in exact lockstep, training tracks dense DP closely
    (``tests/test_powersgd.py``); does not compose with ``presynced``,
    and is REJECTED with ``tp_axis``/``ep_axis``: the hook's factor
    all-reduce and error-feedback state are data-axis-only — a
    TP/EP-sharded gradient leaf would be compressed per model-shard with
    no cross-shard consistency, silently corrupting the low-rank
    approximation rather than degrading gracefully.

    ``zero`` selects the weight-update sharding level (``parallel.zero``,
    arXiv 2004.13336).  ``True``/``1``: ZeRO-1 — grads reduce_scatter as
    one flat vector, the update runs on each replica's 1/N shard,
    updated params all_gather back; ``state`` must come from
    ``zero_state``; mutually exclusive with ``bucket_bytes``.
    ``2``: the BUCKETED layout — grads leave backward via per-bucket
    reduce-scatter (the full reduced f32 gradient vector never
    materializes), update on the shard, per-bucket all-gather back;
    ``bucket_bytes`` now sets the bucket granularity (must match
    ``zero_state(level=2, bucket_bytes=...)``).  ``3``: additionally
    params STAY sharded
    between steps (``Zero3Params``) and re-gather bucketwise inside the
    differentiated function at the top of each step, so AD's transpose
    of the gather reduce-scatters the grads; the state never holds a
    replicated param tree.  Levels 2/3 shard over the data axis only
    (no tp/ep composition — use level 1 or fsdp for that); both expose
    their scatter/gather stream as a ``comm_schedule`` IR for SL302.

    ``presynced`` (a predicate on gradient-leaf key paths, e.g.
    ``lambda path: path[0] == "layers"``) marks leaves whose gradients
    the MODEL already reduced over the data axis —
    ``TransformerConfig.grad_sync_axis`` reduces the scanned blocks'
    grads inside the backward scan body, the only place they exist
    before the loop stacks them.  The step then syncs only the remaining
    leaves; re-reducing an averaged gradient would be a numeric no-op
    but pays the full wire bytes twice.

    ``grad_sync=False`` is the ``DDP.no_sync()`` analog: gradients are NOT
    averaged across the data axis — each replica applies its local grads
    and params diverge.  For manual accumulation schemes outside the
    compiled step, and for the comm/compute overlap probe
    (``utils.metrics.overlap_probe``), which times this compute-only
    variant against the full step.

    ``cp_axis`` adds context parallelism: batch leaves arrive sharded
    (batch-dim → ``axis_name``, seq-dim → ``cp_axis``, all rank >= 2) and
    the model must attend collectively over the sequence axis
    (``TransformerConfig.cp_axis``, ring attention).  Gradients are first
    pmean'd over ``cp_axis`` — that reduction COMPLETES the gradient of
    the sequence-sharded loss (it is model math, not DP sync, so it
    happens even under ``grad_sync=False``) — then flow through the
    normal data-axis machinery, so accumulation, bucketing, and ZeRO-1
    all compose with CP unchanged.

    ``tp_axis`` adds tensor parallelism (``parallel.tensor_parallel``):
    params/opt-state arrive sharded by ``tp_state_specs`` (build the
    state with ``shard_state_tp``), the batch is replicated over the
    axis, and the model must set ``TransformerConfig.tp_axis``.  Thanks
    to the copy/reduce operator pair inside the model, every gradient
    leaf comes out complete per position — sharded leaves as their local
    shard, replicated leaves identically everywhere — so the data-axis
    sync needs no TP-awareness.  ``zero=True`` composes: the flat-chunk
    machinery operates on each position's LOCAL param shard (uniform
    along the data axis, identical flat offsets across model positions),
    so elementwise updates keep replicated leaves in lockstep while
    optimizer state shards n_data × n_tp ways; build the state with
    ``zero_state(..., tp_axis=...)``.

    ``grad_clip`` clips the synced gradient to a global L2 norm (the
    ``torch.nn.utils.clip_grad_norm_`` analog, applied after the
    all-reduce exactly as DDP users do).  Under ``zero=True`` the norm
    is computed psum-exactly over the flat chunks.  Under tp/ep_axis the
    norm is axis-aware (``model_axes_sumsq`` / duplicate-de-weighted
    flat chunks): sharded leaves psum over their model axes, replicated
    leaves count once — every position computes the same global norm, so
    the scale stays uniform.

    ``nonfinite_guard=True`` adds the numerical fault guard: before any
    gradient leaves this position (sync, compression hook, optimizer),
    the step computes a mesh-uniform "all gradients finite" bit
    (``lax.pmin`` across the data and model axes, so every position
    reaches the same verdict).  On a bad step the gradients are zeroed —
    a NaN must never reach the powersgd error-feedback state or the
    wire — and the ENTIRE state update is discarded (params, optimizer
    moments, model buffers, comm hook state all keep their old values;
    zeroed grads would still move Adam's moments, so masking grads alone
    is not a skip).  Only ``state.step`` advances, and the step reports
    ``metrics['nonfinite_grad']`` (0.0/1.0) for host-side accounting —
    ``training.fault_tolerance.NonFiniteBreaker`` turns a run of them
    into a hard stop.  This is the torch ``GradScaler.step``-skip analog
    for bf16/f32 training, where there is no loss scale to shrink.

    ``integrity_every=N`` arms the silent-data-corruption probe
    (``training.integrity``): every N steps the program digests the bit
    patterns of its INPUT state (params + optimizer moments + buffers;
    params only under ZeRO-1) and all_gathers the per-rank digests —
    one sub-kilobyte collective on cadence, nothing off cadence.  On a
    row mismatch the update is discarded nonfinite-guard-style (the
    corrupt rank's gradients already entered the all-reduce) and the
    step reports ``metrics['sdc_mismatch']`` (0.0/1.0) plus the
    ``metrics['sdc_digest']`` matrix for host-side majority-vote
    attribution and eviction (dpp.py --integrity-every).

    ``ep_axis`` adds expert parallelism for MoE configs
    (``parallel.expert_parallel``): expert weight stacks shard over the
    axis, the batch replicates, and — as with TP — the MoE module's
    copy/reduce operators complete every gradient, so no extra sync is
    needed here.  TP and EP compose (disjoint parameter sets), and
    ``zero=True`` composes with both by the same local-flat-shard
    argument (build the state with ``zero_state(..., ep_axis=...)``).
    """
    zero_level = int(zero)
    if zero_level not in (0, 1, 2, 3):
        raise ValueError(f"zero={zero!r} (want False/True or a level 0-3)")
    if zero_level == 1 and bucket_bytes is not None:
        # Level 1's single monolithic flat has no buckets to size;
        # levels 2/3 take it as their bucket granularity.
        raise ValueError("zero=1 does its own reduction; drop "
                         "bucket_bytes (or use zero=2/3, whose bucketed "
                         "stream it sizes)")
    if zero_level >= 2 and (tp_axis is not None or ep_axis is not None):
        raise ValueError(
            "zero=2/3 shard over the data axis only; compose tp/ep with "
            "zero=1 or the fsdp path"
        )
    if presynced is not None and (zero or not grad_sync):
        # ZeRO's reduce_scatter SUMS shards: feeding it leaves the model
        # already averaged would divide those grads by the axis size
        # twice.  grad_sync=False skips the step's sync entirely, so a
        # skip-list is meaningless there.
        raise ValueError("presynced requires grad_sync=True and zero=False")
    if not grad_sync and (zero or bucket_bytes is not None):
        raise ValueError("grad_sync=False skips the reduction entirely; "
                         "it does not compose with zero/bucket_bytes")
    if grad_compress not in (None, "bf16", "powersgd"):
        raise ValueError(
            f"grad_compress must be None, 'bf16' or 'powersgd'; got "
            f"{grad_compress!r}"
        )
    if grad_compress is not None and (zero or not grad_sync):
        # ZeRO owns its reduce_scatter; compressing there is a separate
        # (unimplemented) path — reject rather than silently not compress.
        raise ValueError("grad_compress requires grad_sync=True and "
                         "zero=False")
    if grad_compress == "powersgd" and presynced is not None:
        # The in-scan-body sync reduces layer grads dense before the
        # hook could see them — the two mechanisms don't compose.
        raise ValueError("grad_compress='powersgd' does not compose with "
                         "presynced (in-scan-body grad sync)")
    if grad_compress == "powersgd" and (
        tp_axis is not None or ep_axis is not None
    ):
        # The hook all-reduces low-rank factors over the DATA axis only
        # and its error-feedback state carries no model-axis sharding:
        # a TP/EP-sharded leaf would be compressed per model shard with
        # no cross-shard agreement on the factors — silent corruption,
        # not graceful degradation.  Reject like presynced/zero above.
        raise ValueError("grad_compress='powersgd' does not compose with "
                         "tp_axis/ep_axis: the low-rank factor reduction "
                         "and error-feedback state are data-axis-only")
    if grad_clip is not None and not grad_sync:
        # Unsynced per-replica grads have per-replica norms: clipping
        # would scale each replica differently (same divergence as the
        # tp/ep case).  Clip in the manual scheme instead.
        raise ValueError("grad_clip requires grad_sync=True")
    if buffer_sync not in ("mean", "broadcast"):
        # No "local" mode: model state is declared replicated (out_specs
        # P()), so per-replica divergent buffers would be silently
        # inconsistent — unlike DDP's broadcast_buffers=False, where each
        # process legitimately owns its module.
        raise ValueError(
            f"buffer_sync must be 'mean' or 'broadcast'; got {buffer_sync!r}"
        )
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    if integrity_every is not None:
        # SDC replica fingerprint (training.integrity): digest the INPUT
        # state every N steps and all_gather the per-rank digests.  The
        # probe's premise is that post-allreduce state is bitwise-
        # replicated across the data axis, so it only composes with
        # layouts that keep it that way: synced grads, replicated or
        # ZeRO-1 params (levels 2/3 shard the comparable state away),
        # no model axes (TP/EP-sharded leaves differ per position by
        # construction, and CP's second axis would give each data rank
        # cp_size distinct digest buffers).
        if integrity_every < 1:
            raise ValueError(
                f"integrity_every must be >= 1, got {integrity_every}"
            )
        if not grad_sync:
            raise ValueError(
                "integrity_every requires grad_sync=True: unsynced "
                "replicas legitimately diverge, so a digest mismatch "
                "means nothing"
            )
        if zero_level >= 2:
            raise ValueError(
                "integrity_every needs bitwise-replicated state to "
                "compare; zero=2/3 shard it — use zero<=1"
            )
        if tp_axis is not None or ep_axis is not None or cp_axis is not None:
            raise ValueError(
                "integrity_every composes with the data axis only; "
                "tp/ep/cp-sharded layouts have no replicated digest "
                "domain over 'data' alone"
            )

    # Compilation-affecting factory flags, attached to the returned step
    # as ``aot_signature`` — the warm-start store (training.warm_start)
    # folds this into the executable's invalidation key, so a flag change
    # (say, donation on → off) can never silently reuse a stale binary.
    # ``presynced`` is a predicate whose identity is process-local; the
    # key can only honestly record its presence.
    aot_signature = {
        "factory": "make_train_step",
        "axis_name": axis_name,
        "accum_steps": accum_steps,
        "bucket_bytes": bucket_bytes,
        "donate": donate,
        "with_model_state": with_model_state,
        "zero": zero_level,
        "grad_sync": grad_sync,
        "buffer_sync": buffer_sync,
        "cp_axis": cp_axis,
        "tp_axis": tp_axis,
        "ep_axis": ep_axis,
        "grad_clip": grad_clip,
        "presynced": presynced is not None,
        "grad_compress": grad_compress,
        "nonfinite_guard": nonfinite_guard,
        "integrity_every": integrity_every,
    }

    # FLOP-accounting handoff for the MFU meter (observability.cost_model).
    # The one fact only this factory knows: accumulation SPLITS the batch
    # into accum_steps microbatches of B/accum_steps — it does not repeat
    # it — so per-step FLOPs equal one full-batch pass regardless of the
    # accumulation degree.  Recording it here means a meter wired to this
    # step cannot double-count microbatches.
    flop_signature = {
        "train_flop_multiplier": 3,  # fwd + ~2x bwd (PaLM appendix B)
        "accum_steps": accum_steps,
        "microbatch_fraction": 1.0 / accum_steps,
        "loss_evals_per_step": accum_steps,
    }

    # Expected-collective manifest for the graph linter
    # (analysis.graph_lint): which gradient-sized collectives this
    # configuration is SUPPOSED to lower to, per mesh axis.  Kept next
    # to aot_signature because they answer the same question at
    # different layers — "what program did this factory promise?".
    from distributeddataparallel_tpu.analysis.rules import (
        collective_manifest,
    )

    _any_coll = {
        p: (0, None)
        for p in ("psum", "reduce_scatter", "psum_scatter", "all_gather",
                  "ppermute", "all_to_all")
    }
    if zero_level:
        # All levels promise reduce_scatter in, all_gather out.  Levels
        # 2/3 additionally promise NO gradient-sized dense psum survives
        # lowering: with no model-state buffers to sync, every psum in
        # the program is a scalar (loss/metrics/clip-norm), so the
        # nonscalar-psum bound is EXACTLY zero — a reintroduced dense
        # all-reduce fails GL001 by count, not just SF201 by size.
        ps = (0, None) if (with_model_state or zero_level == 1) else (0, 0)
        _reduce = {axis_name: {"reduce_scatter": (1, None),
                               "all_gather": (1, None),
                               "psum": ps}}
    elif not grad_sync:
        # no_sync analog: gradients stay per-replica; scalar metric
        # pmeans are uncounted, so just declare the axis with no floor.
        _reduce = {axis_name: {"psum": (0, None)}}
    else:
        _reduce = {axis_name: {"psum": (1, None)}}
    if integrity_every is not None:
        # The SDC digest adds exactly one data-axis all_gather (the
        # stacked per-leaf digest vector, inside the cadence cond — the
        # linter walks cond branches, so it is statically visible every
        # build).  Declared here so GL001 stays EXACT: on the plain-DP
        # path the bound is (1, 1) — a duplicated digest gather is a
        # finding, same as a duplicated grad sync; ZeRO-1 already
        # gathers its updated params, so its floor moves up by one.
        if zero_level:
            _reduce[axis_name]["all_gather"] = (2, None)
        else:
            _reduce[axis_name]["all_gather"] = (1, 1)
    for ax in (cp_axis, tp_axis, ep_axis):
        if ax is not None:
            _reduce.setdefault(ax, dict(_any_coll))
    # The unbucketed leaf-wise layout is exactly countable: one psum per
    # param leaf, no more (a second sync is the classic 2x-wire bug).
    _exact = (
        grad_sync and not zero and bucket_bytes is None
        and grad_compress is None and not with_model_state
        and not nonfinite_guard and grad_clip is None
    )
    collective_manifest_ = collective_manifest(
        ("zero" if zero_level == 1 else f"zero{zero_level}")
        if zero_level else "dp",
        grad_reduce=_reduce,
        donate=donate,
        # coalesced buckets and ZeRO master flats legitimately reduce f32
        allow_f32_reduce=bool(bucket_bytes or zero or grad_compress),
        per_leaf_axes=(axis_name,) if _exact else (),
    )

    def _micro(lf, params, model_state, mb, rng):
        """One microbatch: returns (loss, aux, new_model_state, grads).
        ``lf`` is the (possibly wrapped) loss function — zero3 passes a
        wrapper that gathers the flat param shard first, so the grads
        here are the flat cotangent, already reduce-scattered by the
        gather's transpose."""
        if with_model_state:
            (loss, (aux, new_ms)), grads = jax.value_and_grad(
                lf, has_aux=True
            )(params, model_state, mb, rng)
        else:
            (loss, aux), grads = jax.value_and_grad(lf, has_aux=True)(
                params, mb, rng
            )
            new_ms = model_state
        return loss, aux, new_ms, grads

    def _replica_step(state: TrainState, batch: Pytree, rng: jax.Array):
        # Runs per mesh position under shard_map: `batch` is this replica's
        # shard; params/opt state are replicated.
        orig_state = state  # pre-update snapshot for the nonfinite guard
        idx = lax.axis_index(axis_name)
        rng = jax.random.fold_in(rng, idx)
        if cp_axis is not None:
            rng = jax.random.fold_in(rng, lax.axis_index(cp_axis))

        if zero_level == 3:
            # Differentiate w.r.t. the flat shard: the bucketwise gather
            # runs INSIDE the loss, so backward's transpose of it IS the
            # per-bucket reduce-scatter of the grads (sum semantics —
            # zero3_update divides by the axis size).
            from distributeddataparallel_tpu.parallel.zero import (
                zero3_gather,
            )

            _meta = state.params.meta
            if with_model_state:
                lf = lambda flat, ms, mb, r: loss_fn(
                    zero3_gather(flat, _meta, axis_name), ms, mb, r
                )
            else:
                lf = lambda flat, mb, r: loss_fn(
                    zero3_gather(flat, _meta, axis_name), mb, r
                )
            params_in = state.params.flat
        else:
            lf = loss_fn
            params_in = state.params

        if accum_steps == 1:
            loss, aux, new_ms, grads = _micro(
                lf, params_in, state.model_state, batch, rng
            )
        else:
            # no_sync analog: accumulate locally, reduce once at the end.
            for leaf in jax.tree.leaves(batch):
                if leaf.shape[0] % accum_steps != 0:
                    raise ValueError(
                        f"per-replica batch {leaf.shape[0]} is not divisible "
                        f"by accum_steps={accum_steps}; choose a batch size "
                        f"that is a multiple of accum_steps"
                    )
            micro = jax.tree.map(
                lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:]),
                batch,
            )

            def body(carry, xs):
                acc_grads, acc_loss, acc_aux, ms = carry
                mb, step_rng = xs
                l, a, ms, g = _micro(lf, params_in, ms, mb, step_rng)
                acc_grads = jax.tree.map(jnp.add, acc_grads, g)
                return (acc_grads, acc_loss + l, jax.tree.map(jnp.add, acc_aux, a), ms), None

            # Zero-initialized carry with structure from eval_shape (no
            # second trace of the model: the fwd+bwd is compiled once, in
            # the scan body).
            first_mb = jax.tree.map(lambda x: x[0], micro)
            l_s, a_s, _, g_s = jax.eval_shape(
                functools.partial(_micro, lf),
                params_in, state.model_state, first_mb, rng
            )
            zeros = lambda t: jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), t
            )
            rngs = jax.vmap(lambda i: jax.random.fold_in(rng, i))(
                jnp.arange(accum_steps)
            )
            (grads, loss, aux, new_ms), _ = lax.scan(
                body,
                (zeros(g_s), zeros(l_s), zeros(a_s), state.model_state),
                (micro, rngs),
            )
            inv = 1.0 / accum_steps
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss = loss * inv
            aux = jax.tree.map(lambda a: a * inv, aux)

        if cp_axis is not None:
            # Complete the seq-sharded gradient: each position's loss saw
            # only its sequence chunk; the replicated params' gradient is
            # the mean over chunks.  Loss/aux likewise become global.
            with jax.named_scope(scopes.GRAD_SYNC):
                grads = jax.tree.map(lambda g: lax.pmean(g, cp_axis), grads)
            with jax.named_scope(scopes.METRICS):
                loss = lax.pmean(loss, cp_axis)
                aux = jax.tree.map(lambda a: lax.pmean(a, cp_axis), aux)

        if nonfinite_guard:
            # Decide BEFORE any gradient leaves this position: a NaN must
            # never reach the wire, the powersgd error-feedback state, or
            # ZeRO's reduce_scatter.  pmin over the data + model axes
            # makes the verdict mesh-uniform — every position skips (or
            # applies) together, keeping replicas in lockstep.  (cp_axis
            # needs no pmin: grads were just pmean'd over it, so all CP
            # positions already hold identical values.)
            ok = jnp.bool_(True)
            for g in jax.tree.leaves(grads):
                ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
            fin = ok.astype(jnp.float32)
            for ax in (axis_name, tp_axis, ep_axis):
                if ax is not None:
                    fin = lax.pmin(fin, ax)
            ok = fin > 0
            # Zeroed (not masked-out) grads keep every downstream path —
            # sync, compression, clip, update — shape- and control-flow-
            # identical; the state select below undoes their effect.
            grads = jax.tree.map(
                lambda g: jnp.where(ok, g, jnp.zeros_like(g)), grads
            )

        if zero_level == 1:
            # ZeRO-1: reduce_scatter + sharded update + all_gather.
            from distributeddataparallel_tpu.parallel.zero import zero_update

            maxes = tuple(
                ax for ax in (tp_axis, ep_axis) if ax is not None
            )
            lspecs = None
            if maxes and grad_clip is not None:
                from distributeddataparallel_tpu.parallel.expert_parallel import (
                    model_axes_param_specs,
                )

                lspecs = model_axes_param_specs(grads, tp_axis, ep_axis)
            new_params, new_opt_state = zero_update(
                grads, state, axis_name, mesh.shape[axis_name],
                clip_norm=grad_clip, model_axes=maxes, local_specs=lspecs,
            )
            new_state = state.replace(
                step=state.step + 1, params=new_params,
                opt_state=new_opt_state,
            )
        elif zero_level == 2:
            # ZeRO-2: bucketed reduce-scatter straight into the shard,
            # sharded update, bucketed all-gather back.
            from distributeddataparallel_tpu.parallel.zero import (
                bucket_plan,
                zero2_update,
            )

            plan = bucket_plan(
                state.params, mesh.shape[axis_name], bucket_bytes
            )
            new_params, new_opt_state = zero2_update(
                grads, state, axis_name, mesh.shape[axis_name], plan,
                clip_norm=grad_clip,
            )
            new_state = state.replace(
                step=state.step + 1, params=new_params,
                opt_state=new_opt_state,
            )
        elif zero_level == 3:
            # ZeRO-3: grads arrived flat and reduce-scattered (gather
            # transpose); the updated shard IS the next state's params.
            from distributeddataparallel_tpu.parallel.zero import (
                Zero3Params,
                zero3_update,
            )

            new_flat, new_opt_state = zero3_update(
                grads, state, axis_name, mesh.shape[axis_name],
                clip_norm=grad_clip,
            )
            new_state = state.replace(
                step=state.step + 1,
                params=Zero3Params(flat=new_flat, meta=_meta),
                opt_state=new_opt_state,
            )
        else:
            if grad_sync:
                # THE DDP moment: average grads across the data axis.
                if grad_compress == "powersgd":
                    # Low-rank comm hook: factors all-reduce instead of
                    # the gradient matrices; hook state (warm Q + error
                    # feedback) rides in state.comm_state.
                    from distributeddataparallel_tpu.parallel.powersgd import (
                        powersgd_sync,
                    )

                    with jax.named_scope(scopes.GRAD_SYNC):
                        grads, new_comm = powersgd_sync(
                            grads, state.comm_state, axis_name
                        )
                    state = state.replace(comm_state=new_comm)
                elif presynced is None:
                    grads = all_reduce_gradients(
                        grads, axis_name, op="mean",
                        bucket_bytes=bucket_bytes, compress=grad_compress,
                    )
                else:
                    # Model-synced leaves (grad_sync_axis: reduced inside
                    # the backward scan body) pass through; the step
                    # reduces only the rest (embeddings/head/final norm).
                    flat, treedef = jax.tree_util.tree_flatten_with_path(
                        grads
                    )
                    keys = [
                        tuple(
                            getattr(k, "key", getattr(k, "idx", str(k)))
                            for k in path
                        )
                        for path, _ in flat
                    ]
                    rest = [
                        leaf for (path, leaf), k in zip(flat, keys)
                        if not presynced(k)
                    ]
                    rest = iter(all_reduce_gradients(
                        rest, axis_name, op="mean",
                        bucket_bytes=bucket_bytes, compress=grad_compress,
                    ))
                    grads = jax.tree.unflatten(
                        treedef,
                        [
                            leaf if presynced(k) else next(rest)
                            for (path, leaf), k in zip(flat, keys)
                        ],
                    )
            if grad_clip is not None:
                from distributeddataparallel_tpu.parallel.data_parallel import (
                    clip_scale,
                    model_axes_sumsq,
                    sumsq_f32,
                )

                with jax.named_scope(scopes.GRAD_CLIP):
                    if tp_axis is not None or ep_axis is not None:
                        # Megatron/expert shards: per-leaf-spec-aware
                        # global norm — sharded leaves psum over their
                        # model axes, replicated leaves (complete per
                        # position) count once.  The result is identical
                        # on every position, so the scale is uniform.
                        from distributeddataparallel_tpu.parallel.expert_parallel import (
                            model_axes_param_specs,
                        )

                        sumsq = model_axes_sumsq(
                            grads,
                            model_axes_param_specs(grads, tp_axis, ep_axis),
                        )
                    else:
                        # Grads are complete per position here (post sync
                        # / cp pmean), so the local norm IS the global
                        # norm.
                        sumsq = sumsq_f32(grads)
                    scale = clip_scale(jnp.sqrt(sumsq), grad_clip)
                    grads = jax.tree.map(lambda g: g * scale, grads)
            with jax.named_scope(scopes.OPTIMIZER):
                new_state = state.apply_gradients(grads)
        if with_model_state:
            sync_axes = (axis_name,) + (
                (cp_axis,) if cp_axis is not None else ()
            )
            if buffer_sync == "mean":
                # SyncBN-flavored: average the stats across replicas.
                for ax in sync_axes:
                    new_ms = jax.tree.map(
                        lambda s, a=ax: lax.pmean(s, a), new_ms
                    )
            elif buffer_sync == "broadcast":
                # DDP broadcast_buffers: everyone adopts position 0's
                # buffers.  Mask to position (0[, 0]) ONCE, then psum over
                # every sync axis — re-masking between psums would zero
                # the value on non-zero data ranks before the second
                # reduction ever sees it.
                is_zero = lax.axis_index(axis_name) == 0
                if cp_axis is not None:
                    is_zero = jnp.logical_and(
                        is_zero, lax.axis_index(cp_axis) == 0
                    )

                def _bcast(s):
                    s = jnp.where(is_zero, s, jnp.zeros_like(s))
                    for ax in sync_axes:
                        s = lax.psum(s, ax)
                    return s

                new_ms = jax.tree.map(_bcast, new_ms)
            new_state = new_state.replace(model_state=new_ms)
        if integrity_every is not None:
            # Replica fingerprint of the INPUT state, taken before this
            # step's all-reduce could spread a corrupt rank's gradients.
            # Off cadence the cond's zero branch runs — no collective
            # executes, no host sync is implied, and the all-zero matrix
            # trivially satisfies the row-equality verdict below.
            # check_vma=False means each position digests ITS OWN buffer
            # of the "replicated" state — physical divergence is the
            # signal; the gathered matrix is identical on every rank, so
            # the verdict is mesh-uniform without further reduction.
            from distributeddataparallel_tpu.training.integrity import (
                digest_parts,
                tree_digest,
            )

            _dg_parts = digest_parts(orig_state, zero_level)
            _n_rows = mesh.shape[axis_name]
            _n_leaves = len(jax.tree.leaves(_dg_parts))
            sdc_digests = lax.cond(
                orig_state.step % integrity_every == 0,
                lambda _: lax.all_gather(tree_digest(_dg_parts), axis_name),
                lambda _: jnp.zeros((_n_rows, _n_leaves), jnp.uint32),
                operand=None,
            )
            sdc_ok = jnp.all(sdc_digests == sdc_digests[0:1])
        if nonfinite_guard or integrity_every is not None:
            # Skip-step semantics: zeroed grads still advance Adam's
            # moments and weight decay, so masking grads alone is not a
            # skip — discard the WHOLE update (params, optimizer moments,
            # buffers, comm hook state) and let only the step counter
            # advance, mirroring torch GradScaler's skipped step.  The
            # digest verdict rides the SAME select (a mismatching rank's
            # gradients already entered this step's reduction, so
            # applying the update would bake the corruption into every
            # replica; the host-side voter evicts the liar before the
            # next update lands).  Folding both verdicts into one
            # whole-state select — keep = finite AND replicas-agree —
            # means arming integrity on top of the nonfinite guard adds
            # only the cadence-gated digest, not a second state-sized
            # select: the select fuses with the update's final write,
            # and its cost is paid once however many guards are on.
            keep = jnp.bool_(True)
            if nonfinite_guard:
                keep = jnp.logical_and(keep, ok)
            if integrity_every is not None:
                keep = jnp.logical_and(keep, sdc_ok)
            new_state = jax.tree.map(
                lambda n, o: jnp.where(keep, n, o), new_state, orig_state
            )
            new_state = new_state.replace(step=orig_state.step + 1)
        with jax.named_scope(scopes.METRICS):
            metrics = {"loss": lax.pmean(loss, axis_name)}
            metrics.update(
                {k: lax.pmean(v, axis_name) for k, v in aux.items()}
            )
        if nonfinite_guard:
            # Already mesh-uniform (pmin above): no further reduction.
            metrics["nonfinite_grad"] = 1.0 - fin
        if integrity_every is not None:
            # sdc_mismatch: 0.0/1.0 verdict (mesh-uniform).  sdc_digest:
            # the full (n_ranks, n_leaves) matrix for host-side majority
            # vote — only fetched on cadence AND mismatch, so it costs
            # no host sync on the happy path.
            metrics["sdc_mismatch"] = 1.0 - sdc_ok.astype(jnp.float32)
            metrics["sdc_digest"] = sdc_digests
        return new_state, metrics

    # Params/opt-state replicated (P()), batch sharded on the data axis
    # (and the seq axis under CP), rng replicated; outputs replicated.
    #
    # check_vma=False: with varying-manual-axes tracking on, the AD
    # transpose of replicated (unvarying) params inserts an implicit psum,
    # so grads would arrive pre-summed and the explicit reduction below
    # would silently become a no-op (sum semantics = world_size× the DDP
    # learning rate).  This framework keeps the DDP-style *explicit* sync
    # point — grads stay per-replica until all_reduce_gradients — which is
    # also what makes the bucketed variant possible.
    batch_spec = (
        P(axis_name, cp_axis) if cp_axis is not None else P(axis_name)
    )
    jit_kwargs = {"donate_argnums": (0,)} if donate else {}

    def _attach_comm_schedule(fn):
        # Schedule-as-data for the SL3xx linter: bucketed grad sync
        # exposes its bucket order as a builder (the partition
        # depends on the param tree, so it can't be a constant like the
        # pipeline tick tables).  Compressed sync reduces factors, not
        # buckets — no IR.
        if zero_level >= 2:
            # zero2's lintable hop stream is the per-bucket grad
            # reduce-scatter (once per step, outside any accum scan);
            # zero3's is the per-bucket param all-gather, which runs
            # inside the microbatch — so its tick count multiplies by
            # accum_steps, exactly as the traced-hop counter sees it.
            from distributeddataparallel_tpu.analysis.schedule_lint import (
                grad_sync_schedule_ir,
            )
            from distributeddataparallel_tpu.parallel.zero import (
                Zero3Params,
                bucket_plan,
            )

            prim = "reduce_scatter" if zero_level == 2 else "all_gather"

            def _zero_cs(params):
                if isinstance(params, Zero3Params):
                    nb = params.meta.plan.n_buckets
                else:
                    nb = bucket_plan(
                        params, mesh.shape[axis_name], bucket_bytes
                    ).n_buckets
                ticks = nb * (accum_steps if zero_level == 3 else 1)
                return grad_sync_schedule_ir(
                    ticks, axis=axis_name, prim=prim
                )

            fn.comm_schedule = _zero_cs
        elif (
            grad_sync and not zero_level and grad_compress is None
            and bucket_bytes is not None
        ):
            fn.comm_schedule = lambda params: comm_schedule_ir(
                params, bucket_bytes=bucket_bytes, axis=axis_name
            )
        return fn

    if (
        not zero_level and tp_axis is None and ep_axis is None
        and grad_compress != "powersgd"
    ):
        sharded = jax.shard_map(
            _replica_step,
            mesh=mesh,
            in_specs=(P(), batch_spec, P()),
            out_specs=(P(), P()),
            check_vma=False,
        )
        jitted = jax.jit(sharded, **jit_kwargs)
        jitted.aot_signature = aot_signature
        jitted.flop_signature = flop_signature
        jitted.collective_manifest = collective_manifest_
        return _attach_comm_schedule(jitted)

    # ZeRO / TP / EP: the state's leaves carry per-leaf shardings (ZeRO:
    # flat opt chunks over the data axis; TP/EP: Megatron/expert layouts
    # over their model axes), so the spec tree depends on the state
    # structure — build on first call (jit caches thereafter).
    compiled = None

    def _build(state: TrainState):
        nonlocal compiled
        if compiled is None:
            if zero_level:
                from distributeddataparallel_tpu.parallel.zero import (
                    state_specs,
                )

                specs = state_specs(state, axis_name, tp_axis, ep_axis)
            else:
                from distributeddataparallel_tpu.parallel.expert_parallel import (
                    model_axes_state_specs,
                )

                specs = model_axes_state_specs(state, tp_axis, ep_axis)
            if grad_compress == "powersgd":
                from distributeddataparallel_tpu.parallel.powersgd import (
                    powersgd_state_specs,
                )

                # Distinguish "never initialized" ({} / None / empty
                # containers) from "initialized, nothing above the
                # compression floor" (a params-shaped tree of None
                # ENTRIES — valid: every leaf syncs dense).  Leaf count
                # is 0 for both, so count entries instead.
                from distributeddataparallel_tpu.parallel.powersgd import (
                    _is_entry,
                )

                entries = jax.tree.flatten(
                    state.comm_state, is_leaf=_is_entry
                )[0]
                if state.comm_state is None or not entries:
                    raise ValueError(
                        "grad_compress='powersgd' needs hook state: build "
                        "the TrainState with comm_state=powersgd_state("
                        "params, n_data, rank) (parallel.powersgd)"
                    )
                specs = specs.replace(
                    comm_state=powersgd_state_specs(
                        state.comm_state, axis_name
                    )
                )
            sharded = jax.shard_map(
                _replica_step,
                mesh=mesh,
                in_specs=(specs, batch_spec, P()),
                out_specs=(specs, P()),
                check_vma=False,
            )
            compiled = jax.jit(sharded, **jit_kwargs)
        return compiled

    def step(state: TrainState, batch: Pytree, rng: jax.Array):
        return _build(state)(state, batch, rng)

    # AOT access to the SAME jit (specs included): evidence harnesses
    # lower the real step for a multi-chip TPU topology with abstract
    # state (parallel.expert_parallel.ep_memory_evidence).
    step.lower = lambda state, batch, rng: _build(state).lower(
        state, batch, rng
    )
    step.aot_signature = aot_signature
    step.flop_signature = flop_signature
    step.collective_manifest = collective_manifest_
    return _attach_comm_schedule(step)


def make_eval_step(
    metric_fn: Callable[..., dict],
    *,
    mesh: Mesh,
    axis_name: str = "data",
    with_model_state: bool = False,
    masked: bool = False,
    param_specs=None,
):
    """Jit'd eval step: per-replica metrics pmean'd across the data axis.

    ``metric_fn(params, batch)`` or, with model state,
    ``metric_fn(params, model_state, batch)``.  The reference has no
    evaluation at all (SURVEY.md §2d.5); this is the beyond-parity minimum
    for the BASELINE configs.

    ``masked=True``: exact evaluation over sampler-padded batches
    (``DataLoader(with_mask=True)``).  The batch dict carries a per-row
    ``"valid"`` mask (0 on padded duplicate rows); metric_fn must return
    PER-ROW vectors (shape (local_rows,), e.g. ``per_example_cross_entropy``)
    and the step returns ``(metrics, count)``: the global masked means and
    the global valid-row count.  Padded rows contribute to neither, and
    weighting each batch's means by its returned count reduces exactly to
    the mean over unique samples — no host-side knowledge of the sampler's
    pad geometry required.

    ``param_specs``: per-leaf PartitionSpec tree for TP-sharded params
    (``tp_param_specs``) — evaluation then runs on the sharded params
    directly (metric_fn built on the TP model) instead of gathering a
    replicated copy.  Default: params replicated.
    """

    def _replica_eval(params: Pytree, model_state: Pytree, batch: Pytree):
        if masked:
            batch = dict(batch)
            mask = batch.pop("valid")
        if with_model_state:
            metrics = metric_fn(params, model_state, batch)
        else:
            metrics = metric_fn(params, batch)
        if masked:
            from distributeddataparallel_tpu.parallel.data_parallel import (
                masked_tree_mean,
            )

            return masked_tree_mean(metrics, mask, axis_name)
        return jax.tree.map(lambda m: lax.pmean(m, axis_name), metrics)

    sharded = jax.shard_map(
        _replica_eval,
        mesh=mesh,
        in_specs=(param_specs if param_specs is not None else P(), P(),
                  P(axis_name)),
        out_specs=P(),
        check_vma=False,
    )
    jitted = jax.jit(sharded)
    if with_model_state:
        return jitted
    return lambda params, batch: jitted(params, {}, batch)
