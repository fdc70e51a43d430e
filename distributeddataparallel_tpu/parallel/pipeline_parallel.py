"""Pipeline parallelism: GPipe-style stage sharding over a ``pipe`` mesh
axis (beyond-reference scope, completing the DP/TP/CP/PP axis set).

The TPU-native shape of PP exploits a property this framework already
has: with ``scan_layers=True`` the decoder stack's parameters are
STACKED arrays with a leading layer dimension, so "split the model into
stages" is literally "shard that leading dim over the pipe axis" — each
mesh position holds ``L / n_stages`` layers and runs the same scanned
block code on its slice.

Three schedules share the stage sharding (``make_pp_train_step(...,
schedule=)``): GPipe (default, below), 1F1B
(``_pp_1f1b_loss_and_grads`` — interleaved manual backward, O(stages)
activation memory instead of O(microbatches); see its docstring), and
``zb`` — a ZB-H1-style zero-bubble variant of 1F1B that splits each
backward into an activation-grad unit B (critical path) and a
weight-grad unit W, rendered as three segmented scans so warm-up ticks
never execute a dead backward slot and drain ticks never execute a
dead forward slot.  The per-stage useful-slot counters the schedules
carry make the bubble MEASURED (``pp_phase_counts`` in the step
metrics), not just analytic.

The GPipe schedule inside ``shard_map``:

- The per-position batch splits into M microbatches.  Each tick, stage 0
  injects the next microbatch's embeddings, every stage applies its
  layer slice, and activations rotate one hop with ``lax.ppermute``
  (XLA overlaps the transfer with the next tick's compute).
- After ``n_stages - 1`` warm-up ticks the pipe is full; the last stage
  computes logits + loss for one microbatch per tick.  Bubble ticks
  process don't-care buffers whose results never reach the loss, so AD
  gives them zero cotangents — and the BACKWARD pipeline (reverse
  schedule, reverse ppermute) emerges entirely from differentiating the
  forward loop; no hand-written reverse schedule exists anywhere.
- Replicated parameters (embeddings, final norm, lm head) get gradient
  contributions only on the stages that use them (0 and n-1); a psum
  over the pipe axis completes them.  Layer-slice gradients are local by
  construction.  The data axis then applies the ordinary DDP mean.

Restrictions: ``scan_layers=True`` configs without dropout.  DP, TP
(``cfg.tp_axis``), and CP (``cfg.cp_axis``, ring attention with
host-side input/target split) all compose with the pipeline; the
microbatch loop is itself the gradient-accumulation analog.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any


def pp_param_specs(
    tree: Pytree,
    axis_name: str = "pipe",
    tp_axis: str | None = None,
    ep_axis: str | None = None,
) -> Pytree:
    """Spec tree: any leaf under a ``layers`` path component shards its
    LEADING (stacked-layer) dim over the pipe axis; everything else is
    replicated.  Works for optimizer state too (optax trees embed the
    param paths).

    With ``tp_axis``/``ep_axis`` the Megatron / expert trailing-dim rules
    compose underneath (disjoint leaf sets): a stacked q_proj kernel
    becomes e.g. ``P('pipe', None, 'model', None)``, a stacked expert
    weight ``P('pipe', 'expert', None, None)``.
    """
    from distributeddataparallel_tpu.parallel import (
        expert_parallel,
        tensor_parallel,
    )

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    treedef = jax.tree.structure(tree)
    specs = []
    for path, leaf in flat:
        names = tuple(
            str(getattr(k, "key", getattr(k, "name", k))) for k in path
        )
        if "layers" in names and getattr(leaf, "ndim", 0) >= 1:
            trailing = (None,) * (leaf.ndim - 1)
            for axis, rule in (
                (tp_axis, tensor_parallel._spec_for_path),
                (ep_axis, expert_parallel._spec_for_path),
            ):
                if axis is None:
                    continue
                inner = rule(names, leaf, axis)
                if any(inner):
                    # Right-aligned partition of the trailing dims (the
                    # leading dim is the stacked layer axis).
                    trailing = tuple(inner)[-(leaf.ndim - 1):]
                    break
            specs.append(P(*((axis_name,) + trailing)))
        else:
            specs.append(P())
    return jax.tree.unflatten(treedef, specs)


def pp_state_specs(
    state,
    axis_name: str = "pipe",
    tp_axis: str | None = None,
    ep_axis: str | None = None,
) -> Pytree:
    """Spec tree for a whole TrainState under PP (single source for both
    placement and the step's shard_map in_specs)."""
    return state.replace(
        step=P(),
        params=pp_param_specs(state.params, axis_name, tp_axis, ep_axis),
        opt_state=pp_param_specs(state.opt_state, axis_name, tp_axis, ep_axis),
        model_state=jax.tree.map(lambda _: P(), state.model_state),
    )


def interleave_layer_perm(L: int, n: int, v: int) -> "np.ndarray":
    """Storage order of the stacked layer dim for interleaved 1F1B.

    With ``v`` virtual chunks per stage (Megatron-LM interleaved
    schedule, arXiv 2104.04473 §2.3), stage ``s`` owns the round-robin
    layer chunks ``{s, s+n, ..., s+(v-1)n}`` (chunk length
    ``Lc = L/(n·v)``) — non-contiguous in logical layer order.  The
    stacked dim shards CONTIGUOUSLY over the pipe axis, so placement
    permutes rows so that position ``s``'s contiguous block is its v
    chunks in chunk-major order.  Returns ``perm`` with
    ``stored = logical[perm]``; invert with ``np.argsort(perm)``.
    """
    import numpy as np

    Lc = L // (n * v)
    perm = np.empty((L,), np.int64)
    i = 0
    for s in range(n):
        for c in range(v):
            base = (c * n + s) * Lc
            perm[i : i + Lc] = np.arange(base, base + Lc)
            i += Lc
    return perm


def shard_state_pp(
    state,
    mesh: Mesh,
    axis_name: str = "pipe",
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    virtual: int = 1,
):
    """Place a full TrainState with the stacked layer dim sharded over the
    pipe axis (the PP analog of ``broadcast_params``).

    ``virtual > 1`` (interleaved 1F1B): the stacked layer dim of every
    ``layers`` leaf — params AND optimizer state (optax trees embed the
    param paths) — is stored in ``interleave_layer_perm`` order before
    placement, so each pipe position's contiguous shard is its v
    round-robin chunks.  Invert with the perm's argsort when gathering
    params back to the logical model layout.
    """
    import numpy as np

    n = mesh.shape[axis_name]
    for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]:
        names = tuple(str(getattr(k, "key", k)) for k in path)
        if "layers" in names and leaf.shape[0] % (n * virtual):
            raise ValueError(
                f"pipeline: stacked layer dim {leaf.shape[0]} of param "
                f"{'/'.join(names)} is not divisible by {n} stages"
                + (f" x {virtual} virtual chunks" if virtual > 1 else "")
            )
    if virtual > 1:
        def permute_layers(tree):
            flat = jax.tree_util.tree_flatten_with_path(tree)
            out = []
            for path, leaf in flat[0]:
                names = tuple(str(getattr(k, "key", k)) for k in path)
                if "layers" in names and getattr(leaf, "ndim", 0) >= 1:
                    perm = interleave_layer_perm(leaf.shape[0], n, virtual)
                    leaf = jnp.asarray(leaf)[np.asarray(perm)]
                out.append(leaf)
            return jax.tree.unflatten(flat[1], out)

        state = state.replace(
            params=permute_layers(state.params),
            opt_state=permute_layers(state.opt_state),
        )
    if ep_axis is not None:
        from distributeddataparallel_tpu.parallel.expert_parallel import (
            check_ep_divisibility,
        )

        check_ep_divisibility(state.params, mesh, ep_axis)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        state,
        pp_state_specs(state, axis_name, tp_axis, ep_axis),
    )


def _stage_stack(cfg, n_stages: int):
    """The scanned block module for ONE stage's layer slice — built by
    the same factory TransformerLM uses (``scanned_layer_cls``), so a
    slice of the full model's stacked params applies directly and the
    two can never drift."""
    from distributeddataparallel_tpu.models.transformer import (
        scanned_layer_cls,
    )

    if cfg.num_layers % n_stages:
        raise ValueError(
            f"pipeline: num_layers {cfg.num_layers} not divisible by "
            f"{n_stages} stages"
        )
    return scanned_layer_cls(cfg, cfg.num_layers // n_stages)(cfg)


def _embed(cfg, params, tokens, positions=None):
    """Token (+ learned positional) embedding from raw params — mirrors
    TransformerLM's input block (models/transformer.py) without dropout.

    ``positions``: global token positions of this shard (context
    parallelism); defaults to ``arange(S)``.
    """
    emb = params["token_embed"]["embedding"]  # (V, d) f32
    x = emb[tokens].astype(cfg.dtype)
    if cfg.positional == "learned":
        if positions is None:
            # Static slice (cheaper than a gather-by-iota in the tick loop).
            pos = params["pos_embed"][: tokens.shape[1]]
        else:
            pos = params["pos_embed"][positions]
        x = x + pos.astype(cfg.dtype)
    return x


def _head(cfg, params, x):
    """Final norm + logits from raw params — mirrors TransformerLM's
    output block (f32 logits, cfg.dtype matmul operands)."""
    from distributeddataparallel_tpu.models.transformer import _make_norm

    x = _make_norm(cfg, "final_norm").apply(
        {"params": params["final_norm"]}, x
    )
    if cfg.tie_embeddings:
        w = params["token_embed"]["embedding"].astype(cfg.dtype)  # (V, d)
        return jax.lax.dot_general(
            x.astype(cfg.dtype), w, (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    w = params["lm_head"]["kernel"].astype(cfg.dtype)  # (d, V)
    return jax.lax.dot_general(
        x.astype(cfg.dtype), w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _check_seq_bound(cfg, S: int, n_cp: int = 1) -> None:
    """Same guard TransformerLM.__call__ enforces: past the positional
    table bound, XLA silently CLAMPS RoPE/pos_embed gathers instead of
    erroring — training/eval would proceed on wrong positions."""
    if S * n_cp > cfg.max_seq_len:
        raise ValueError(
            f"global seq len {S * n_cp} > max_seq_len {cfg.max_seq_len}"
        )


def _pipeline_ticks(
    cfg,
    params,
    mbs_in,
    *,
    pp_axis: str,
    n: int,
    microbatches: int,
    run_stage,
    on_output,
    positions=None,
):
    """THE GPipe schedule, shared by the train and eval steps: M + n - 1
    ticks; each tick embeds the next microbatch at stage 0, applies this
    stage's layer slice (``run_stage(x, t, s) -> y``), rotates activations
    one hop, and hands each completed microbatch's last-stage activations
    to ``on_output(mb_index, y, s)``.  Callers accumulate through
    closures; bubble outputs are don't-care values the callers mask on
    ``s == n - 1``, which is what lets AD reconstruct the reverse
    pipeline on its own.
    """
    M = microbatches
    s = lax.axis_index(pp_axis)
    _, mb_rows, S = mbs_in.shape
    perm = [(i, (i + 1) % n) for i in range(n)]
    buf = jnp.zeros((mb_rows, S, cfg.d_model), cfg.dtype)
    for t in range(M + n - 1):
        x0 = _embed(cfg, params, mbs_in[min(t, M - 1)], positions)
        x = jnp.where(s == 0, x0, buf)
        y = run_stage(x, t, s)
        buf = lax.ppermute(y, pp_axis, perm)
        oi = t - (n - 1)
        if oi >= 0:
            on_output(oi, y, s)


def make_pp_eval_step(
    cfg,
    *,
    mesh: Mesh,
    microbatches: int,
    data_axis: str = "data",
    pp_axis: str = "pipe",
):
    """Forward-only pipelined evaluation for a scanned TransformerLM.

    ``eval_step(params, batch) -> (metrics, count)`` with the same
    contract as ``make_eval_step(masked=True)``: ``batch = {"tokens":
    (B_local, S+1), "valid": (B_local,)}`` sharded over ``data_axis``,
    per-row metrics weighted by the valid mask so sampler-padded
    duplicate rows contribute nothing, and the returned count is the
    global number of valid rows.  The microbatch ticks reuse the same
    embed/stack/head pieces as the train pipeline; only the last stage's
    outputs reach the metric sums (masked per position, completed with
    one psum over the pipe).  TP composes exactly as in training.
    """
    from distributeddataparallel_tpu.models.transformer import (
        rope_frequencies,
    )
    from distributeddataparallel_tpu.ops.losses import (
        per_example_accuracy,
        per_example_cross_entropy,
    )

    if not cfg.scan_layers:
        raise ValueError("pipeline parallelism requires scan_layers=True")
    if cfg.cp_axis is not None:
        raise ValueError("pipelined eval does not support cp_axis")
    n_stages = mesh.shape[pp_axis]
    M = microbatches
    stack = _stage_stack(cfg, n_stages)

    def _eval(params, batch):
        toks, valid = batch["tokens"], batch["valid"]
        inputs, targets = toks[:, :-1], toks[:, 1:]
        n = n_stages
        pad = (-inputs.shape[0]) % M
        if pad:
            # Tail batch whose per-position rows don't divide the
            # microbatch count (drop_last=False loaders): pad with
            # valid=0 rows — the mask already zero-weights them, same
            # contract as the non-PP masked eval.
            zrow = jnp.zeros((pad, inputs.shape[1]), inputs.dtype)
            inputs = jnp.concatenate([inputs, zrow])
            targets = jnp.concatenate([targets, zrow])
            valid = jnp.concatenate(
                [valid, jnp.zeros((pad,), valid.dtype)]
            )
        mb_rows = inputs.shape[0] // M
        S = inputs.shape[1]
        _check_seq_bound(cfg, S)
        mbs_in = inputs.reshape(M, mb_rows, S)
        mbs_tgt = targets.reshape(M, mb_rows, S)
        mbs_val = valid.reshape(M, mb_rows).astype(jnp.float32)
        rope = (
            rope_frequencies(
                cfg.dims_per_head, cfg.max_seq_len, theta=cfg.rope_theta
            )
            if cfg.positional == "rope"
            else None
        )
        layer_shard = params["layers"]
        loss_sum = acc_sum = cnt = jnp.zeros((), jnp.float32)

        def run_stage(x, t, s):
            y, _ = stack.apply({"params": layer_shard}, x, None, rope, True)
            return y

        def on_output(oi, y, s):
            nonlocal loss_sum, acc_sum, cnt
            logits = _head(cfg, params, y)
            v = mbs_val[oi]
            on_last = (s == n - 1).astype(jnp.float32)
            loss_sum = loss_sum + on_last * jnp.sum(
                per_example_cross_entropy(logits, mbs_tgt[oi]) * v
            )
            acc_sum = acc_sum + on_last * jnp.sum(
                per_example_accuracy(logits, mbs_tgt[oi]) * v
            )
            cnt = cnt + on_last * jnp.sum(v)

        _pipeline_ticks(
            cfg, params, mbs_in, pp_axis=pp_axis, n=n, microbatches=M,
            run_stage=run_stage, on_output=on_output,
        )
        # Only stage n-1 accumulated: the pipe psum replicates the sums;
        # the data psum then makes them global.
        sums = [
            lax.psum(lax.psum(x, pp_axis), data_axis)
            for x in (loss_sum, acc_sum, cnt)
        ]
        loss_sum, acc_sum, cnt = sums
        denom = jnp.maximum(cnt, 1.0)
        return {"loss": loss_sum / denom, "accuracy": acc_sum / denom}, cnt

    compiled = None

    def eval_step(params, batch):
        nonlocal compiled
        if compiled is None:
            pspecs = pp_param_specs(params, pp_axis, cfg.tp_axis, cfg.ep_axis)
            sharded = jax.shard_map(
                _eval,
                mesh=mesh,
                in_specs=(
                    pspecs,
                    {"tokens": P(data_axis), "valid": P(data_axis)},
                ),
                out_specs=(P(), P()),
                check_vma=False,
            )
            compiled = jax.jit(sharded)
        return compiled(params, batch)

    return eval_step


def _pp_1f1b_loss_and_grads(
    cfg,
    stack,
    params,
    inputs,
    targets,
    *,
    pp_axis: str,
    n: int,
    microbatches: int,
    moe_aux_weight: float = 0.0,
    virtual: int = 1,
    schedule: str = "1f1b",
):
    """1F1B schedule with a MANUAL backward: returns ``(loss, grads,
    phase_counts)`` with the (loss, grads) pair shaped exactly like
    ``value_and_grad(pp_loss)`` so the surrounding step (pipe psum
    completion, DP sync, ZeRO) is schedule-agnostic.  ``phase_counts``
    is a per-stage ``(3,)`` int32 vector counting the VALID (F, B, W)
    slots this stage executed — the measured side of the bubble
    accounting (off-schedule masked slots don't count).

    GPipe (``pp_loss``) differentiates through the whole tick loop, so
    AD keeps every microbatch's stage activations alive until the
    reverse sweep — O(M) activation memory.  Here forward and backward
    interleave on a synchronized alternating clock (even ticks forward,
    odd ticks backward — the SPMD rendering of Megatron-LM's 1F1B,
    arXiv 2104.04473 §2.2): a microbatch's backward starts as soon as
    its forward leaves the last stage, so at most ``2(n-1)`` microbatch
    inputs are in flight per stage regardless of M.  Only the STAGE
    INPUT is saved per in-flight microbatch (a ``2n+1``-slot ring, last
    slot = scratch for masked writes); the backward tick recomputes the
    stage forward under ``jax.vjp`` — stage-granular activation
    checkpointing, the standard 1F1B memory/compute trade.

    Schedule (F-tick index k, B-tick index k'): stage s runs forward of
    microbatch ``k - s`` and backward of microbatch ``k' - 2(n-1) + s``;
    activations hop +1 after every F-tick, cotangents hop -1 after every
    B-tick.  The last stage seeds the backward from the loss vjp of the
    microbatch it just finished; stage 0's outgoing cotangent feeds the
    embedding vjp.  Per-stage schedule shifts are data-dependent on
    ``axis_index``, so off-schedule ticks compute on clamped dummies and
    every accumulation is masked — exactly the trick the GPipe path uses
    for its bubble ticks.

    MoE aux loss (``moe_aux_weight > 0``): the B-tick's ``jax.vjp`` of
    the stage already recomputes the stage forward, so the router's
    sown aux value rides along free — the stage function returns
    ``(y, aux)`` (``mutable=["intermediates"]`` inside the vjp) and the
    aux output's cotangent is the constant ``moe_aux_weight/(n·M)``,
    matching GPipe's ``psum(aux_acc)/(n·M)`` term exactly.

    TP and CP compose: the stage body's Megatron psums and the ring's
    ppermutes sit inside ``jax.vjp``, which transposes them exactly as
    AD does; the outer step completes the sequence-sharded gradient
    with its cp pmean, schedule-agnostic.

    Head/embed vjps are gated on the owning stage with ``lax.cond``
    (ADVICE r3): at Llama-scale vocab the d×V head matmuls rival a
    stage's layer compute, so running them masked-to-zero on every
    stage would cost ~n_stages× redundant FLOPs per tick.  The
    predicate depends only on the pipe index, so model-axis peers
    always agree — any Megatron collective inside the branch stays
    matched.

    ``virtual > 1`` — INTERLEAVED 1F1B (Megatron arXiv 2104.04473
    §2.3): each stage holds ``v`` round-robin layer chunks (state
    placed with ``shard_state_pp(virtual=v)``; ``stack`` is built for
    chunk length ``L/(n·v)``) and the schedule's unit becomes a
    (chunk, microbatch) pair.  Microbatches proceed in groups of n;
    within a group, stage s's F-unit sequence is chunk-major
    ``(c, m mod n)`` and its B-unit sequence is reverse-chunk-major —
    the generalization keeps every transfer a +1 (F) / -1 (B) ring hop
    with one tick of latency, including the wrap that carries chunk c's
    output from stage n-1 into chunk c+1 on stage 0, so the alternating
    F/B clock and masked-validity machinery are unchanged.  Fill/drain
    spans become ``v·n`` chunk-ticks of 1/v stage-work each, shrinking
    the warm-up/drain bubble per device from ``(n-1)`` stage-units
    toward ``n/2 + n/(2v)`` — the measured tick accounting is reported
    by ``pp_bubble_fraction`` and recorded in the bench.  Requires
    ``num_layers % (n·v) == 0``; the unit ordering needs no divisibility
    of M (off-group units are masked like any bubble tick).

    ``schedule="zb"`` — ZERO-BUBBLE (ZB-H1-style W/B decomposition,
    arXiv 2401.10241 lineage; see also arXiv 2412.14374): the joint
    stage vjp splits into an activation-grad unit **B** (``jax.vjp``
    w.r.t. the stage input only — the cotangent must keep flowing up
    the pipe, so B stays on the critical path) and a weight-grad unit
    **W** (``jax.vjp`` w.r.t. the layer params only — nothing
    downstream consumes it, so it is off the critical path).  XLA CSE
    merges the two vjps' duplicated forward recompute.  What zb gives
    against 1f1b: the same loss to the bit on the same params (the
    forward slot is the same code), the same microbatches summed in the
    same order, and every gradient leaf equal to f32 rounding of its
    accumulated sum — NOT bit equality, and not by construction: each
    primitive's transpose is the same, but XLA compiles a slot anew in
    every scan body it appears in and is free to tile a bias gradient's
    row reduction differently there (on the CPU mesh ``o_proj/bias`` and
    ``up_proj/bias`` differ from 1f1b's by 1-2 ulp after one step, and
    ``up_proj/bias`` still does when zb takes dx and dW from one joint
    vjp; ``tests/test_pp_zb.py`` holds every leaf to 4 ulp of its scale).

    In this SPMD masked-scan rendering a masked slot still burns wall
    clock, so the win comes from SEGMENTATION, not from moving W: the
    1F1B scan executes an F-slot AND a B-slot every tick (2T slots of
    capacity for 2Mv useful), while the zb rendering runs three scans
    with heterogeneous bodies — warm-up ticks ``[0, vn-1)`` execute
    only the F slot, steady ticks ``[vn-1, j_last+n)`` execute F+B+W,
    drain ticks ``[j_last+n, T)`` execute only B+W — so the dead
    phases genuinely do not execute.  Capacity drops to
    ``3·(j_last+n)`` slots for ``3Mv`` useful: bubble
    ``1 - Mv/(j_last+n)`` vs 1F1B's ``1 - Mv/T``
    (``_zb_segments`` / ``pp_bubble_fraction(schedule="zb")``).  W
    runs the SAME tick as its B (deferral depth 0): deferring W
    further would lengthen the scan without creating capacity, and
    same-tick W keeps memory identical to 1F1B — the activation ring
    is unchanged and no pending-W state accumulates.  Composition
    limits in v1: no ``cfg.cp_axis`` and no MoE aux loss (the factory
    rejects both loudly); TP and ZeRO compose as in 1F1B.
    """
    from distributeddataparallel_tpu.models.transformer import (
        rope_frequencies,
    )
    from distributeddataparallel_tpu.ops.losses import lm_cross_entropy

    M = microbatches
    s = lax.axis_index(pp_axis)
    mb_rows = inputs.shape[0] // M
    S = inputs.shape[1]
    positions = None
    n_cp = 1
    if cfg.cp_axis is not None:
        # CP composition: inputs arrive sequence-sharded (host-side
        # shift, see shard_lm_batch); the stage blocks run ring
        # attention with global positions.  The ring's ppermutes sit
        # inside jax.vjp, which transposes them exactly as AD does (the
        # same argument as TP) — and the outer _step completes the
        # seq-sharded gradient with its cp pmean, schedule-agnostic.
        from distributeddataparallel_tpu.parallel.context_parallel import (
            cp_positions,
        )

        n_cp = int(lax.psum(1, cfg.cp_axis))
        positions = cp_positions(S, cfg.cp_axis)
    _check_seq_bound(cfg, S, n_cp)
    mbs_in = inputs.reshape(M, mb_rows, S)
    mbs_tgt = targets.reshape(M, mb_rows, S)
    rope = (
        rope_frequencies(
            cfg.dims_per_head, cfg.max_seq_len, theta=cfg.rope_theta
        )
        if cfg.positional == "rope"
        else None
    )

    head_keys = ("final_norm",) + (
        ("token_embed",) if cfg.tie_embeddings else ("lm_head",)
    )
    embed_keys = ("token_embed",) + (
        ("pos_embed",) if cfg.positional == "learned" else ()
    )

    use_aux = cfg.moe_experts > 0 and moe_aux_weight > 0.0

    def stage_fn(layer_params, x):
        y, _ = stack.apply(
            {"params": layer_params}, x, positions, rope, True
        )
        return y

    def stage_fn_aux(layer_params, x):
        from distributeddataparallel_tpu.models.transformer import (
            moe_aux_from_intermediates,
        )

        (y, _), col = stack.apply(
            {"params": layer_params}, x, positions, rope, True,
            mutable=["intermediates"],
        )
        return y, moe_aux_from_intermediates(col)

    def head_loss(hparams, y, tgt):
        return lm_cross_entropy(_head(cfg, hparams, y), tgt)

    def embed_fn(eparams, toks):
        return _embed(cfg, eparams, toks, positions)

    v = virtual
    # Chunk length of the LOCAL stacked shard (leaves carry L/n rows;
    # each of the v chunks is L/(n*v) of them).
    n_slots = v * 2 * n + 1      # per-chunk 2n ring; last slot = scratch
    saved = jnp.zeros((n_slots, mb_rows, S, cfg.d_model), cfg.dtype)
    fbuf = jnp.zeros((mb_rows, S, cfg.d_model), cfg.dtype)
    bbuf = jnp.zeros((mb_rows, S, cfg.d_model), cfg.dtype)
    gacc = jax.tree.map(jnp.zeros_like, params)
    loss_acc = jnp.zeros((), jnp.float32)
    perm_f = [(i, (i + 1) % n) for i in range(n)]
    perm_b = [((i + 1) % n, i) for i in range(n)]

    def _acc(acc_tree, keys, grad_tree, w):
        out = dict(acc_tree)
        for k in keys:
            out[k] = jax.tree.map(
                lambda a, g: a + g.astype(a.dtype) * w,
                acc_tree[k], grad_tree[k],
            )
        return out

    def _decode_unit(j):
        """Unit index -> (chunk, microbatch, valid): groups of n
        microbatches cycle chunk-major (g asc, c asc, m-offset asc)."""
        g = j // (n * v)
        r = j % (n * v)
        c = r // n
        m = g * n + (r % n)
        valid = (j >= 0) & (m < M)
        return c, m, valid

    def _chunk_params(c):
        if v == 1:
            return params["layers"]
        Lc = jax.tree.leaves(params["layers"])[0].shape[0] // v
        return jax.tree.map(
            lambda p: lax.dynamic_slice_in_dim(p, c * Lc, Lc, 0),
            params["layers"],
        )

    _, T = _1f1b_ticks(n, M, v)
    split_bw = schedule == "zb"
    if split_bw and use_aux:
        raise ValueError("zb schedule does not support the MoE aux loss")

    # lax.scan, NOT an unrolled python loop, for two load-bearing
    # reasons: the carried ring buffer updates alias in place, and
    # iteration boundaries stop the scheduler from hoisting every
    # B-tick's recompute ahead of the backwards (which would resurrect
    # the O(M) liveness this schedule exists to kill).  The tick body
    # is factored into per-phase SLOTS so 1f1b (F+B every tick) and zb
    # (segmented F / F+B+W / B+W bodies) render from the same code.
    def f_slot(carry, i):
        # --- F slot, tick i: stage s runs forward of unit i - s --------
        # (0 <= m < M subsumes the tick-range bound: i < T implies the
        # per-stage unit index is already past the last unit when
        # off-schedule)
        saved, fbuf, bbuf, gacc, loss_acc, aux_acc, counts = carry
        cf, mf, valid = _decode_unit(i - s)
        mc = jnp.clip(mf, 0, M - 1)
        toks = lax.dynamic_index_in_dim(mbs_in, mc, 0, keepdims=False)
        x = jnp.where((s == 0) & (cf == 0), embed_fn(params, toks), fbuf)
        slot = jnp.where(valid, cf * (2 * n) + mc % (2 * n), v * 2 * n)
        saved = lax.dynamic_update_slice_in_dim(saved, x[None], slot, 0)
        fbuf = lax.ppermute(stage_fn(_chunk_params(cf), x), pp_axis, perm_f)
        counts = counts + valid.astype(jnp.int32) * jnp.array(
            [1, 0, 0], jnp.int32
        )
        return (saved, fbuf, bbuf, gacc, loss_acc, aux_acc, counts)

    def bw_slot(carry, i):
        # --- B (+W) slot, tick i: stage s runs backward of unit
        #     i - (vn - 1) - (n - 1 - s), chunks in REVERSE order -------
        saved, fbuf, bbuf, gacc, loss_acc, aux_acc, counts = carry
        cb, mb_, valid = _decode_unit(i - (v * n - 1) - (n - 1 - s))
        cb = v - 1 - cb
        mc = jnp.clip(mb_, 0, M - 1)
        slot = jnp.where(valid, cb * (2 * n) + mc % (2 * n), v * 2 * n)
        xb = lax.dynamic_index_in_dim(saved, slot, 0, keepdims=False)
        chunk_p = _chunk_params(cb)
        if split_bw:
            # ZB W/B decomposition: B = vjp w.r.t. the stage INPUT only
            # (params enter as a closure constant, so no dW cotangent
            # path is built); W below is the params-only twin.
            y, b_vjp = jax.vjp(lambda xx: stage_fn(chunk_p, xx), xb)
            aux = jnp.zeros((), jnp.float32)
        elif use_aux:
            (y, aux), stage_vjp = jax.vjp(stage_fn_aux, chunk_p, xb)
        else:
            y, stage_vjp = jax.vjp(stage_fn, chunk_p, xb)
            aux = jnp.zeros((), jnp.float32)
        tgt = lax.dynamic_index_in_dim(mbs_tgt, mc, 0, keepdims=False)
        on_last = (s == n - 1) & (cb == v - 1)
        head_params = {kk: params[kk] for kk in head_keys}

        # Gated head vjp (ADVICE r3): only the last stage pays the d×V
        # matmuls; other stages take the zeros branch.  The predicate is
        # uniform across non-pipe axes, so branch collectives match.
        def do_head(y_):
            lval, head_vjp = jax.vjp(
                lambda hp, yy: head_loss(hp, yy, tgt), head_params, y_
            )
            # Seed 1/M: the step's loss is the microbatch MEAN, so each
            # microbatch's cotangent carries the mean's scaling.
            dhp_, dy_ = head_vjp(jnp.full((), 1.0 / M, lval.dtype))
            return lval, dhp_, dy_

        def skip_head(y_):
            return jax.tree.map(
                lambda t: jnp.zeros(t.shape, t.dtype),
                jax.eval_shape(do_head, y_),
            )

        lval, dhp, dy_head = lax.cond(on_last, do_head, skip_head, y)
        gy = jnp.where(on_last, dy_head.astype(fbuf.dtype), bbuf)
        if split_bw:
            # B unit: activation grad only — the cotangent the next
            # stage up is waiting on.  W unit: weight grad only, same
            # tick (deferral depth 0 — see the docstring).  Each vjp
            # transposes the same primitives the joint vjp would, so
            # dx/dlayers equal its to f32 rounding and CSE shares the
            # recompute.
            (dx,) = b_vjp(gy)
            _, w_vjp = jax.vjp(lambda lp: stage_fn(lp, xb), chunk_p)
            (dlayers,) = w_vjp(gy)
        elif use_aux:
            # The aux output's cotangent: GPipe adds
            # moe_aux_weight * psum(aux_acc) / (n*M) to the loss, so
            # every valid (stage-chunk, microbatch) aux value carries
            # this constant derivative (v·n·M units in total).  Invalid
            # ticks are masked by w below.
            dlayers, dx = stage_vjp(
                (gy, jnp.asarray(moe_aux_weight / (n * v * M), aux.dtype))
            )
        else:
            dlayers, dx = stage_vjp(gy)
        toksb = lax.dynamic_index_in_dim(mbs_in, mc, 0, keepdims=False)

        # Gated embed vjp: only stage 0's outgoing cotangent feeds it.
        def do_embed(dx_):
            _, embed_vjp = jax.vjp(
                lambda ep: embed_fn(ep, toksb),
                {kk: params[kk] for kk in embed_keys},
            )
            (dep_,) = embed_vjp(dx_)
            return dep_

        def skip_embed(dx_):
            return jax.tree.map(
                lambda t: jnp.zeros(t.shape, t.dtype),
                jax.eval_shape(do_embed, dx_),
            )

        dep = lax.cond((s == 0) & (cb == 0), do_embed, skip_embed, dx)
        w = valid.astype(jnp.float32)
        if v == 1:
            gacc = _acc(gacc, ("layers",), {"layers": dlayers}, w)
        else:
            Lc = jax.tree.leaves(params["layers"])[0].shape[0] // v

            def _upd_chunk(a, g):
                cur = lax.dynamic_slice_in_dim(a, cb * Lc, Lc, 0)
                return lax.dynamic_update_slice_in_dim(
                    a, cur + g.astype(a.dtype) * w, cb * Lc, 0
                )

            gacc = {
                **gacc,
                "layers": jax.tree.map(_upd_chunk, gacc["layers"], dlayers),
            }
        gacc = _acc(gacc, head_keys, dhp, w * on_last.astype(jnp.float32))
        gacc = _acc(gacc, embed_keys, dep, w)
        loss_acc = loss_acc + jnp.where(valid & on_last, lval, 0.0)
        aux_acc = aux_acc + jnp.where(valid, aux, 0.0)
        bbuf = lax.ppermute(dx, pp_axis, perm_b)
        counts = counts + valid.astype(jnp.int32) * (
            jnp.array([0, 1, 1], jnp.int32) if split_bw
            else jnp.array([0, 1, 0], jnp.int32)
        )
        return (saved, fbuf, bbuf, gacc, loss_acc, aux_acc, counts)

    aux_acc = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((3,), jnp.int32)
    carry = (saved, fbuf, bbuf, gacc, loss_acc, aux_acc, counts)
    if split_bw:
        # Three segmented scans with heterogeneous bodies — THE
        # zero-bubble mechanism (the W split alone buys nothing in an
        # SPMD rendering where masked slots still burn wall clock):
        # warm-up ticks run no backward slot, drain ticks run no
        # forward slot, so per-stage capacity is 3·(j_last+n) slots
        # instead of the uniform body's 3·T.  Tick indices stay GLOBAL
        # across the segments; the arithmetic is _zb_segments — the
        # same closed form pp_bubble_fraction(schedule="zb") prices.
        warm, _steady, _drain, f_end = _zb_segments(n, M, v)

        def f_tick(c, i):
            return f_slot(c, i), None

        def fbw_tick(c, i):
            return bw_slot(f_slot(c, i), i), None

        def bw_tick(c, i):
            return bw_slot(c, i), None

        carry, _ = lax.scan(
            f_tick, carry, jnp.arange(0, warm, dtype=jnp.int32)
        )
        carry, _ = lax.scan(
            fbw_tick, carry, jnp.arange(warm, f_end, dtype=jnp.int32)
        )
        carry, _ = lax.scan(
            bw_tick, carry, jnp.arange(f_end, T, dtype=jnp.int32)
        )
    else:
        # One scan iteration = one F-tick + one B-tick (the even/odd
        # clock flattened).
        def tick(c, i):
            return bw_slot(f_slot(c, i), i), None

        carry, _ = lax.scan(tick, carry, jnp.arange(T, dtype=jnp.int32))
    saved, fbuf, bbuf, gacc, loss_acc, aux_acc, counts = carry

    # Only the last stage accumulated loss; psum-fwd/identity-bwd is
    # irrelevant here (no AD through this), plain psum replicates it.
    loss = lax.psum(loss_acc, pp_axis) / M
    if use_aux:
        # Mirror pp_loss: per-stage-chunk aux summed over the pipe,
        # averaged over stage-chunks × microbatches.
        loss = loss + moe_aux_weight * (
            lax.psum(aux_acc, pp_axis) / (n * v * M)
        )
    return loss, gacc, counts


def _1f1b_ticks(n: int, M: int, v: int) -> tuple[int, int]:
    """(last valid unit index, scan length T) of the 1F1B schedule —
    THE tick arithmetic, shared by the compiled schedule
    (``_pp_1f1b_loss_and_grads``) and the bubble accounting
    (``pp_bubble_fraction``) so the reported number cannot drift from
    the schedule that runs."""
    # Last VALID unit (m = M-1, c = v-1); off-group units past it are
    # bubbles anyway.
    j_last = ((M - 1) // n) * n * v + (v - 1) * n + (M - 1) % n
    # F span ends at j_last + (n-1); B span at (vn-1) + (n-1) + j_last.
    return j_last, j_last + v * n + n - 1


def _zb_segments(n: int, M: int, v: int) -> tuple[int, int, int, int]:
    """(warmup, steady, drain, f_end) tick-segment lengths of the zb
    schedule — THE zb tick arithmetic, shared by the compiled
    three-scan rendering and ``pp_bubble_fraction(schedule="zb")``.

    Warm-up ``[0, vn-1)`` runs F slots only (the first backward — unit
    0 on stage n-1 — cannot start before tick ``vn-1``); steady
    ``[vn-1, f_end)`` runs F+B+W; drain ``[f_end, T)`` runs B+W only
    (the last forward — unit j_last on stage n-1 — finishes at tick
    ``f_end - 1``).  The segments sum to the 1F1B scan length T, so zb
    changes per-tick slot CAPACITY, never the critical path.
    """
    j_last, T = _1f1b_ticks(n, M, v)
    warm = v * n - 1
    f_end = j_last + n
    return warm, f_end - warm, T - f_end, f_end


def pp_bubble_fraction(
    n: int, microbatches: int, virtual: int = 1, schedule: str = "1f1b"
) -> dict:
    """Exact slot accounting of a pipeline schedule's bubble.

    ``schedule="1f1b"``: the scan runs ``T`` iterations; each executes
    one F-unit and one B-unit slot of ``1/virtual`` stage-work each,
    masked off-schedule.  Useful work per device = ``2·M·virtual``
    unit-slots out of ``2·T`` — the rest is bubble (warm-up/drain
    idle).  ``T`` comes from ``_1f1b_ticks``, the same arithmetic the
    compiled schedule uses, so the number IS the schedule, not an
    estimate; the bench records it next to the wall-clock step times.

    ``schedule="zb"``: three phases (F, B, W) over the segmented scans
    of ``_zb_segments`` — slot capacity per stage is F-window + B-window
    + W-window = ``3·(j_last+n)`` for ``3·M·virtual`` useful slots, so
    the bubble is ``1 - M·v/(j_last+n)`` < the 1F1B fraction at every
    (n, M, v).  ``slot_windows`` (phase -> [start, end) tick) is the
    per-phase capacity table the measured-bubble reconstruction and
    the SL30x lint both consume.
    """
    M, v = microbatches, virtual
    j_last, T = _1f1b_ticks(n, M, v)
    if schedule == "zb":
        warm, steady, drain, f_end = _zb_segments(n, M, v)
        useful = 3 * M * v
        total = 3 * f_end
        return {
            "n_stages": n,
            "microbatches": M,
            "virtual": v,
            "schedule": "zb",
            "ticks": T,
            "segments": {"warmup": warm, "steady": steady, "drain": drain},
            "slot_windows": {
                "F": (0, f_end),
                "B": (v * n - 1, T),
                "W": (v * n - 1, T),
            },
            "useful_slots": useful,
            "slot_capacity": total,
            "bubble_fraction": round((total - useful) / total, 4),
            # per-device idle in full-stage-compute units: 3 slots/v
            # make up one stage-unit of F+B+W work.
            "bubble_stage_units": round((total - useful) / (3 * v), 4),
        }
    useful = 2 * M * v
    total = 2 * T
    return {
        "n_stages": n,
        "microbatches": M,
        "virtual": v,
        "schedule": "1f1b",
        "ticks": T,
        "slot_windows": {"F": (0, T), "B": (0, T)},
        "useful_slots": useful,
        "slot_capacity": total,
        "bubble_fraction": round((total - useful) / total, 4),
        # per-device idle in full-stage-compute units (ticks are 1/v of
        # a stage): the cross-virtual-degree comparable number.
        "bubble_stage_units": round((total - useful) / (2 * v), 4),
    }


def make_pp_train_step(
    cfg,
    *,
    mesh: Mesh,
    microbatches: int,
    data_axis: str = "data",
    pp_axis: str = "pipe",
    donate: bool = True,
    grad_sync: bool = True,
    moe_aux_weight: float = 0.01,
    zero: bool = False,
    schedule: str = "gpipe",
    grad_clip: float | None = None,
    virtual: int = 1,
):
    """Compiled DP x PP train step for a scanned TransformerLM config.

    ``virtual > 1`` selects INTERLEAVED scheduling (v layer chunks per
    stage; state must be placed with ``shard_state_pp(virtual=v)`` so
    each pipe position's contiguous rows are its round-robin chunks).
    Requires ``schedule="1f1b"`` or ``"zb"`` and
    ``num_layers % (n_stages · v) == 0``; see
    ``_pp_1f1b_loss_and_grads`` for the schedules and
    ``pp_bubble_fraction`` for the bubble accounting.

    ``schedule="zb"`` — zero-bubble ZB-H1-style W/B split (see
    ``_pp_1f1b_loss_and_grads``): 1f1b's losses, and its grads to f32
    rounding of each leaf's accumulated sum,
    smaller bubble (``1 - Mv/(j_last+n)`` vs ``1 - Mv/T``), same
    activation memory.  v1 rejects ``cfg.cp_axis`` and the MoE aux
    loss.  The 1f1b and zb steps return measured per-stage
    ``pp_phase_counts`` (F/B/W useful-slot counters) in their metrics.

    ``zero=True``: ZeRO-1 over the data axis on the PIPE-LOCAL param
    shards — after the pipe psum completes every gradient, each
    position's local tree (its layer slice + the replicated leaves) is
    flattened, reduce-scattered over ``data_axis``, updated on the 1/N
    chunk, and gathered back.  Local sizes are uniform along the data
    axis and flat offsets identical across pipe positions, so the
    elementwise update keeps pipe-replicated leaves in lockstep — the
    same argument as ZeRO x TP.  Build the state with
    ``zero_state(..., pp_axis=...)``.

    ``step(state, batch, rng) -> (state, metrics)`` with
    ``batch = {"tokens": (B, S+1) int32}`` sharded over ``data_axis``
    (replicated over the pipe axis); the per-position rows must divide
    ``microbatches``.  State comes from ``shard_state_pp``.

    PP x TP: when ``cfg.tp_axis`` is set, each stage's blocks run
    Megatron-sharded over that (third) mesh axis; layer params shard over
    BOTH pipe (leading layer dim) and model (trailing dims).  Embeddings
    and head are computed replicated over the model axis (their grads
    complete through the blocks' copy/reduce operators), so only the
    pipe-axis psum below is needed for them.

    PP x CP: when ``cfg.cp_axis`` is set, the batch arrives pre-split as
    ``{"inputs", "targets"}`` sharded (rows → ``data_axis``, sequence →
    the cp axis; see ``shard_lm_batch`` — the next-token shift crosses
    seq shards so it must happen host-side), stage blocks run ring
    attention with global positions, and gradients are pmean'd over the
    cp axis after the pipe completion (the sequence-sharded loss's
    missing reduction, exactly as in ``make_train_step``).
    """
    from distributeddataparallel_tpu.models.transformer import (
        rope_frequencies,
    )
    from distributeddataparallel_tpu.ops.losses import lm_cross_entropy
    from distributeddataparallel_tpu.parallel.data_parallel import (
        all_reduce_gradients,
    )

    if not cfg.scan_layers:
        raise ValueError("pipeline parallelism requires scan_layers=True")
    if cfg.dropout_rate:
        raise ValueError("pipeline v1 does not support dropout")
    if zero and not grad_sync:
        # Same contract as make_train_step: the ZeRO reduce_scatter IS
        # the sync — it cannot be skipped.
        raise ValueError("grad_sync=False does not compose with zero=True")
    if grad_clip is not None and not grad_sync:
        # Same contract as make_train_step: unsynced per-replica grads
        # have per-replica norms — clipping would scale each data-axis
        # replica differently and params would drift.
        raise ValueError("grad_clip requires grad_sync=True")
    if schedule not in ("gpipe", "1f1b", "zb"):
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    if virtual < 1:
        raise ValueError(f"virtual must be >= 1, got {virtual}")
    if virtual > 1 and schedule == "gpipe":
        raise ValueError(
            "virtual (interleaved) stages require schedule='1f1b' — the "
            "GPipe path runs whole contiguous stages"
        )
    if schedule == "zb":
        if cfg.cp_axis is not None:
            raise ValueError(
                "zb schedule does not compose with cp_axis yet — use "
                "schedule='1f1b' for context-parallel pipelines"
            )
        if cfg.moe_experts > 0 and moe_aux_weight > 0.0:
            raise ValueError(
                "zb schedule does not support the MoE aux loss (the B/W "
                "split has no aux cotangent path) — set "
                "moe_aux_weight=0.0 or use schedule='1f1b'"
            )
    n_stages = mesh.shape[pp_axis]
    M = microbatches
    stack = _stage_stack(cfg, n_stages * virtual)

    def pp_loss(params, inputs, targets):
        """inputs/targets: (B_local, S_local) — the next-token shift
        already applied (host-side under CP, trivially otherwise)."""
        n = n_stages
        mb_rows = inputs.shape[0] // M
        S = inputs.shape[1]
        mbs_in = inputs.reshape(M, mb_rows, S)
        mbs_tgt = targets.reshape(M, mb_rows, S)
        positions = None
        n_cp = 1
        if cfg.cp_axis is not None:
            from distributeddataparallel_tpu.parallel.context_parallel import (
                cp_positions,
            )

            n_cp = int(lax.psum(1, cfg.cp_axis))
            positions = cp_positions(S, cfg.cp_axis)
        _check_seq_bound(cfg, S, n_cp)
        rope = (
            rope_frequencies(
                cfg.dims_per_head, cfg.max_seq_len, theta=cfg.rope_theta
            )
            if cfg.positional == "rope"
            else None
        )
        layer_shard = params["layers"]

        use_aux = cfg.moe_experts > 0 and moe_aux_weight > 0.0
        acc = jnp.zeros((), jnp.float32)
        aux_acc = jnp.zeros((), jnp.float32)

        def run_stage(x, t, s):
            nonlocal aux_acc
            if not use_aux:
                y, _ = stack.apply(
                    {"params": layer_shard}, x, positions, rope, True
                )
                return y
            (y, _), col = stack.apply(
                {"params": layer_shard}, x, positions, rope, True,
                mutable=["intermediates"],
            )
            from distributeddataparallel_tpu.models.transformer import (
                moe_aux_from_intermediates,
            )

            # Count only ticks where this stage processed a REAL
            # microbatch (stage s holds microbatch t - s).
            valid = jnp.logical_and(t - s >= 0, t - s < M)
            aux_acc = aux_acc + jnp.where(
                valid, moe_aux_from_intermediates(col), 0.0
            )
            return y

        def on_output(oi, y, s):
            nonlocal acc
            logits = _head(cfg, params, y)
            mb_loss = lm_cross_entropy(logits, mbs_tgt[oi])
            acc = acc + jnp.where(s == n - 1, mb_loss, 0.0)

        _pipeline_ticks(
            cfg, params, mbs_in, pp_axis=pp_axis, n=n, microbatches=M,
            run_stage=run_stage, on_output=on_output, positions=positions,
        )
        # Only the last stage accumulated; the psum replicates the total.
        # MUST be the custom-vjp reduce (psum fwd, identity bwd): under
        # check_vma=False, lax.psum's transpose psums the replicated
        # cotangent again, scaling every gradient by n_stages.  Under CP
        # this is still the LOCAL (per-seq-shard) loss; the seq reduction
        # happens outside the differentiated function.
        from distributeddataparallel_tpu.parallel.tensor_parallel import (
            reduce_from_tp,
        )

        loss = reduce_from_tp(acc, pp_axis) / M
        if use_aux:
            # Each stage accumulated its own layer slice's aux over its M
            # real ticks; the pipe psum completes the layer sum.  Mean
            # over stages x microbatches keeps the weight comparable to
            # the non-PP MoE loss.
            loss = loss + moe_aux_weight * (
                reduce_from_tp(aux_acc, pp_axis) / (n * M)
            )
        return loss

    def _step(state, batch, rng):
        if cfg.cp_axis is not None:
            inputs, targets = batch["inputs"], batch["targets"]
        else:
            toks = batch["tokens"]
            inputs, targets = toks[:, :-1], toks[:, 1:]
        if schedule in ("1f1b", "zb"):
            loss, grads, phase_counts = _pp_1f1b_loss_and_grads(
                cfg, stack, state.params, inputs, targets,
                pp_axis=pp_axis, n=n_stages, microbatches=M,
                moe_aux_weight=moe_aux_weight, virtual=virtual,
                schedule=schedule,
            )
        else:
            loss, grads = jax.value_and_grad(pp_loss)(
                state.params, inputs, targets
            )
            phase_counts = None
        # Complete replicated-param grads over the pipe (only the stages
        # that use them contributed); layer-slice grads stay local.
        gspecs = pp_param_specs(grads, pp_axis, cfg.tp_axis, cfg.ep_axis)
        grads = jax.tree.map(
            lambda g, sp: g if any(sp) else lax.psum(g, pp_axis),
            grads,
            gspecs,
        )
        if cfg.cp_axis is not None:
            # Complete the sequence-sharded gradient (model math, exactly
            # as in make_train_step's cp handling).
            grads = jax.tree.map(
                lambda g: lax.pmean(g, cfg.cp_axis), grads
            )
            loss = lax.pmean(loss, cfg.cp_axis)
        model_axes = tuple(
            ax for ax in (pp_axis, cfg.tp_axis, cfg.ep_axis)
            if ax is not None
        )
        if zero:
            from distributeddataparallel_tpu.parallel.zero import zero_update

            new_params, new_opt = zero_update(
                grads, state, data_axis, mesh.shape[data_axis],
                clip_norm=grad_clip, model_axes=model_axes,
                local_specs=gspecs if grad_clip is not None else None,
            )
            new_state = state.replace(
                step=state.step + 1, params=new_params, opt_state=new_opt
            )
        else:
            if grad_sync:
                grads = all_reduce_gradients(grads, data_axis, op="mean")
            if grad_clip is not None:
                # Axis-aware global norm: stage-local layer slices psum
                # over the pipe (and Megatron/expert) axes, replicated
                # leaves (complete per position after the psum above)
                # count once — identical on every position.
                from distributeddataparallel_tpu.parallel.data_parallel import (
                    clip_scale,
                    model_axes_sumsq,
                )

                scale = clip_scale(
                    jnp.sqrt(model_axes_sumsq(grads, gspecs)), grad_clip
                )
                grads = jax.tree.map(lambda g: g * scale, grads)
            new_state = state.apply_gradients(grads)
        metrics = {"loss": lax.pmean(loss, data_axis)}
        if phase_counts is not None:
            # Measured per-stage useful-slot counters, gathered over the
            # pipe into an (n_stages, 3) [F, B, W] table — identical on
            # every device, so the replicated out-spec is exact.  This
            # is the device-side half of the measured-bubble loop
            # (observability.pipeline reconstructs the fraction).
            metrics["pp_phase_counts"] = lax.all_gather(
                phase_counts, pp_axis
            )
        return new_state, metrics

    compiled = None
    jit_kwargs = {"donate_argnums": (0,)} if donate else {}

    if cfg.cp_axis is not None:
        batch_spec: Any = {
            "inputs": P(data_axis, cfg.cp_axis),
            "targets": P(data_axis, cfg.cp_axis),
        }
    else:
        batch_spec = P(data_axis)

    def step(state, batch, rng):
        nonlocal compiled
        if compiled is None:
            if zero:
                from distributeddataparallel_tpu.parallel.zero import (
                    state_specs,
                )

                specs = state_specs(
                    state, data_axis, cfg.tp_axis, cfg.ep_axis, pp_axis
                )
            else:
                specs = pp_state_specs(
                    state, pp_axis, cfg.tp_axis, cfg.ep_axis
                )
            sharded = jax.shard_map(
                _step,
                mesh=mesh,
                in_specs=(specs, batch_spec, P()),
                out_specs=(specs, P()),
                check_vma=False,
            )
            compiled = jax.jit(sharded, **jit_kwargs)
            step.jitted = compiled  # introspection: memory_analysis, AOT
        return compiled(state, batch, rng)

    step.jitted = None

    # Expected-collective manifest for the graph linter: activations
    # flow between stages via ppermute on the pipe axis; gradients
    # reduce over data (psum, or reduce_scatter/all_gather under ZeRO)
    # and over pipe for the replicated "rest" params.
    from distributeddataparallel_tpu.analysis.rules import (
        collective_manifest,
    )

    _any = {p: (0, None) for p in ("psum", "reduce_scatter",
                                   "psum_scatter", "all_gather",
                                   "ppermute", "all_to_all")}
    if zero:
        _data = {"reduce_scatter": (1, None), "all_gather": (1, None),
                 "psum": (0, None)}
    elif grad_sync:
        _data = {"psum": (1, None)}
    else:
        _data = {"psum": (0, None)}
    _reduce = {
        data_axis: _data,
        pp_axis: {"ppermute": (1, None), "psum": (0, None)},
    }
    for ax in (cfg.cp_axis, cfg.tp_axis, cfg.ep_axis):
        if ax is not None:
            _reduce.setdefault(ax, dict(_any))
    step.collective_manifest = collective_manifest(
        "pp-zero" if zero else "pp",
        grad_reduce=_reduce,
        donate=donate,
        allow_f32_reduce=True,
    )

    # Schedule-as-data for the SL3xx linter: the tick table this step
    # claims to run, rebuilt from the schedule definition (NOT from the
    # tick arithmetic above — the lint cross-checks the two, and
    # bubble_accounting is the factory-side number SL304 compares
    # against the table's).
    from distributeddataparallel_tpu.analysis.schedule_lint import (
        gpipe_schedule_ir,
        one_f_one_b_schedule_ir,
        zb_schedule_ir,
    )

    if schedule == "zb":
        step.schedule_ir = zb_schedule_ir(
            n_stages, M, virtual, hop_axis=pp_axis
        )
        step.bubble_accounting = pp_bubble_fraction(
            n_stages, M, virtual, schedule="zb"
        )
    elif schedule == "1f1b":
        step.schedule_ir = one_f_one_b_schedule_ir(
            n_stages, M, virtual, hop_axis=pp_axis
        )
        step.bubble_accounting = pp_bubble_fraction(n_stages, M, virtual)
    else:
        step.schedule_ir = gpipe_schedule_ir(n_stages, M, hop_axis=pp_axis)
        step.bubble_accounting = None
    return step
