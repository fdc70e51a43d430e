"""ZeRO-1-style cross-replica weight-update sharding (TPU-native).

The reference trains pure-DP with fully replicated optimizer state
(`optim.SGD`, ref dpp.py:41) — every rank redundantly stores and updates
identical state.  For the Llama-3 8B config (BASELINE 5) that redundancy
is what breaks the per-chip memory budget (SURVEY.md §7 hard-part 3), and
the TPU-native fix is the cross-replica weight-update sharding of
arXiv 2004.13336 (the XLA-side ZeRO-1, referenced from PAPERS.md):

    grads --reduce_scatter--> 1/N grad shard per replica
          --optimizer update on the shard (opt state lives sharded)
          --all_gather--> full updated params on every replica

Same math as DDP+optimizer (identical updates, bitwise modulo reduction
order), ~same communication volume as one all-reduce (reduce_scatter +
all_gather = all_reduce's two phases), but optimizer state memory drops
N×: per chip, Adam on 8B goes from ~64 GB of f32 (mu+nu) to ~8 GB on an
8-way axis.

Mechanics: parameters/grads are flattened into one f32 vector padded to a
multiple of the axis size; each replica owns one contiguous chunk.  The
optimizer transform runs on that flat chunk — valid for elementwise
transforms (sgd, momentum, adam, adamw's decoupled decay).  Transforms
needing *global* tensor structure (clip_by_global_norm across the full
tree) would see only the local chunk; compose those upstream of the
train step or use replicated DP instead.

Used through ``training.train_step.make_train_step(..., zero=True)`` with
a state built by ``zero_state(...)``.

ZeRO-2/3 extension (arXiv 2004.13336's full weight-update sharding):
``zero_state(..., level=2/3)`` + ``make_train_step(..., zero=2/3)``.
Both levels move to a BUCKETED flat layout (``bucket_plan``): leaves are
grouped into ~bucket_bytes buckets (reverse leaf order, the
gradient-ready order ``native.plan_buckets`` emits), each bucket padded
to a multiple of the axis size, and a device's shard is the
concatenation of its per-bucket sub-chunks.  Bucketing is what lets the
reduce-scatter start before the last grad exists and the all-gather
interleave with tail-of-step compute, instead of one monolithic vector
serializing the wire behind the slowest leaf.

  level 2: grads leave backward via per-bucket ``psum_scatter`` into the
      1/N shard — the full *reduced* f32 gradient vector is never
      materialized (only a bucket-sized staging concat plus the shard);
      update runs on the shard; params re-replicate via per-bucket
      ``all_gather``.
  level 3: params STAY sharded between steps (``Zero3Params`` holds just
      the flat f32 shard + static layout meta); each step all-gathers
      them bucketwise inside the differentiated function, so AD's
      transpose of the gather IS the reduce-scatter of the grads and the
      update consumes the shard directly — no replicated param tree ever
      lives in the state, only the transient gathered values inside the
      step.

``moment_dtype=`` stores optimizer moments low-bit between steps
(``low_bit_moments``): bf16 or blockwise-int8, written back each step
with stochastic rounding (``ops/quant``) so the round-trip is unbiased.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distributeddataparallel_tpu.observability import scopes

Pytree = Any

#: Default bucket granularity for the zero2/zero3 flat layout.
ZERO_BUCKET_BYTES = 1 << 20


def flat_size(params: Pytree, num_shards: int) -> tuple[int, int]:
    """(padded_total, chunk): total f32 elements padded to num_shards."""
    total = sum(leaf.size for leaf in jax.tree.leaves(params))
    chunk = -(-total // num_shards)
    return chunk * num_shards, chunk


def flatten_f32(params: Pytree, padded: int, cast: str = "f32") -> jnp.ndarray:
    """Concat all leaves into one padded flat vector.

    ``cast`` makes the dtype policy explicit instead of silently
    upcasting whatever arrives:

    - ``"f32"`` (default): every leaf is upcast to f32 — the master-copy
      convention of the ZeRO update path, where the flat vector IS the
      f32 master and ``unflatten`` casts back per leaf.
    - ``"preserve"``: keep the tree's own (uniform) dtype — for bf16
      master-param configs that want the flat vector in bf16 too.  A
      MIXED-dtype tree raises: concatenating would silently promote the
      narrow leaves, which is exactly the bug this flag exists to stop.
    - ``"strict"``: raise unless every leaf is already f32 — for callers
      that want proof no hidden upcast (and its 2x memory) happened.
    """
    leaves = jax.tree.leaves(params)
    dtypes = {jnp.dtype(l.dtype) for l in leaves}
    if cast == "f32":
        flat = jnp.concatenate(
            [l.reshape(-1).astype(jnp.float32) for l in leaves]
        )
    elif cast == "preserve":
        if len(dtypes) > 1:
            raise TypeError(
                "flatten_f32(cast='preserve'): tree mixes dtypes "
                f"{sorted(str(d) for d in dtypes)}; concatenation would "
                "silently promote — cast the tree to one dtype first or "
                "use cast='f32' for an explicit f32 master"
            )
        flat = jnp.concatenate([l.reshape(-1) for l in leaves])
    elif cast == "strict":
        bad = dtypes - {jnp.dtype(jnp.float32)}
        if bad:
            raise TypeError(
                "flatten_f32(cast='strict'): non-f32 leaves present "
                f"({sorted(str(d) for d in bad)}); pass cast='f32' to "
                "upcast explicitly or cast='preserve' for a uniform "
                "non-f32 master"
            )
        flat = jnp.concatenate([l.reshape(-1) for l in leaves])
    else:
        raise ValueError(
            f"flatten_f32: unknown cast={cast!r} "
            "(want 'f32', 'preserve', or 'strict')"
        )
    return jnp.pad(flat, (0, padded - flat.shape[0]))


def unflatten(flat: jnp.ndarray, like: Pytree) -> Pytree:
    """Inverse of flatten_f32: split `flat` back into `like`'s structure,
    casting each leaf to its original dtype."""
    leaves, treedef = jax.tree.flatten(like)
    out, offset = [], 0
    for leaf in leaves:
        n = leaf.size
        out.append(
            flat[offset : offset + n].reshape(leaf.shape).astype(leaf.dtype)
        )
        offset += n
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Bucketed flat layout (zero2/zero3)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """The static bucketed-flat layout shared by every zero2/zero3 site
    (scatter, update, gather, opt-state init).  ``buckets`` holds leaf
    indices per bucket in reduction order; ``padded`` is each bucket's
    flat length padded to a multiple of the axis size; ``sub`` is the
    per-position sub-chunk (``padded[b] // num_shards``); ``local`` is
    one position's total shard length (``sum(sub)``).  Frozen tuples so
    the plan can ride static (hashable) through jit/shard_map."""

    buckets: tuple[tuple[int, ...], ...]
    padded: tuple[int, ...]
    sub: tuple[int, ...]
    local: int

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)


def bucket_plan(
    params: Pytree, num_shards: int, bucket_bytes: int | None = None
) -> BucketPlan:
    """Plan the bucketed flat layout for ``params`` over ``num_shards``.

    Reuses ``native.plan_buckets`` (reverse leaf order — the order grads
    become ready in backward) on the f32-master byte sizes; each bucket
    pads independently to the axis size so every position owns an equal
    sub-chunk of every bucket.  Works on concrete arrays or
    ShapeDtypeStructs (only ``.size`` is read), so mesh-sim can plan on
    abstract params."""
    from distributeddataparallel_tpu import native

    leaves = jax.tree.leaves(params)
    groups = native.plan_buckets(
        [leaf.size * 4 for leaf in leaves], bucket_bytes or ZERO_BUCKET_BYTES
    )
    buckets, padded, sub = [], [], []
    for idxs in groups:
        size = sum(leaves[i].size for i in idxs)
        pad = -(-size // num_shards) * num_shards
        buckets.append(tuple(idxs))
        padded.append(pad)
        sub.append(pad // num_shards)
    return BucketPlan(
        buckets=tuple(buckets),
        padded=tuple(padded),
        sub=tuple(sub),
        local=sum(sub),
    )


def _flatten_bucket(leaves: list, idxs: tuple[int, ...], padded_b: int):
    flat = jnp.concatenate(
        [leaves[i].reshape(-1).astype(jnp.float32) for i in idxs]
    )
    return jnp.pad(flat, (0, padded_b - flat.shape[0]))


def scatter_grads_bucketed(
    grads: Pytree, plan: BucketPlan, axis_name: str, num_shards: int
):
    """Local per-leaf grads -> this position's reduce-scattered flat
    shard (mean over the axis).  Each bucket goes through its own
    ``psum_scatter``, so only a bucket-sized f32 staging concat plus the
    growing 1/N shard are live past the reduction — the full *reduced*
    gradient vector never exists (the ZeRO-2 memory claim), and the
    per-bucket collectives can overlap the rest of backward."""
    leaves = jax.tree.leaves(grads)
    subs = [
        lax.psum_scatter(
            _flatten_bucket(leaves, idxs, padded_b),
            axis_name,
            scatter_dimension=0,
            tiled=True,
        )
        for idxs, padded_b in zip(plan.buckets, plan.padded)
    ]
    return jnp.concatenate(subs) / num_shards


def shard_params_bucketed(params: Pytree, plan: BucketPlan, axis_name: str):
    """Local view of (replicated) params -> this position's flat f32
    shard in the bucketed layout.  The layout twin of
    ``scatter_grads_bucketed`` — element i of the result is the param
    for element i of the scattered grad shard."""
    leaves = jax.tree.leaves(params)
    idx = lax.axis_index(axis_name)
    subs = [
        lax.dynamic_slice(
            _flatten_bucket(leaves, idxs, padded_b), (idx * sub_b,), (sub_b,)
        )
        for idxs, padded_b, sub_b in zip(plan.buckets, plan.padded, plan.sub)
    ]
    return jnp.concatenate(subs)


def gather_params_bucketed(
    flat_shard, like: Pytree, plan: BucketPlan, axis_name: str
) -> Pytree:
    """This position's flat shard -> the full param tree, one
    ``all_gather`` per bucket (static slice offsets, so the unflatten is
    free at runtime).  Differentiable: AD's transpose of the gather is a
    per-bucket ``psum_scatter`` of the cotangents — which is exactly how
    zero3 gets its grads reduce-scattered without writing that code."""
    leaves, treedef = jax.tree.flatten(like)
    out: list = [None] * len(leaves)
    off = 0
    for idxs, sub_b in zip(plan.buckets, plan.sub):
        full = lax.all_gather(
            flat_shard[off : off + sub_b], axis_name, axis=0, tiled=True
        )
        o = 0
        for i in idxs:
            leaf = leaves[i]
            out[i] = (
                full[o : o + leaf.size]
                .reshape(leaf.shape)
                .astype(leaf.dtype)
            )
            o += leaf.size
        off += sub_b
    return jax.tree.unflatten(treedef, out)


# ---------------------------------------------------------------------------
# ZeRO-3 sharded-param state
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Zero3Meta:
    """Static (hashable) layout metadata for a zero3 flat param shard:
    everything needed to rebuild the structured tree from the flat
    vector.  Rides as a non-pytree field of ``Zero3Params`` so it is
    part of the jit/shard_map static signature, not a traced value."""

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[str, ...]
    plan: BucketPlan
    num_shards: int

    def like(self) -> Pytree:
        """The structured tree as ShapeDtypeStructs (shape/dtype only —
        all the gather needs)."""
        return jax.tree.unflatten(
            self.treedef,
            [
                jax.ShapeDtypeStruct(s, jnp.dtype(d))
                for s, d in zip(self.shapes, self.dtypes)
            ],
        )


@flax.struct.dataclass
class Zero3Params:
    """What ``TrainState.params`` holds at zero3: the flat f32 master
    shard (global shape ``(num_shards * plan.local,)``, sharded
    ``P(axis)``) plus the static layout meta.  The structured tree only
    exists transiently inside the step (bucketwise gather)."""

    flat: jax.Array
    meta: Zero3Meta = flax.struct.field(pytree_node=False)


def zero3_meta(params: Pytree, num_shards: int, plan: BucketPlan) -> Zero3Meta:
    leaves, treedef = jax.tree.flatten(params)
    return Zero3Meta(
        treedef=treedef,
        shapes=tuple(tuple(l.shape) for l in leaves),
        dtypes=tuple(str(jnp.dtype(l.dtype)) for l in leaves),
        plan=plan,
        num_shards=num_shards,
    )


def zero3_gather(flat_shard, meta: Zero3Meta, axis_name: str) -> Pytree:
    """Local flat shard -> full structured params (inside shard_map).
    THE zero3 forward entry: trace this inside the differentiated
    function so its transpose reduce-scatters the grads."""
    return gather_params_bucketed(flat_shard, meta.like(), meta.plan, axis_name)


def zero3_gather_params(state, mesh: Mesh, axis_name: str = "data") -> Pytree:
    """Host-side helper: materialize the full (replicated) param tree
    from a zero3 TrainState — for eval, export, or a dp-layout
    checkpoint.  Costs one full param gather; don't call it per step."""
    meta = state.params.meta
    fn = jax.jit(
        jax.shard_map(
            lambda f: zero3_gather(f, meta, axis_name),
            mesh=mesh,
            in_specs=(P(axis_name),),
            out_specs=jax.tree.map(lambda _: P(), meta.like()),
            check_vma=False,
        )
    )
    return fn(state.params.flat)


# ---------------------------------------------------------------------------
# Low-bit optimizer moments
# ---------------------------------------------------------------------------


class LowBitMomentState(NamedTuple):
    """Wrapper state: the inner tx's state with large float vectors held
    compressed, plus the PRNG key that drives the stochastic-rounding
    writeback."""

    inner: Any
    key: jax.Array


def low_bit_moments(
    tx: optax.GradientTransformation,
    moment_dtype: str | None,
    *,
    seed: int = 0,
    min_size: int = 256,
) -> optax.GradientTransformation:
    """Store ``tx``'s moment vectors in ``moment_dtype`` between steps.

    Each step: decompress -> inner ``tx.update`` in f32 -> recompress
    with STOCHASTIC rounding (``ops/quant``), so the quantization error
    enters the moment EMA as zero-mean noise rather than a systematic
    truncation bias — the error compensation that keeps low-bit Adam
    converging.  ``moment_dtype``:

    - ``None``/``"f32"``: returns ``tx`` unchanged.
    - ``"bf16"``: float vectors >= ``min_size`` elements kept as bf16
      (2 bytes/param/moment).
    - ``"int8"``: kept as blockwise-absmax int8 + per-block f32 scales
      (~1 byte/param/moment; ``ops.quant.MOMENT_BLOCK`` block length).

    Scalars and small leaves (bias-correction counts, etc.) stay f32.
    Key threading is data-independent, so identical keys across mesh
    positions are fine — each position quantizes different elements.
    """
    if moment_dtype in (None, "f32", "float32"):
        return tx
    if moment_dtype not in ("bf16", "bfloat16", "int8"):
        raise ValueError(
            f"low_bit_moments: moment_dtype={moment_dtype!r} "
            "(want None/'f32', 'bf16', or 'int8')"
        )
    from distributeddataparallel_tpu.ops.quant import (
        Q8Moment,
        dequantize_moment,
        quantize_moment_int8,
        stochastic_round_bf16,
    )

    to_int8 = moment_dtype == "int8"

    def _compressible(leaf) -> bool:
        return (
            not isinstance(leaf, Q8Moment)
            and hasattr(leaf, "dtype")
            and jnp.issubdtype(leaf.dtype, jnp.floating)
            and getattr(leaf, "ndim", 0) == 1
            and leaf.size >= min_size
        )

    def _compress(tree, key):
        leaves, treedef = jax.tree.flatten(tree)
        out = []
        for i, leaf in enumerate(leaves):
            if _compressible(leaf):
                k = jax.random.fold_in(key, i)
                out.append(
                    quantize_moment_int8(leaf, k)
                    if to_int8
                    else stochastic_round_bf16(leaf, k)
                )
            else:
                out.append(leaf)
        return jax.tree.unflatten(treedef, out)

    def _decompress(tree):
        def _dq(leaf):
            if isinstance(leaf, Q8Moment):
                return dequantize_moment(leaf)
            if hasattr(leaf, "dtype") and leaf.dtype == jnp.bfloat16:
                return leaf.astype(jnp.float32)
            return leaf

        return jax.tree.map(
            _dq, tree, is_leaf=lambda x: isinstance(x, Q8Moment)
        )

    def init(params):
        key, sub = jax.random.split(jax.random.PRNGKey(seed))
        return LowBitMomentState(inner=_compress(tx.init(params), sub), key=key)

    def update(updates, state, params=None):
        new_updates, new_inner = tx.update(
            updates, _decompress(state.inner), params
        )
        key, sub = jax.random.split(state.key)
        return new_updates, LowBitMomentState(
            inner=_compress(new_inner, sub), key=key
        )

    return optax.GradientTransformation(init, update)


def _leaf_spec(
    leaf,
    axis_name: str,
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    pp_axis: str | None = None,
):
    """The ZeRO layout rule, in one place: vector state (flat momentum,
    mu/nu chunks) is sharded along the data axis — jointly with any
    model axes (Megatron TP / expert EP) when params are sharded over
    them, since each model position flattens a DIFFERENT local param
    shard; scalars (step counts) stay replicated."""
    if getattr(leaf, "ndim", 0) < 1:
        return P()
    axes = (axis_name,) + tuple(
        a for a in (tp_axis, ep_axis, pp_axis) if a is not None
    )
    return P(axes if len(axes) > 1 else axis_name)


def opt_state_specs(
    tx: optax.GradientTransformation,
    chunk: int,
    axis_name: str = "data",
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    pp_axis: str | None = None,
) -> Pytree:
    """PartitionSpec tree for a tx.init over a flat chunk."""
    shapes = jax.eval_shape(
        tx.init, jax.ShapeDtypeStruct((chunk,), jnp.float32)
    )
    return jax.tree.map(
        lambda s: _leaf_spec(s, axis_name, tp_axis, ep_axis, pp_axis), shapes
    )


def _param_specs(
    params: Pytree,
    tp_axis: str | None,
    ep_axis: str | None = None,
    pp_axis: str | None = None,
) -> Pytree:
    """Param layout for the ZeRO machinery: replicated, or the combined
    Megatron/expert layout when composing with TP/EP — and the stacked
    layer-dim pipeline layout (Megatron/expert rules composing
    underneath) when composing with PP.  The ONE spec source shared by
    init, state build, and the train step's in_specs."""
    if pp_axis is not None:
        from distributeddataparallel_tpu.parallel.pipeline_parallel import (
            pp_param_specs,
        )

        return pp_param_specs(params, pp_axis, tp_axis, ep_axis)
    from distributeddataparallel_tpu.parallel.expert_parallel import (
        model_axes_param_specs,
    )

    return model_axes_param_specs(params, tp_axis, ep_axis)


def _local_chunk(
    params: Pytree, param_specs: Pytree, mesh: Mesh, num_shards: int
) -> int:
    """Per-position flat chunk length when params are sharded by
    ``param_specs`` (host-side mirror of what ``flat_size`` sees on local
    shapes inside shard_map).  ``shard_shape`` raises on non-divisible
    dims, so a bad layout fails here, loudly, not as a downstream
    out_specs mismatch."""
    import math

    from jax.sharding import NamedSharding

    total = sum(
        math.prod(NamedSharding(mesh, spec).shard_shape(leaf.shape))
        for leaf, spec in zip(
            jax.tree.leaves(params), jax.tree.leaves(param_specs)
        )
    )
    return -(-total // num_shards)


def shard_opt_state(
    params: Pytree,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = "data",
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    pp_axis: str | None = None,
    plan: BucketPlan | None = None,
) -> Pytree:
    """Initialize optimizer state sharded 1/N per mesh position.

    Each position runs ``tx.init`` on its own flat param chunk; vector
    state (momentum, mu/nu) therefore never exists fully replicated.
    Under ``tp_axis``/``ep_axis`` the flattened vector is each position's
    LOCAL Megatron/expert shard, so the flat state is additionally
    sharded over those model axes (state memory drops by the product of
    all the axis sizes per chip).

    With ``plan`` (zero2/zero3), the chunk uses the BUCKETED layout —
    the same ``BucketPlan`` the step's scatter/gather uses, so the opt
    vectors line up element-for-element with the scattered grads.
    """
    n = mesh.shape[axis_name]
    if plan is not None:

        def init_shard(p):
            return tx.init(shard_params_bucketed(p, plan, axis_name))

        pspecs = jax.tree.map(lambda _: P(), params)
        chunk = plan.local
    else:

        def init_shard(p):
            padded_l, chunk_l = flat_size(p, n)  # local (traced) shapes
            flat = flatten_f32(p, padded_l)
            idx = lax.axis_index(axis_name)
            return tx.init(
                lax.dynamic_slice(flat, (idx * chunk_l,), (chunk_l,))
            )

        pspecs = _param_specs(params, tp_axis, ep_axis, pp_axis)
        chunk = _local_chunk(params, pspecs, mesh, n)

    fn = jax.jit(
        jax.shard_map(
            init_shard,
            mesh=mesh,
            in_specs=(pspecs,),
            out_specs=opt_state_specs(
                tx, chunk, axis_name, tp_axis, ep_axis, pp_axis
            ),
            check_vma=False,
        )
    )
    return fn(params)


def zero_state(
    *,
    apply_fn,
    params: Pytree,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    axis_name: str = "data",
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    pp_axis: str | None = None,
    model_state: Pytree | None = None,
    level: int = 1,
    moment_dtype: str | None = None,
    bucket_bytes: int | None = None,
):
    """Build a TrainState whose optimizer state is ZeRO-sharded.

    Drop-in replacement for ``TrainState.create`` when using
    ``make_train_step(..., zero=level)``.  With ``tp_axis``/``ep_axis``,
    params are placed in the Megatron/expert layout and the flat
    optimizer state shards over ALL the axes — pass the same axes to
    ``make_train_step``.

    ``level``: 1 (sharded opt state, replicated params — the original
    path), 2 (bucketed layout, reduce-scattered grads), or 3 (params
    additionally stay sharded between steps as ``Zero3Params``).
    Levels 2/3 shard over the data axis only — compose model axes with
    level 1 or the fsdp path instead.  ``bucket_bytes`` sets the
    zero2/3 bucket granularity and MUST match the value given to
    ``make_train_step`` (both default to ``ZERO_BUCKET_BYTES``; a
    mismatch fails loudly as a flat-length mismatch at trace time).
    ``moment_dtype``: see ``low_bit_moments``.
    """
    from distributeddataparallel_tpu.training.state import TrainState

    level = int(level)
    if level not in (1, 2, 3):
        raise ValueError(f"zero_state: level={level!r} (want 1, 2, or 3)")
    if level >= 2 and (
        tp_axis is not None or ep_axis is not None or pp_axis is not None
    ):
        raise ValueError(
            "zero_state: level 2/3 shard over the data axis only; "
            "compose tp/ep/pp with level=1 or use the fsdp path"
        )
    tx = low_bit_moments(tx, moment_dtype)
    n = mesh.shape[axis_name]
    # The step counter rides the mesh replicated in EVERY layout: a
    # checkpoint restore uses the template's shardings leaf-for-leaf,
    # and an uncommitted scalar restores COMMITTED to device 0 — which
    # makes the restored state unsteppable next to mesh-committed
    # params/opt chunks.
    from jax.sharding import NamedSharding

    step0 = jax.device_put(
        jnp.zeros((), jnp.int32), NamedSharding(mesh, P())
    )

    if level == 3:
        plan = bucket_plan(params, n, bucket_bytes)
        meta = zero3_meta(params, n, plan)
        rep = jax.tree.map(lambda _: P(), params)

        def init_fn(p):
            flat = shard_params_bucketed(p, plan, axis_name)
            return flat, tx.init(flat)

        flat, opt_state = jax.jit(
            jax.shard_map(
                init_fn,
                mesh=mesh,
                in_specs=(rep,),
                out_specs=(
                    P(axis_name),
                    opt_state_specs(tx, plan.local, axis_name),
                ),
                check_vma=False,
            )
        )(params)
        return TrainState(
            step=step0,
            params=Zero3Params(flat=flat, meta=meta),
            opt_state=opt_state,
            model_state=model_state if model_state is not None else {},
            apply_fn=apply_fn,
            tx=tx,
        )

    if level == 2:
        plan = bucket_plan(params, n, bucket_bytes)
        return TrainState(
            step=step0,
            params=params,
            opt_state=shard_opt_state(params, tx, mesh, axis_name, plan=plan),
            model_state=model_state if model_state is not None else {},
            apply_fn=apply_fn,
            tx=tx,
        )

    if tp_axis is not None or ep_axis is not None or pp_axis is not None:
        params = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            params,
            _param_specs(params, tp_axis, ep_axis, pp_axis),
        )
    return TrainState(
        step=step0,
        params=params,
        opt_state=shard_opt_state(
            params, tx, mesh, axis_name, tp_axis, ep_axis, pp_axis
        ),
        model_state=model_state if model_state is not None else {},
        apply_fn=apply_fn,
        tx=tx,
    )


def zero_update(
    grads: Pytree,
    state,
    axis_name: str,
    num_shards: int,
    clip_norm: float | None = None,
    model_axes: tuple = (),
    local_specs: Pytree | None = None,
):
    """The sharded-update step body (runs inside shard_map).

    grads are this replica's *local* (unreduced) gradients; returns
    (new_params, new_opt_state) with params fully replicated again.
    ``num_shards`` is the static data-axis size (chunk sizes must be
    known at trace time).

    ``clip_norm``: clip the (synced) gradient to this global L2 norm —
    EXACT despite the sharded layout: the chunks partition the full
    gradient vector, so the global norm² is one psum of local chunk
    norm²s.  Under model-axis composition, pass ``model_axes`` (the
    tp/ep/pp mesh axis names) and ``local_specs`` (the per-leaf
    PartitionSpec tree for the local grads — the same tree the caller's
    in_specs came from): each position's flat holds its LOCAL tree, so
    model-sharded leaves appear once across positions while leaves
    replicated over an axis appear size(axis) times; elements are
    de-weighted by that duplicate count (``flat_chunk_sumsq``) before
    psumming over the data axis AND every model axis.
    """
    n = num_shards
    idx = lax.axis_index(axis_name)
    padded, chunk = flat_size(state.params, n)

    # reduce_scatter: each replica receives the SUM of its 1/N chunk,
    # divided for DDP mean semantics (ref dpp.py grad averaging).
    with jax.named_scope(scopes.GRAD_SYNC):
        flat_g = flatten_f32(grads, padded)
        g_shard = lax.psum_scatter(
            flat_g, axis_name, scatter_dimension=0, tiled=True
        ) / n
    if clip_norm is not None:
        from distributeddataparallel_tpu.parallel.data_parallel import (
            clip_scale,
            flat_chunk_sumsq,
            spec_axes,
            sumsq_f32,
        )

        with jax.named_scope(scopes.GRAD_CLIP):
            if model_axes:
                if local_specs is None:
                    raise ValueError(
                        "clip under model_axes needs local_specs (the "
                        "per-leaf PartitionSpec tree of the local grads)"
                    )
                # Per-leaf duplicate count: product of the model-axis sizes
                # the leaf is NOT sharded over (its copies across those
                # positions are identical).  Static at trace time.
                sizes = [l.size for l in jax.tree.leaves(grads)]
                dups = [
                    int(np.prod([
                        lax.axis_size(ax)
                        for ax in model_axes
                        if ax not in spec_axes(sp)
                    ] or [1]))
                    for sp in jax.tree.leaves(
                        local_specs,
                        is_leaf=lambda x: isinstance(x, P),
                    )
                ]
                s = flat_chunk_sumsq(g_shard, idx * chunk, sizes, dups)
                s = lax.psum(s, axis_name)
                for ax in model_axes:
                    s = lax.psum(s, ax)
                gnorm = jnp.sqrt(s)
            else:
                gnorm = jnp.sqrt(lax.psum(sumsq_f32(g_shard), axis_name))
            g_shard = g_shard * clip_scale(gnorm, clip_norm)

    with jax.named_scope(scopes.OPTIMIZER):
        flat_p = flatten_f32(state.params, padded)
        p_shard = lax.dynamic_slice(flat_p, (idx * chunk,), (chunk,))

        updates, new_opt_state = state.tx.update(
            g_shard, state.opt_state, p_shard
        )
        new_p_shard = optax.apply_updates(p_shard, updates)

        new_flat = lax.all_gather(new_p_shard, axis_name, axis=0, tiled=True)
        new_params = unflatten(new_flat, state.params)
    return new_params, new_opt_state


def _clip_shard(g_shard, axis_name: str, clip_norm: float):
    """Clip a flat gradient shard to the global norm: the shards partition
    the gradient vector, so norm² is one psum of the local norm²s."""
    from distributeddataparallel_tpu.parallel.data_parallel import (
        clip_scale,
        sumsq_f32,
    )

    with jax.named_scope(scopes.GRAD_CLIP):
        gnorm = jnp.sqrt(lax.psum(sumsq_f32(g_shard), axis_name))
        return g_shard * clip_scale(gnorm, clip_norm)


def zero2_update(
    grads: Pytree,
    state,
    axis_name: str,
    num_shards: int,
    plan: BucketPlan,
    clip_norm: float | None = None,
):
    """ZeRO-2 step body (inside shard_map): per-bucket reduce-scatter of
    the local grads, sharded update, per-bucket all-gather of the new
    params.  ``plan`` must be the SAME plan the opt state was built with
    (``zero_state(level=2)``).  Clipping is exact: the bucketed shards
    partition the gradient vector (padding is zeros), so the global
    norm² is one psum of local chunk norm²s."""
    with jax.named_scope(scopes.GRAD_SYNC):
        g_shard = scatter_grads_bucketed(grads, plan, axis_name, num_shards)
    if clip_norm is not None:
        g_shard = _clip_shard(g_shard, axis_name, clip_norm)

    with jax.named_scope(scopes.OPTIMIZER):
        p_shard = shard_params_bucketed(state.params, plan, axis_name)
        updates, new_opt_state = state.tx.update(
            g_shard, state.opt_state, p_shard
        )
        new_p_shard = optax.apply_updates(p_shard, updates)
        new_params = gather_params_bucketed(
            new_p_shard, state.params, plan, axis_name
        )
    return new_params, new_opt_state


def zero3_update(
    g_shard,
    state,
    axis_name: str,
    num_shards: int,
    clip_norm: float | None = None,
):
    """ZeRO-3 step body (inside shard_map): the grads arrive ALREADY
    reduce-scattered — ``g_shard`` is the flat local cotangent of
    ``state.params.flat``, summed over the axis by the transpose of the
    bucketwise gather in forward.  Divide for mean semantics, update the
    shard, done: the new flat shard IS the next state's params (the
    re-gather happens at the top of the next step).  Returns
    (new_flat, new_opt_state)."""
    with jax.named_scope(scopes.GRAD_SYNC):
        g_shard = g_shard / num_shards
    if clip_norm is not None:
        g_shard = _clip_shard(g_shard, axis_name, clip_norm)

    with jax.named_scope(scopes.OPTIMIZER):
        p_shard = state.params.flat
        updates, new_opt_state = state.tx.update(
            g_shard, state.opt_state, p_shard
        )
        new_flat = optax.apply_updates(p_shard, updates)
    return new_flat, new_opt_state


def state_specs(
    state,
    axis_name: str = "data",
    tp_axis: str | None = None,
    ep_axis: str | None = None,
    pp_axis: str | None = None,
) -> Pytree:
    """Per-leaf PartitionSpec tree for a ZeRO TrainState: everything
    replicated except the flat (ndim>=1) optimizer-state vectors — and,
    under ``tp_axis``/``ep_axis``/``pp_axis``, the sharded params.  A
    zero3 state's ``Zero3Params.flat`` shards along the data axis."""
    opt_specs = jax.tree.map(
        lambda l: _leaf_spec(l, axis_name, tp_axis, ep_axis, pp_axis),
        state.opt_state,
    )
    if isinstance(state.params, Zero3Params):
        param_specs = Zero3Params(flat=P(axis_name), meta=state.params.meta)
    else:
        param_specs = _param_specs(state.params, tp_axis, ep_axis, pp_axis)
    return state.replace(
        step=P(),
        params=param_specs,
        opt_state=opt_specs,
        model_state=jax.tree.map(lambda _: P(), state.model_state),
    )
