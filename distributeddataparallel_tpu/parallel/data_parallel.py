"""Data-parallel gradient synchronization: the TPU-native DDP core.

What ``DDP(model, device_ids=[rank])`` (ref dpp.py:39) does imperatively —
broadcast initial params, hook autograd, bucket gradients into 25 MiB
groups, all-reduce each bucket asynchronously overlapped with backward,
divide by world size — falls out declaratively in SPMD JAX:

- *param broadcast*  → ``broadcast_params``: replicate across the mesh
  (and across hosts from process 0, the exact analog of DDP's rank-0
  broadcast).
- *grad hooks + all-reduce* → ``all_reduce_gradients``: ``lax.pmean`` per
  leaf over the ``data`` mesh axis inside the jit'd step; XLA's combiner
  merges the leaves into a few large all-reduces and schedules them (how
  much of the exchange is hidden behind the backward is read on the chip:
  ``train_exposed_collective_frac``, PERF.md).
- *bucketing* → ``bucket_gradients``: optional explicit 25 MiB-style
  coalescing of gradient leaves into a few large all-reduces.  Stock XLA
  usually makes this unnecessary; it exists for parity with BASELINE
  config 4 ("bucketed psum all-reduce"), and ZeRO-2/3 size their
  scatter/gather stream with the same ``bucket_bytes``.
- *no_sync / grad accumulation* → handled in ``training.train_step`` by
  accumulating microbatch grads locally and reducing once per boundary.

All reduction helpers are designed to run **inside** ``shard_map`` (they
reference a named mesh axis).
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributeddataparallel_tpu.observability import scopes

Pytree = Any

#: DDP's default bucket size: 25 MiB (SURVEY.md §2b, torch Reducer default).
DEFAULT_BUCKET_BYTES = 25 * 1024 * 1024


def _check_compress(compress: str | None) -> None:
    if compress not in (None, "bf16"):
        raise ValueError(f"compress must be None or 'bf16', got {compress!r}")


def all_reduce_gradients(
    grads: Pytree,
    axis_name: str = "data",
    *,
    op: str = "mean",
    bucket_bytes: int | None = None,
    compress: str | None = None,
) -> Pytree:
    """All-reduce a gradient pytree across the data axis (inside shard_map).

    ``op='mean'`` reproduces DDP's divide-by-world-size so every replica
    holds averaged gradients and stays in lockstep under a local optimizer
    step (ref dpp.py:52-53 semantics).  ``bucket_bytes=None`` reduces
    leaf by leaf; a size coalesces the leaves first (``bucket_gradients``).

    ``compress='bf16'`` is the comm-hook analog of torch DDP's
    ``bf16_compress_hook`` (the stack behind ref dpp.py:52's
    ``register_comm_hook`` surface): gradients cross the wire in
    bfloat16 — half the bytes of f32 — and are cast back to each leaf's
    dtype after the reduce.  bf16 keeps f32's exponent range, so unlike
    the fp16 hook no loss-scaling is needed; replicas remain in lockstep
    because every replica sees the SAME compressed-then-averaged value.
    """
    if op not in ("mean", "sum"):
        raise ValueError(f"op must be 'mean' or 'sum', got {op!r}")
    _check_compress(compress)
    red = lax.pmean if op == "mean" else lax.psum

    def _leaf(g):
        if compress == "bf16" and g.dtype == jnp.float32:
            return red(g.astype(jnp.bfloat16), axis_name).astype(g.dtype)
        return red(g, axis_name)

    # One scope for the whole exchange — packing, collective, unpacking —
    # so the device trace can give it an owner (observability/scopes.py).
    with jax.named_scope(scopes.GRAD_SYNC):
        if bucket_bytes is not None:
            return bucket_gradients(
                grads, axis_name, op=op, bucket_bytes=bucket_bytes,
                compress=compress,
            )
        return jax.tree.map(_leaf, grads)


def bucket_gradients(
    grads: Pytree,
    axis_name: str = "data",
    *,
    op: str = "mean",
    bucket_bytes: int = DEFAULT_BUCKET_BYTES,
    compress: str | None = None,
) -> Pytree:
    """Coalesced all-reduce: flatten grad leaves into ~bucket_bytes groups,
    reduce each group as one flat f32 vector, scatter back (each leaf
    returns in its own dtype).

    The explicit analog of DDP's Reducer bucketing (25 MiB default).  Like
    DDP, buckets are formed in *reverse* leaf order so the bucket containing
    the last-computed (earliest-layer) grads is reduced last — giving the
    XLA scheduler the same freedom to overlap early buckets with remaining
    backward work.
    """
    from distributeddataparallel_tpu import native

    _check_compress(compress)
    leaves, treedef = jax.tree.flatten(grads)
    # Reverse-order ~bucket_bytes grouping, planned by the native layer
    # (the role DDP gives its C++ Reducer); runs at trace time.
    buckets = native.plan_buckets(
        [l.size * l.dtype.itemsize for l in leaves], bucket_bytes
    )

    reduced: list[Any] = [None] * len(leaves)
    # Static mean divisor: lax.psum(1, axis) would materialize a scalar
    # all-reduce per bucket on the TPU backend; the axis size is known at
    # trace time.
    inv_n = 1.0 / lax.axis_size(axis_name)
    for bucket in buckets:
        bdt = jnp.float32
        if compress == "bf16" and all(
            leaves[i].dtype == jnp.float32 for i in bucket
        ):
            # bf16 comm-hook (torch bf16_compress_hook semantics:
            # compress -> average -> decompress), f32 buckets only — the
            # same predicate the unbucketed leaf path applies.  A bucket
            # holding sub-f32 leaves (bf16/fp16 grads) must not take a
            # second precision hit, and an f64 leaf must not silently
            # drop 45 mantissa bits on the wire.
            bdt = jnp.bfloat16
        if len(bucket) == 1:
            # Single-leaf bucket: skip the concat/flatten round-trip —
            # the leaf keeps its layout.
            flat = leaves[bucket[0]].astype(bdt)
        else:
            flat = jnp.concatenate(
                [leaves[i].reshape(-1).astype(bdt) for i in bucket]
            )
        flat = lax.psum(flat, axis_name)
        if op == "mean":
            flat = flat * jnp.asarray(inv_n, bdt)
        if len(bucket) == 1:
            i = bucket[0]
            reduced[i] = flat.astype(leaves[i].dtype)
            continue
        offset = 0
        for i in bucket:
            n = leaves[i].size
            reduced[i] = (
                flat[offset : offset + n]
                .reshape(leaves[i].shape)
                .astype(leaves[i].dtype)
            )
            offset += n
    return jax.tree.unflatten(treedef, reduced)


def comm_schedule_ir(
    params,
    *,
    bucket_bytes: int | None = None,
    axis: str = "data",
    prim: str = "psum",
):
    """The bucketed grad-sync order as schedule IR (``ScheduleIR``,
    kind="grad-sync"): one tick per bucket, buckets planned from the
    param tree by the SAME planner the traced step uses
    (``native.plan_buckets``), so the SL302 traced-count check catches
    the step and the plan diverging (e.g. a refactor dropping the
    coalescing).

    ``bucket_bytes=None`` means leaf-sized buckets (one psum per leaf).
    Attached by ``make_train_step`` as ``step.comm_schedule(params)`` —
    a builder, not a constant, because the partition depends on the
    param tree the step is eventually called with.
    """
    from distributeddataparallel_tpu import native
    from distributeddataparallel_tpu.analysis.schedule_lint import (
        grad_sync_schedule_ir,
    )

    leaves = jax.tree.leaves(params)
    if bucket_bytes is None:
        n_buckets = len(leaves)
    else:
        n_buckets = len(native.plan_buckets(
            [l.size * l.dtype.itemsize for l in leaves], bucket_bytes
        ))
    return grad_sync_schedule_ir(n_buckets, axis=axis, prim=prim)


def sync_grad_in_backward(
    x: Pytree,
    axis_name: str,
    *,
    op: str = "mean",
    compress: str | None = None,
):
    """Identity on the forward; all-reduces the COTANGENT over
    ``axis_name`` on the backward.

    Applied to a parameter *use site* inside a ``lax.scan`` body (the
    scanned transformer block reads its per-layer param slice through
    this, ``models.transformer grad_sync_axis``), the gradient of that
    slice is reduced INSIDE the backward scan iteration — which is the
    only place a scanned model's layer grads exist before the loop
    stacks them: a reduction after the loop has no backward left to run
    beside, one inside the body has the rest of that trip's.  No chip
    run has timed it (no cell scans its layers; ROADMAP D4, D18).  The
    train step must then SKIP these leaves in its own sync
    (``make_train_step(presynced=...)``) — re-reducing an averaged
    gradient is numerically a no-op but pays the full wire bytes twice.

    Forward-only applies (eval, decode) never touch the axis, so the
    model stays usable outside ``shard_map``.

    ``compress='bf16'``: the cotangent crosses the wire in bfloat16 (the
    in-scan-body arm of the bf16 comm hook — see
    ``all_reduce_gradients``).
    """
    _check_compress(compress)

    @jax.custom_vjp
    def ident(t):
        return t

    def fwd(t):
        return t, None

    def bwd(_, g):
        red = lax.pmean if op == "mean" else lax.psum
        if compress == "bf16" and g.dtype == jnp.float32:
            return (red(g.astype(jnp.bfloat16), axis_name).astype(g.dtype),)
        return (red(g, axis_name),)

    ident.defvjp(fwd, bwd)
    return jax.tree.map(ident, x)


def sumsq_f32(tree: Pytree):
    """Sum of squares of every leaf, accumulated in float32 (bf16 grads
    would lose the norm to ~8 mantissa bits) — the building block for
    global-norm clipping in every layout (replicated, ZeRO chunks, FSDP
    flats: sharded layouts psum this across their axis, which is exact
    because the shards partition the gradient vector)."""
    import jax.numpy as jnp

    return sum(
        jnp.sum(l.astype(jnp.float32) ** 2) for l in jax.tree.leaves(tree)
    )


def spec_axes(spec) -> tuple:
    """Mesh axis names a PartitionSpec shards over (flattened, deduped)."""
    axes = []
    for part in tuple(spec):
        if part is None:
            continue
        for ax in (part if isinstance(part, tuple) else (part,)):
            if ax is not None and ax not in axes:
                axes.append(ax)
    return tuple(axes)


def model_axes_sumsq(grads: Pytree, specs: Pytree):
    """Exact global gradient sum-of-squares under model-axis sharding
    (inside shard_map) — the global-norm-clip building block for
    TP/EP/PP layouts.

    Per leaf: the local shard's f32 sumsq, psum'd over every mesh axis
    the leaf's PartitionSpec shards it on.  Leaves replicated over an
    axis are identical there (the conjugate custom-VJP ops complete
    their grads per position), so no psum — adding them once per
    position is the de-duplication.  The total is identical on every
    mesh position, which is what makes a uniform clip scale safe.
    """
    gl = jax.tree.leaves(grads)
    sl = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    if len(gl) != len(sl):
        raise ValueError(
            f"grads/specs leaf count mismatch: {len(gl)} vs {len(sl)}"
        )
    total = jnp.zeros((), jnp.float32)
    for g, sp in zip(gl, sl):
        s = jnp.sum(g.astype(jnp.float32) ** 2)
        for ax in spec_axes(sp):
            s = lax.psum(s, ax)
        total = total + s
    return total


def flat_chunk_sumsq(
    chunk,
    chunk_start,
    leaf_sizes: Sequence[int],
    leaf_dup: Sequence[int],
):
    """Sum-of-squares of one flat-layout gradient chunk with duplicate
    de-weighting — the ZeRO/FSDP-side counterpart of
    ``model_axes_sumsq``.

    The flat vector concatenates leaves (``leaf_sizes`` elements each,
    then zero padding); ``leaf_dup[i]`` is how many model-axis positions
    hold an identical copy of leaf i (1 = sharded/unique).  Elements of
    duplicated leaves contribute ``x²/dup`` so that the subsequent psum
    over the model axes counts them exactly once.  ``chunk_start`` may
    be traced (``axis_index * chunk``).
    """
    x2 = chunk.astype(jnp.float32) ** 2
    pos = chunk_start + jnp.arange(chunk.shape[0])
    w = jnp.ones_like(x2)
    off = 0
    for size, dup in zip(leaf_sizes, leaf_dup):
        if dup != 1:
            w = jnp.where(
                (pos >= off) & (pos < off + size), 1.0 / dup, w
            )
        off += size
    return jnp.sum(x2 * w)


def clip_scale(gnorm, clip_norm: float):
    """min(1, clip/norm): the torch clip_grad_norm_ scale factor — ONE
    definition (epsilon included) shared by the replicated, ZeRO, and
    FSDP clip paths."""
    import jax.numpy as jnp

    return jnp.minimum(1.0, clip_norm / (gnorm + 1e-12))


def broadcast_params(params: Pytree, mesh: Mesh) -> Pytree:
    """Replicate params across every device of the mesh.

    The analog of DDP's construction-time broadcast of rank-0 parameters
    (SURVEY.md §2b "Gradient synchronization" (i)).  Within one process this
    is a replicated ``device_put``; across processes, values from process 0
    are broadcast to all so every host starts from identical weights even if
    their host-side RNG diverged.
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        params = multihost_utils.broadcast_one_to_all(params)
    return jax.device_put(params, NamedSharding(mesh, P()))


class DataParallel:
    """Object-style facade over the mesh, mirroring the DDP wrapper's role.

    Where the reference writes::

        model = DDP(model, device_ids=[rank])          # ref dpp.py:39

    this framework writes::

        dp = DataParallel(mesh)                        # or DataParallel()
        params = dp.replicate(params)                  # DDP ctor broadcast
        step = make_train_step(loss_fn, opt, mesh=dp.mesh)
        batch = dp.shard_batch(batch)                  # data -> 'data' axis

    It owns no gradient machinery itself — synchronization lives inside the
    compiled step — but centralizes mesh construction, replication, and
    batch sharding so user code never touches device objects (the analog of
    ``.to(rank)`` at ref dpp.py:38,48 disappearing).
    """

    def __init__(
        self,
        mesh: Mesh | None = None,
        *,
        axis_name: str = "data",
        devices: Sequence[jax.Device] | None = None,
    ):
        if mesh is None:
            from distributeddataparallel_tpu.runtime.distributed import make_mesh

            mesh = make_mesh((axis_name,), devices=devices)
        if axis_name not in mesh.axis_names:
            raise ValueError(
                f"axis {axis_name!r} not in mesh axes {mesh.axis_names}"
            )
        self.mesh = mesh
        self.axis_name = axis_name

    @property
    def num_replicas(self) -> int:
        return self.mesh.shape[self.axis_name]

    def replicate(self, tree: Pytree) -> Pytree:
        return broadcast_params(tree, self.mesh)

    def shard_batch(self, batch: Pytree) -> Pytree:
        """Place a host batch sharded along the data axis (single impl in
        ``data.loader.shard_batch``: sharded device_put on one host,
        per-process global-array assembly multi-host)."""
        from distributeddataparallel_tpu.data.loader import shard_batch

        return shard_batch(batch, self.mesh, self.axis_name)


def masked_tree_mean(
    metrics: Pytree,
    mask: jnp.ndarray,
    axis_name: str,
    seq_axis: str | None = None,
):
    """Global masked mean of per-row metric trees: ``(means, count)``.

    ``metrics`` leaves are per-row vectors on this shard; ``mask`` is the
    matching (rows,) validity mask (0 on sampler-padded duplicate rows).
    With ``seq_axis`` set (DP×CP), per-row values are first pmean'd over
    the sequence axis — chunks are equal-length, so that is the exact
    global per-row mean — before the masked reduction over ``axis_name``.
    The single implementation keeps DP and DP×CP eval semantics from
    drifting (used by ``make_eval_step`` / ``make_cp_eval_step``).
    """
    mask = mask.astype(jnp.float32)
    den = lax.psum(jnp.sum(mask), axis_name)

    def _mean(v):
        v = v.astype(jnp.float32)
        if seq_axis is not None:
            v = lax.pmean(v, seq_axis)
        num = lax.psum(jnp.sum(v * mask), axis_name)
        return num / jnp.maximum(den, 1.0)

    return jax.tree.map(_mean, metrics), den
