"""Context parallelism: ring attention over a sequence mesh axis.

The reference has no attention model and no sequence dimension at all
(SURVEY.md §5 "Long-context"); this module is the framework's long-context
scaling path, built the TPU way:

- The sequence dimension is sharded across a ``seq`` mesh axis: each
  device holds a (B, S/N, H, D) slice of q, k, v.
- **Ring attention** (Liu et al., arXiv 2310.01889 pattern): kv chunks
  rotate around the ring with ``lax.ppermute`` over ICI while each device
  accumulates blockwise attention of its local queries against the
  visiting chunk using the same online-softmax update as the flash kernel
  (``ops.pallas_attention``) — the full (S, S) score matrix never exists,
  and per-device memory stays O(S/N).
- XLA overlaps the ppermute transfer of chunk s+1 with the attention
  compute of chunk s (the latency-hiding scheduler sees independent
  DMA/compute chains), which is the property that makes the ring scale.
- Causal masking uses *global* offsets derived from ``lax.axis_index``,
  so cross-chunk blocks mask correctly; fully-masked visiting chunks
  still traverse the ring (uniform schedule) but their contribution is
  exactly zero.

``ring_attention`` is a collective op: it must run inside ``shard_map``
with ``axis_name`` bound.  ``make_cp_train_step`` wires it (together with
data parallelism on a second axis) into a compiled LM training step where
activations are sequence-sharded end to end — embeddings, norms, and MLPs
are per-token and need no communication; attention is the one collective.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from distributeddataparallel_tpu.ops.attention import NEG_INF, causal_mask_bias

Pytree = Any


def _flash_ring_fwd_impl(q, k, v, axis_name: str, interpret: bool):
    """Ring forward where each hop's block is the PALLAS flash kernel.

    Hop 0 runs the causal diagonal; later hops run the visiting chunk
    unmasked (its keys are strictly earlier) and wrapped chunks (strictly
    later keys) are zeroed by forcing their lse to -inf before the
    online-softmax merge of normalized partials:
    ``o = Σ o_i · exp(lse_i - logaddexp(lse…))``.
    Returns ``(out, lse)`` with lse (B, H, S) f32 — the backward's
    global row statistics.
    """
    from distributeddataparallel_tpu.ops.pallas_attention import (
        _flash_fwd_impl,
    )

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    out, lse8 = _flash_fwd_impl(q, k, v, causal=True, interpret=interpret)
    lse = lse8[:, 0, :].reshape(B, H, S)
    of = out.astype(jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(carry, s):
        kc, vc, of, lse = carry
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        oh, lseh8 = _flash_fwd_impl(
            q, kc, vc, causal=False, interpret=interpret
        )
        lseh = lseh8[:, 0, :].reshape(B, H, S)
        # After s hops this device holds chunk idx - s; wrapped (future)
        # chunks contribute nothing.
        lseh = jnp.where(idx - s >= 0, lseh, NEG_INF)
        lse_new = jnp.logaddexp(lse, lseh)
        w_old = jnp.exp(lse - lse_new).transpose(0, 2, 1)[..., None]
        w_new = jnp.exp(lseh - lse_new).transpose(0, 2, 1)[..., None]
        of = of * w_old + oh.astype(jnp.float32) * w_new
        return (kc, vc, of, lse_new), None

    (_, _, of, lse), _ = lax.scan(
        hop, (k, v, of, lse), jnp.arange(1, n)
    )
    return of.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_ring_attention(
    q, k, v, axis_name: str, interpret: bool = False
):
    """Causal ring attention whose per-hop block math runs in the Pallas
    flash kernel (``ops.pallas_attention``) instead of XLA einsums —
    the long-context CP path at flash speed (README r2 admitted the ring
    couldn't use the kernel; this closes it).

    Same contract as ``ring_attention``: local shards (B, S/N, H, D)
    inside shard_map, kv already expanded to the query head count.
    The backward is the standard ring-flash scheme: per hop, the saved
    GLOBAL (out, lse) make ``exp(s - lse)`` the exact softmax slice for
    the visiting chunk, so the per-chunk Pallas backward kernels emit
    exact dq/dk/dv pieces; dk/dv ride the ring with their chunk and one
    final hop returns them to the owner.
    """
    out, _ = _flash_ring_fwd_impl(q, k, v, axis_name, interpret)
    return out


def _flash_ring_fwd(q, k, v, axis_name, interpret):
    out, lse = _flash_ring_fwd_impl(q, k, v, axis_name, interpret)
    return out, (q, k, v, out, lse)


def _flash_ring_bwd(axis_name, interpret, res, do):
    from distributeddataparallel_tpu.ops.pallas_attention import (
        _bwd as flash_bwd,
    )

    q, k, v, out, lse = res
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    lse8 = jnp.broadcast_to(
        lse.reshape(B * H, 1, S), (B * H, 8, S)
    )
    # Hop 0: own chunk, causal diagonal.
    dq, dk, dv = flash_bwd(
        True, interpret, None, None, (q, k, v, out, lse8), do
    )
    perm = [(i, (i + 1) % n) for i in range(n)]

    def hop(carry, s):
        kc, vc, dkc, dvc, dq = carry
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        dkc = lax.ppermute(dkc, axis_name, perm)
        dvc = lax.ppermute(dvc, axis_name, perm)
        dq_h, dk_h, dv_h = flash_bwd(
            False, interpret, None, None, (q, kc, vc, out, lse8), do
        )
        live = (idx - s >= 0).astype(dq.dtype)
        dq = dq + dq_h * live
        dkc = dkc + dk_h.astype(dkc.dtype) * live
        dvc = dvc + dv_h.astype(dvc.dtype) * live
        return (kc, vc, dkc, dvc, dq), None

    (_, _, dkc, dvc, dq), _ = lax.scan(
        hop, (k, v, dk, dv, dq), jnp.arange(1, n)
    )
    # Chunks sit one hop short of home after n-1 rotations; the final
    # rotation delivers each chunk's accumulated gradient to its owner.
    dk = lax.ppermute(dkc, axis_name, perm)
    dv = lax.ppermute(dvc, axis_name, perm)
    return dq, dk, dv


flash_ring_attention.defvjp(_flash_ring_fwd, _flash_ring_bwd)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = True,
    impl: str = "auto",
) -> jnp.ndarray:
    """Blockwise ring attention over the ``axis_name`` mesh axis.

    q, k, v: local shards (B, S_local, H, D); the global sequence is the
    concatenation of shards in axis order.  Returns the local (B, S_local,
    H, D) output shard — numerically identical (up to fp accumulation
    order) to slicing full attention over the gathered sequence.

    ``impl``: 'auto' uses the Pallas flash kernel per kv-hop
    (``flash_ring_attention``) whenever the local shapes support it (a
    kernel compile failure fails the jit), 'pallas' also rejects
    unsupported shapes, 'xla' keeps the einsum blocks below.  Only
    causal attention takes the kernel path (the ring's wrap masking
    assumes it).
    """
    if impl in ("auto", "pallas") and causal:
        from distributeddataparallel_tpu.ops.pallas_attention import supported

        if supported(q, k, v) and k.shape[2] == q.shape[2]:
            return flash_ring_attention(q, k, v, axis_name)
        if impl == "pallas":
            raise ValueError(
                f"pallas ring attention unsupported for shapes "
                f"q={q.shape} kv={k.shape} on {jax.default_backend()}"
            )
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    B, S, H, D = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q_off = idx * S

    qf = q.astype(jnp.float32)

    def accumulate(stats, kc, vc, src):
        """Online-softmax update of (m, l, acc) with the visiting chunk
        whose global ring position is `src`."""
        m, l, acc = stats
        logits = (
            jnp.einsum("bqhd,bkhd->bhqk", qf, kc.astype(jnp.float32)) * scale
        )
        if causal:
            logits = logits + causal_mask_bias(
                S, S, q_offset=q_off, kv_offset=src * S
            )[None, None]
        m_cur = jnp.max(logits, axis=-1)          # (B, H, S)
        m_new = jnp.maximum(m, m_cur)
        p = jnp.exp(logits - m_new[..., None])    # (B, H, S, S)
        corr = jnp.exp(m - m_new)
        l_new = corr * l + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vc.astype(jnp.float32)
        )
        return m_new, l_new, acc_new

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, s):
        kc, vc, stats = carry
        # Rotate first (s >= 1): n-1 hops total, no dead final rotation.
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        # After s hops this device holds the chunk from position idx - s.
        stats = accumulate(stats, kc, vc, (idx - s) % n)
        return (kc, vc, stats), None

    m0 = jnp.full((B, H, S), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    acc0 = jnp.zeros((B, H, S, D), jnp.float32)
    stats = accumulate((m0, l0, acc0), k, v, idx)  # own chunk, hop 0
    (_, _, (m, l, acc)), _ = lax.scan(
        step, (k, v, stats), jnp.arange(1, n)
    )
    # Rows with no visible kv (can't happen for causal self-attention, but
    # guard against l == 0 for safety).
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l_safe[..., None]).transpose(0, 2, 1, 3)  # (B, S, H, D)
    return out.astype(q.dtype)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = True,
    impl: str = "auto",
) -> jnp.ndarray:
    """All-to-all (DeepSpeed-Ulysses-style) sequence-parallel attention.

    The dual of ``ring_attention`` over the same sequence-sharded layout
    (local shards (B, S/N, H, D), global sequence = shards in axis
    order), trading N-1 ``ppermute`` hops for two ``all_to_all``s:

    1. all-to-all scatters the HEAD dim and gathers the SEQUENCE dim —
       each device now holds ALL tokens for H/N of the heads;
    2. ordinary full-sequence attention runs locally per head group —
       on TPU this is the framework's own Pallas flash kernel
       (``ops.attention.attention``), which the ring path cannot use
       because no device ever sees the whole sequence;
    3. the inverse all-to-all restores the sequence-sharded layout.

    Trade-offs vs the ring: communication is 2 all-to-alls of the
    activations regardless of N (the ring moves the whole KV cache N-1
    times, overlapped), but parallelism is capped at the head count
    (H % N == 0).  GQA: when the kv head count divides N too, kv travels
    at its own (smaller) head count and the local attention consumes it
    natively; otherwise kv heads are expanded before the exchange.

    Must run inside ``shard_map`` with ``axis_name`` bound.  RoPE /
    positional lookups happen BEFORE this op with global positions
    (``cp_positions``), exactly as for the ring path.
    """
    n = lax.psum(1, axis_name)
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if H % n:
        raise ValueError(
            f"ulysses requires num_heads % axis size == 0, got {H} % {n}"
        )
    if Hkv % n:
        # GQA with a kv head count the axis doesn't divide: replicate kv
        # heads to lcm(Hkv, n) — the smallest count the all_to_all can
        # split — not all the way to H.  rep always divides the GQA group
        # size (H % n == 0 forces it), so the local attention still sees
        # a valid grouped layout, and q-head j keeps mapping to its
        # original kv head j // (H/Hkv).
        import math

        from distributeddataparallel_tpu.ops.attention import repeat_kv

        rep = n // math.gcd(Hkv, n)
        assert H % (Hkv * rep) == 0, (H, Hkv, n)
        k = repeat_kv(k, rep)
        v = repeat_kv(v, rep)
    # Scatter heads / gather sequence: (B, S/N, H, D) -> (B, S, H/N, D).
    # Received shards concatenate in axis order, so the gathered sequence
    # is in global order and q-head block j pairs with kv-head block j
    # (head groups stay contiguous because H/N is a multiple of the GQA
    # group size whenever Hkv % N == 0).
    from distributeddataparallel_tpu.ops.attention import attention

    a2a = lambda x: lax.all_to_all(
        x, axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    out = attention(a2a(q), a2a(k), a2a(v), causal=causal, impl=impl)
    # Inverse: scatter sequence / gather heads -> (B, S/N, H, D).
    return lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def cp_positions(seq_len_local: int, axis_name: str) -> jnp.ndarray:
    """Global token positions of this device's sequence shard (for RoPE /
    learned positional lookups inside shard_map)."""
    return lax.axis_index(axis_name) * seq_len_local + jnp.arange(
        seq_len_local
    )


def make_cp_train_step(
    loss_fn: Callable,
    *,
    mesh: Mesh,
    data_axis: str = "data",
    seq_axis: str = "seq",
    donate: bool = True,
    **kwargs,
):
    """Compiled train step with DP × CP sharding.

    ``loss_fn(params, batch, rng) -> (loss, aux)`` runs per mesh position
    on a batch whose leaves are sharded (batch-dim → ``data_axis``,
    seq-dim → ``seq_axis``); inside it the model must use collective
    attention (``TransformerConfig.cp_axis = seq_axis``) so the sharded
    sequence attends globally.  The per-position mean loss is weighted
    uniformly per token, so gradients are pmean'd over BOTH axes —
    equivalent to global-batch DP on the full sequence.

    Batches come pre-split by the host into {"inputs", "targets"} (the
    next-token shift crosses shard boundaries, so it must happen before
    sharding — see ``data.loader.shard_lm_batch``).

    Thin wrapper over ``training.train_step.make_train_step(cp_axis=...)``
    — every DP feature (gradient accumulation, bucketing, ZeRO-1,
    grad_sync=False) composes with CP through ``kwargs``.
    """
    from distributeddataparallel_tpu.training.train_step import make_train_step

    return make_train_step(
        loss_fn, mesh=mesh, axis_name=data_axis, cp_axis=seq_axis,
        donate=donate, **kwargs,
    )


def make_cp_eval_step(
    metric_fn: Callable,
    *,
    mesh: Mesh,
    data_axis: str = "data",
    seq_axis: str = "seq",
    masked: bool = False,
    param_specs=None,
):
    """Jit'd DP×CP eval: ``metric_fn(params, batch) -> dict`` per position,
    pmean'd over both axes.

    ``masked=True``: exact evaluation over sampler-padded batches.  The
    batch is ``{"inputs", "targets", "valid"}`` (``shard_lm_batch`` with a
    ``valid`` row mask); metric_fn must return PER-ROW vectors over the
    local (rows, seq-chunk) shard.  Per-row values are first pmean'd over
    the seq axis (chunks are equal-length, so this is the exact global
    per-row mean), then masked-mean'd over the data axis so padded
    duplicate rows contribute nothing.  Returns ``(metrics, count)`` like
    ``make_eval_step(masked=True)``.
    """

    def _eval(params: Pytree, batch: Pytree):
        if masked:
            batch = dict(batch)
            mask = batch.pop("valid")
        metrics = metric_fn(params, batch)
        if masked:
            from distributeddataparallel_tpu.parallel.data_parallel import (
                masked_tree_mean,
            )

            return masked_tree_mean(
                metrics, mask, data_axis, seq_axis=seq_axis
            )
        return jax.tree.map(
            lambda m: lax.pmean(lax.pmean(m, data_axis), seq_axis), metrics
        )

    if masked:
        batch_specs: Any = {
            "inputs": P(data_axis, seq_axis),
            "targets": P(data_axis, seq_axis),
            "valid": P(data_axis),
        }
    else:
        batch_specs = P(data_axis, seq_axis)
    sharded = jax.shard_map(
        _eval,
        mesh=mesh,
        in_specs=(param_specs if param_specs is not None else P(),
                  batch_specs),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(sharded)
