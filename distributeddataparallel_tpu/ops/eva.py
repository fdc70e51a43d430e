"""EVA attention (Zheng et al., "Efficient Attention via Control
Variates", arXiv:2302.04542, as EvaByte runs it): exact causal attention
inside blocks of ``window`` positions, and beyond the block one learned
summary for every ``chunk`` keys of every EARLIER block, all under one
softmax.

Per head, with ``scale`` on every score:

- *summaries.*  Chunk ``j`` covers positions ``[chunk * j, chunk * (j +
  1))``.  ``a_jt = softmax_t(scale * <k_t, phi>)`` over the chunk's keys,
  in float32; ``ksum_j = sum_t a_jt k_t + mu``; ``vsum_j = sum_t a_jt v_t``.
- *scores of query i*, in block ``w = i // window``: exact,
  ``<q_i, k_t>`` for ``window * w <= t <= i`` (the blocks are a partition,
  not a sliding band); summarised, ``<q_i, ksum_j>`` for every ``j <
  (window // chunk) * w`` — none of its own block, which would leak later
  positions.  One softmax over both sets.

It is computed as two attentions and a merge.  The local part is plain
causal attention on blocks folded into the batch, ``(B * S / window,
window, H, D)``; the remote part runs on the queries from ``window`` on
(block 0 sees no summary, and a softmax over nothing is no softmax)
against the summaries under the staircase rule ``query i' sees summaries
[0, (i' // window + 1) * window // chunk)``.  Each gives ``(out, lse)``;
the merge weighs them by ``sigmoid(lse_remote - lse_local)``, which is
``e^lse_r / (e^lse_l + e^lse_r)`` without an overflow either way.

Both parts run through ``ops.pallas_attention``'s three kernels where the
shapes allow (the staircase is one more visibility rule of theirs, with
no mask: a (512, 128) tile is wholly seen or wholly not), else in
``jax.numpy``: the remote part then block by block, each block's queries
against exactly the summaries it sees.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributeddataparallel_tpu.observability import scopes
from distributeddataparallel_tpu.ops.attention import (
    attention,
    dot_product_attention,
)


def chunk_summaries(k, v, phi, mu, chunk: int, scale: float):
    """``(ksum, vsum)``, each (B, S / chunk, H, D) in ``k``'s dtype: every
    chunk's keys and values pooled by the softmax of ``scale * <k_t, phi>``
    over the chunk (float32), ``mu`` added to the pooled key.  ``phi`` and
    ``mu`` are (H, D), learned per head."""
    B, S, H, D = k.shape
    if S % chunk:
        raise ValueError(f"{S} positions are no whole number of chunks of {chunk}")
    kc = k.reshape(B, S // chunk, chunk, H, D).astype(jnp.float32)
    vc = v.reshape(B, S // chunk, chunk, H, D).astype(jnp.float32)
    logits = scale * jnp.sum(kc * phi.astype(jnp.float32), axis=-1)
    a = jax.nn.softmax(logits, axis=2)[..., None]          # (B, J, c, H, 1)
    ksum = jnp.sum(a * kc, axis=2) + mu.astype(jnp.float32)
    vsum = jnp.sum(a * vc, axis=2)
    return ksum.astype(k.dtype), vsum.astype(v.dtype)


def remote_blocks(seq: int, window: int) -> tuple[int, int]:
    """``(live, all)`` block-by-block pairs of the staircase at ``seq``
    positions: block ``w`` of queries sees the summaries of blocks ``0 ..
    w - 1`` (28 of 64 at 16,384 positions in blocks of 2,048)."""
    n = seq // window
    return n * (n - 1) // 2, n * n


def _remote_xla(q, ksum, vsum, stair, scale):
    """The remote part in ``jax.numpy``: one block of queries at a time
    against exactly the summaries it sees — no mask and no dead work."""
    q_step, k_step = stair
    outs, lses = [], []
    for w in range(q.shape[1] // q_step):
        out, lse = dot_product_attention(
            q[:, w * q_step:(w + 1) * q_step],
            ksum[:, :(w + 1) * k_step], vsum[:, :(w + 1) * k_step],
            causal=False, scale=scale, return_lse=True,
        )
        outs.append(out)
        lses.append(lse)
    return jnp.concatenate(outs, axis=1), jnp.concatenate(lses, axis=1)


def remote_attention(q, ksum, vsum, stair, *, scale: float, impl: str = "auto"):
    """``(out, lse)`` of queries (B, Sq, H, D) over summaries (B, J, H, D)
    under the staircase ``stair = (q_step, k_step)``: query i sees
    summaries ``[0, (i // q_step + 1) * k_step)``."""
    if impl in ("auto", "pallas"):
        from distributeddataparallel_tpu.ops import pallas_attention

        if pallas_attention.stair_supported(q, ksum, vsum, stair):
            return pallas_attention.flash_attention(
                q, ksum, vsum, False, False, scale, None,
                return_lse=True, stair=stair,
            )
        if impl == "pallas":
            raise ValueError(
                f"the flash kernels cannot run the staircase {stair} on "
                f"q={q.shape} k={ksum.shape} on {jax.default_backend()}"
            )
    return _remote_xla(q, ksum, vsum, stair, scale)


def local_attention(q, k, v, window: int, *, scale: float, impl: str = "auto"):
    """``(out, lse)`` of exact causal attention inside blocks of ``window``
    positions: the blocks folded into the batch, (B * S / window, window,
    H, D), so that each is one row of plain causal attention."""
    B, S, H, D = q.shape
    fold = lambda x: x.reshape(B * (S // window), window, H, D)  # noqa: E731
    out, lse = attention(
        fold(q), fold(k), fold(v), causal=True, impl=impl, scale=scale,
        return_lse=True,
    )
    return out.reshape(B, S, H, D), lse.reshape(B, S, H)


def eva_attention(q, k, v, phi, mu, *, window: int, chunk: int,
                  scale: float | None = None, impl: str = "auto"):
    """q, k, v (B, S, H, D), ``phi`` and ``mu`` (H, D) -> (B, S, H, D).
    With ``S <= window`` there is no summarised term: plain causal
    attention, and ``phi`` and ``mu`` take no part."""
    B, S, H, D = q.shape
    if window % chunk:
        raise ValueError(f"a window of {window} is no whole number of chunks of {chunk}")
    if scale is None:
        scale = D ** -0.5
    if S <= window:
        with jax.named_scope(scopes.EVA_LOCAL):
            return attention(q, k, v, causal=True, impl=impl, scale=scale)
    if S % window:
        raise ValueError(f"{S} positions are no whole number of windows of {window}")
    with jax.named_scope(scopes.EVA_LOCAL):
        out_l, lse_l = local_attention(
            q, k, v, window, scale=scale, impl=impl)
    with jax.named_scope(scopes.EVA_SUMMARIES):
        # the last window's chunks are seen by no query
        ksum, vsum = chunk_summaries(
            k[:, :S - window], v[:, :S - window], phi, mu, chunk, scale
        )
    with jax.named_scope(scopes.EVA_REMOTE):
        out_r, lse_r = remote_attention(
            q[:, window:], ksum, vsum, (window, window // chunk),
            scale=scale, impl=impl,
        )
    with jax.named_scope(scopes.EVA_MERGE):
        # e^lse_r / (e^lse_l + e^lse_r), from the difference alone
        share = jax.nn.sigmoid(lse_r - lse_l[:, window:])[..., None]
        near = out_l[:, window:].astype(jnp.float32)
        merged = near + share * (out_r.astype(jnp.float32) - near)
        return jnp.concatenate(
            [out_l[:, :window], merged.astype(out_l.dtype)], axis=1
        )
