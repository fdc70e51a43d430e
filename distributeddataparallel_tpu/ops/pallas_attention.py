"""Pallas TPU flash attention — blockwise causal attention kernel.

The reference reaches its attention-free compute through cuDNN kernels
(ref dpp.py:14 via torchvision); this is the framework's own TPU kernel for
the LM configs (BASELINE 4-5), written against the Pallas TPU guide
(/opt/skills/guides/pallas_guide.md):

- Grid (batch*heads, q_blocks, kv_blocks), kv innermost; q/k/v tiles are
  DMA'd HBM→VMEM by BlockSpec, matmuls hit the MXU with
  ``preferred_element_type=float32``.
- Online softmax: VMEM scratch carries the running max ``m``, normalizer
  ``l``, and f32 accumulator across kv blocks, so the (S, S) score matrix
  is never materialized — O(S) memory instead of O(S²).
- Causal blocks strictly above the diagonal are skipped with ``pl.when``
  (predicated off — no MXU work, no DMA dependency stalls).
- Backward: ``custom_vjp`` saving (q, k, v, out, lse); gradients use the
  standard flash-attention identities with the saved log-sum-exp,
  recomputing probability tiles BLOCKWISE in two Pallas kernels (the
  FlashAttention-2 split): a dq kernel (kv innermost, dq accumulates in
  VMEM scratch) and a dk/dv kernel (q innermost, dk/dv accumulate in
  scratch).  The (S, S) probability matrix is never materialized in
  either direction — backward peak memory is O(S) per device, which is
  what bounds long-context training.

CPU tests run the same kernel under ``interpret=True``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributeddataparallel_tpu.observability import scopes
from distributeddataparallel_tpu.ops.attention import NEG_INF


def _pick_block(s: int, preferred: tuple[int, ...] = (512, 256, 128)) -> int | None:
    for b in preferred:
        if s % b == 0 and s >= b:
            return b
    return None


def supported(q, k, v) -> bool:
    """True when the flash kernel can run natively on this backend/shapes.

    GQA is native: k/v may carry fewer heads than q (H % Hkv == 0) — the
    kernels index the shared kv head per query-head group through the
    BlockSpec maps, so the repeated kv tensor never materializes.
    """
    if jax.default_backend() != "tpu":
        return False
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    Hkv = k.shape[2]
    if H % Hkv or v.shape != k.shape:
        return False
    return (
        _pick_block(Sq) is not None
        and _pick_block(Skv) is not None
        # Sq > Skv has rows with NO visible keys under the causal
        # align-to-end convention (q_offset < 0): softmax over an empty
        # set is undefined and the kernels would emit uniform garbage for
        # those rows.  Conservatively unsupported (XLA fallback) even for
        # non-causal, where such shapes are rare.
        and Sq <= Skv
        and D % 8 == 0
        and D <= 256
    )


def _block_live(i, j, *, causal: bool, block_q: int, block_k: int, q_offset: int):
    """Causal block-skip predicate shared by forward and backward kernels:
    the (q block i, kv block j) tile is live unless it sits strictly above
    the diagonal.  q_offset aligns query rows to the END of the kv
    sequence (the Sq != Skv decode convention)."""
    q_last = q_offset + i * block_q + block_q - 1
    return (not causal) or (j * block_k <= q_last)


def _causal_mask_scores(s, i, j, *, block_q: int, block_k: int, q_offset: int):
    """Mask the (BQ, BK) score tile above the diagonal with NEG_INF —
    the single in-kernel statement of the position convention (one copy,
    so forward and backward can never drift)."""
    q_pos = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return jnp.where(k_pos <= q_pos, s, NEG_INF)


def _flash_kernel(
    q_ref, k_ref, v_ref,  # (1, BQ, D), (1, BK, D), (1, BK, D)
    o_ref,                # (1, BQ, D)
    lse_ref,              # (1, 8, BQ) — lse broadcast over 8 sublanes to
                          # satisfy the TPU (8, 128) block-tiling minimum
    m_ref, l_ref, acc_ref,  # VMEM scratch: (BQ, 128), (BQ, 128), (BQ, D)
    *, causal: bool, block_q: int, block_k: int, scale: float, q_offset: int,
):
    i = pl.program_id(1)  # q block index
    j = pl.program_id(2)  # kv block index
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    geom = dict(block_q=block_q, block_k=block_k, q_offset=q_offset)

    @pl.when(_block_live(i, j, causal=causal, **geom))
    def _body():
        q = q_ref[0]  # (BQ, D)
        k = k_ref[0]  # (BK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (BQ, BK)
        if causal:
            s = _causal_mask_scores(s, i, j, **geom)

        m_prev = m_ref[:, 0]                      # (BQ,)
        m_cur = jnp.max(s, axis=1)                # (BQ,)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])           # (BQ, BK)
        correction = jnp.exp(m_prev - m_new)      # (BQ,)
        l_new = correction * l_ref[:, 0] + jnp.sum(p, axis=1)
        acc_ref[:] = acc_ref[:] * correction[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == nj - 1)
    def _finish():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / l_safe[:, None]).astype(o_ref.dtype)
        lse = m_ref[:, 0] + jnp.log(l_safe)  # (BQ,)
        lse_ref[0] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _gqa_kv_row(b, *, H: int, Hkv: int):
    """Flat kv row for flat q row ``b``: query head h of batch n reads kv
    head h // (H // Hkv) — the GQA group mapping, done in the BlockSpec
    index map so the repeated kv never materializes."""
    group = H // Hkv
    return (b // H) * Hkv + (b % H) // group


def _flash_fwd_impl(q, k, v, *, causal: bool, interpret: bool):
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(f"num_heads {H} not a multiple of kv heads {Hkv}")
    block_q = _pick_block(Sq)
    block_k = _pick_block(Skv)
    if block_q is None or block_k is None:
        raise ValueError(f"seq lens ({Sq}, {Skv}) not divisible by 128")
    if causal and Sq > Skv:
        raise ValueError(
            f"causal flash attention requires Sq <= Skv (queries align to "
            f"the END of the kv sequence); got Sq={Sq} > Skv={Skv}, which "
            f"leaves rows with no visible keys"
        )
    scale = 1.0 / (D ** 0.5)

    # (B, S, H, D) -> (B*H, S, D): one grid row per (batch, head); kv
    # stays at its own (smaller) head count.
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    kv_row = functools.partial(_gqa_kv_row, H=H, Hkv=Hkv)

    grid = (B * H, Sq // block_q, Skv // block_k)
    kernel = functools.partial(
        _flash_kernel,
        causal=causal, block_q=block_q, block_k=block_k, scale=scale,
        q_offset=Skv - Sq,
    )
    from jax.experimental.pallas import tpu as pltpu

    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (kv_row(b), j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (kv_row(b), j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 8, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
        name=scopes.FLASH_FWD,
    )(qf, kf, vf)
    out = out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    # lse stays in its (B*H, 8, Sq) sublane-broadcast layout: the backward
    # kernels consume exactly this shape, so saving it unsliced avoids a
    # slice here and a re-broadcast (extra HBM copy) per backward pass.
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal: bool = True, interpret: bool = False):
    """Flash attention: q,k,v (B,S,H,D) -> (B,S,H,D), causal by default."""
    out, _ = _flash_fwd_impl(q, k, v, causal=causal, interpret=interpret)
    return out


def _fwd(q, k, v, causal, interpret):
    out, lse = _flash_fwd_impl(q, k, v, causal=causal, interpret=interpret)
    return out, (q, k, v, out, lse)


def _recompute_p_ds(
    q, k, v, do, lse, delta, *,
    i, j, causal, block_q, block_k, scale, q_offset,
):
    """Shared blockwise backward math for one (q block i, kv block j) tile.

    Recomputes the probability tile from the saved log-sum-exp and applies
    the flash-attention identities:

        p  = exp(s - lse)               (exact softmax row, no renorm pass)
        dp = do vᵀ
        ds = p * (dp - delta)           delta = rowsum(do * out), saved
    """
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (BQ, BK)
    if causal:
        s = _causal_mask_scores(
            s, i, j, block_q=block_q, block_k=block_k, q_offset=q_offset
        )
    p = jnp.exp(s - lse[:, None])  # masked entries: exp(NEG_INF - lse) = 0
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (BQ, BK)
    ds = p * (dp - delta[:, None])
    return p, ds


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,  # inputs
    dq_ref,                                           # (1, BQ, D)
    dq_acc,                                           # VMEM (BQ, D) f32
    *, causal: bool, block_q: int, block_k: int, scale: float, q_offset: int,
):
    i = pl.program_id(1)  # q block (outer)
    j = pl.program_id(2)  # kv block (inner: dq accumulates over it)
    nj = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_block_live(i, j, causal=causal, block_q=block_q,
                         block_k=block_k, q_offset=q_offset))
    def _body():
        _, ds = _recompute_p_ds(
            q_ref[0], k_ref[0], v_ref[0], do_ref[0],
            lse_ref[0, 0], delta_ref[0, 0],
            i=i, j=j, causal=causal, block_q=block_q, block_k=block_k,
            scale=scale, q_offset=q_offset,
        )
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(j == nj - 1)
    def _finish():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,  # inputs
    dk_ref, dv_ref,                                   # (1, BK, D) each
    dk_acc, dv_acc,                                   # VMEM (BK, D) f32
    *, causal: bool, block_q: int, block_k: int, scale: float,
    q_offset: int, group: int,
):
    """Grid (B*Hkv, kv blocks, q blocks * group): the inner index walks
    every (q block, group-member q head) pair feeding this KV HEAD's
    block, so GQA's shared kv gradients accumulate in one scratch pass —
    no repeated-kv tensor, no cross-iteration output hazard."""
    j = pl.program_id(1)   # kv block (outer)
    t = pl.program_id(2)   # inner: q block index * group + group member
    nt = pl.num_programs(2)
    i = t // group         # q block (the causal predicate needs it)

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(i, j, causal=causal, block_q=block_q,
                         block_k=block_k, q_offset=q_offset))
    def _body():
        q = q_ref[0]
        do = do_ref[0]
        p, ds = _recompute_p_ds(
            q, k_ref[0], v_ref[0], do,
            lse_ref[0, 0], delta_ref[0, 0],
            i=i, j=j, causal=causal, block_q=block_q, block_k=block_k,
            scale=scale, q_offset=q_offset,
        )
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(t == nt - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd(causal, interpret, res, do):
    """Blockwise flash backward: two Pallas kernels, O(S) peak memory.

    Probability tiles are recomputed per (q block, kv block) pair from the
    saved lse — the (S, S) matrix never exists.  dq runs with kv blocks
    innermost (accumulating dq_i in VMEM); dk/dv run with q blocks
    innermost (accumulating dk_j/dv_j).  ``delta = rowsum(do * out)`` is a
    cheap O(S·D) XLA reduction done once up front.
    """
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    Hkv = k.shape[2]
    group = H // Hkv
    block_q = _pick_block(Sq)
    block_k = _pick_block(Skv)
    scale = 1.0 / (D ** 0.5)
    q_offset = Skv - Sq

    # (B, S, H, D) -> (B*H, S, D) flat layout, matching the forward; kv
    # stays at its own head count (GQA shares it across the group).
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    dof = do.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    outf = out.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kv_row = functools.partial(_gqa_kv_row, H=H, Hkv=Hkv)

    delta = jnp.sum(
        dof.astype(jnp.float32) * outf.astype(jnp.float32), axis=-1
    )  # (B*H, Sq)
    # Row vectors enter the kernels broadcast over 8 sublanes (the TPU
    # (8, 128) tiling minimum).  lse arrives from the forward already in
    # that layout; only delta needs the broadcast.
    lse8 = lse
    delta8 = jnp.broadcast_to(delta[:, None, :], (B * H, 8, Sq))

    from jax.experimental.pallas import tpu as pltpu

    row_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, x, y: (b, x, 0)),   # q
        pl.BlockSpec((1, block_k, D), lambda b, x, y: (kv_row(b), y, 0)),  # k
        pl.BlockSpec((1, block_k, D), lambda b, x, y: (kv_row(b), y, 0)),  # v
        pl.BlockSpec((1, block_q, D), lambda b, x, y: (b, x, 0)),   # do
        pl.BlockSpec((1, 8, block_q), lambda b, x, y: (b, 0, x)),   # lse
        pl.BlockSpec((1, 8, block_q), lambda b, x, y: (b, 0, x)),   # delta
    ]
    kw = dict(
        causal=causal, block_q=block_q, block_k=block_k, scale=scale,
        q_offset=q_offset,
    )

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **kw),
        grid=(B * H, Sq // block_q, Skv // block_k),
        in_specs=row_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, x, y: (b, x, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name=scopes.FLASH_BWD_DQ,
    )(qf, kf, vf, dof, lse8, delta8)

    # dkv grid: one row per KV head; the inner index t walks every
    # (q block, group member) pair so the group's q heads accumulate into
    # the shared kv gradient consecutively (no output-revisit hazard).
    def q_row(b, t):
        return (b // Hkv) * H + (b % Hkv) * group + t % group

    def q_blk(t):
        return t // group

    kv_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, y, t: (q_row(b, t), q_blk(t), 0)),  # q
        pl.BlockSpec((1, block_k, D), lambda b, y, t: (b, y, 0)),   # k
        pl.BlockSpec((1, block_k, D), lambda b, y, t: (b, y, 0)),   # v
        pl.BlockSpec((1, block_q, D), lambda b, y, t: (q_row(b, t), q_blk(t), 0)),  # do
        pl.BlockSpec((1, 8, block_q), lambda b, y, t: (q_row(b, t), 0, q_blk(t))),  # lse
        pl.BlockSpec((1, 8, block_q), lambda b, y, t: (q_row(b, t), 0, q_blk(t))),  # delta
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, group=group, **kw),
        grid=(B * Hkv, Skv // block_k, (Sq // block_q) * group),
        in_specs=kv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, y, t: (b, y, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, y, t: (b, y, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, Skv, D), k.dtype),
            jax.ShapeDtypeStruct((B * Hkv, Skv, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name=scopes.FLASH_BWD_DKV,
    )(qf, kf, vf, dof, lse8, delta8)

    dq = dq.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    dk = dk.reshape(B, Hkv, Skv, D).transpose(0, 2, 1, 3)
    dv = dv.reshape(B, Hkv, Skv, D).transpose(0, 2, 1, 3)
    return dq, dk, dv


flash_attention.defvjp(_fwd, _bwd)
