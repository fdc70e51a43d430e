"""Pallas TPU flash attention — blockwise causal attention kernel.

The reference reaches its attention-free compute through cuDNN kernels
(ref dpp.py:14 via torchvision); this is the framework's own TPU kernel for
the LM configs (BASELINE 4-5), written against the Pallas TPU guide
(/opt/skills/guides/pallas_guide.md):

- Forward: grid (batch*heads, q_blocks, kv_fetches).  K/V of a
  (batch, head) row are fetched as ONE block where they fit
  (``_fwd_plan``: all of Skv at the training shapes, so kv_fetches = 1
  and no grid step is dead) and walked in score tiles by in-kernel loops
  whose trip counts stop at the diagonal (``_kv_span``): the tiles below
  it run without a mask, the tiles it crosses with one, the tiles above
  it are never entered.  Where K/V need several fetches, a step above
  the diagonal names the block already in VMEM, so it issues no DMA.
  ``fwd_tile_counts`` says how often each path engages, from shapes.
- Matmuls hit the MXU with ``preferred_element_type=float32``; q/k/v
  blocks are DMA'd HBM→VMEM by BlockSpec.
- Online softmax: VMEM scratch carries the running max ``m``, normalizer
  ``l``, and f32 accumulator across kv tiles, so the (S, S) score matrix
  is never materialized — O(S) memory instead of O(S²).  In the forward
  the row statistics stay (rows, 128) lane-replicated from the reduction
  to the store (a 1-D row vector costs a relayout a tile).
- Backward kernels (``_bwd_plan``): where a (batch, head) row's operands
  fit the forward's fetch budget (all of S at the training shapes) one
  grid step owns the row and walks its live tiles alone, unrolled where
  they are few, so that no step is dead and the compiler may overlap one
  tile's vector work with the next one's products; else the grid walks a
  row's or a column's live tiles and a step past the last one names the
  block already in VMEM.  The mask runs only on the tiles the diagonal or
  the window's edge crosses, a power-of-two scale is folded into an
  operand, and the dk/dv kernel computes its tiles transposed, (BK, BQ):
  lse and delta are then rows, the (8, Sq) layout they arrive in, and
  both accumulations are plain products.  ``bwd_tile_counts`` says how
  often each path engages.
- Backward: ``custom_vjp`` saving (q, k, v, out, lse); gradients use the
  standard flash-attention identities with the saved log-sum-exp,
  recomputing probability tiles BLOCKWISE in two Pallas kernels (the
  FlashAttention-2 split): a dq kernel (kv innermost, dq accumulates in
  VMEM scratch) and a dk/dv kernel (q innermost, dk/dv accumulate in
  scratch).  The (S, S) probability matrix is never materialized in
  either direction — backward peak memory is O(S) per device, which is
  what bounds long-context training.

- A static ``window`` (None: none) bounds the visible keys on the left
  too: key j is visible to query i iff ``j <= i`` and ``i - j < window``
  (``_kv_window`` and the four functions that state the convention).  The
  forward's grid then has only as many K/V fetches as a q block's window
  can touch, starting at the window's first tile; the backward grids walk
  the live tiles of a row or column alone.  Tiles left of the window are
  never entered and their K/V never fetched.

- A static ``stair = (q_step, k_step)`` (None: none; not causal) is a
  third rule of visibility: query i sees keys ``[0, (i // q_step + 1) *
  k_step)`` — every summary of every earlier window, for ``ops/eva.py``.
  With tiles that divide both steps a tile is wholly visible or wholly
  not, so the rule is a span of kv tiles per q block (``_kv_span``) and a
  first q block per kv block (``_q_walk``) and no mask at all.
- ``flash_attention(..., return_lse=True)`` hands the row statistic out
  too, (B, S, H) float32, and takes its cotangent: the backward's
  ``delta`` becomes ``rowsum(do * out) - d lse`` and nothing else changes.

CPU tests run the same kernel under ``interpret=True``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributeddataparallel_tpu.observability import scopes
from distributeddataparallel_tpu.ops.attention import NEG_INF

_LANES = 128


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


def _stair_blocks(Sq: int, Skv: int, stair: tuple[int, int] | None):
    """Score-tile sides ``(block_q, block_k)``: ``_pick_block`` squares,
    and under a staircase the largest that also divide its two steps, so
    that a tile is wholly seen or wholly not."""
    if stair is None:
        return _pick_block(Sq), _pick_block(Skv)
    return (_pick_block(math.gcd(Sq, stair[0])),
            _pick_block(math.gcd(Skv, stair[1])))


def stair_supported(q, k, v, stair) -> bool:
    """``supported`` for the staircase rule: no causal alignment, so Sq
    may pass Skv (every row sees at least ``k_step`` keys); the tiles
    must divide both steps, so that no tile needs a mask."""
    if jax.default_backend() != "tpu" or v.shape != k.shape:
        return False
    B, Sq, H, D = q.shape
    block_q, block_k = _stair_blocks(Sq, k.shape[1], stair)
    return (
        H == k.shape[2]
        and block_q is not None and block_k is not None
        and -(-Sq // stair[0]) * stair[1] <= k.shape[1]
        and D % 8 == 0 and D <= 256
    )


def _block_live(i, j, *, causal: bool, block_q: int, block_k: int,
                q_offset: int, window: int | None = None):
    """Causal block-skip predicate shared by forward and backward kernels:
    the (q block i, kv block j) tile is live unless it sits strictly above
    the diagonal or, under a ``window``, wholly left of what its first
    query sees.  q_offset aligns query rows to the END of the kv
    sequence (the Sq != Skv decode convention)."""
    if not causal:
        return True
    q_last = q_offset + i * block_q + block_q - 1
    live = j * block_k <= q_last
    if window is not None:
        q_first = q_offset + i * block_q
        live = live & (j * block_k + block_k - 1 + window > q_first)
    return live


def _block_unmasked(i, j, *, causal: bool, block_q: int, block_k: int,
                    q_offset: int, window: int | None = None):
    """The (q block i, kv block j) tile lies wholly at or below the
    diagonal — its last key is visible to its first query — and, under a
    ``window``, wholly inside it — its first key is visible to its last
    query — so it needs no mask.  Non-causal tiles never do."""
    if not causal:
        return True
    q_first = q_offset + i * block_q
    free = j * block_k + block_k - 1 <= q_first
    if window is not None:
        free = free & (j * block_k + window > q_first + block_q - 1)
    return free


def _kv_span(i, *, causal: bool, block_q: int, block_k: int, q_offset: int,
             n_k: int, stair: tuple[int, int] | None = None):
    """``(full, live)`` for q block ``i``: kv tiles ``[0, full)`` are
    ``_block_unmasked``, ``[full, live)`` are live and crossed by the
    diagonal, ``[live, n_k)`` are dead — the two predicates counted in
    closed form (both are monotone in j), so a loop can stop where a grid
    would test.  ``i`` may be a Python int or a traced scalar.

    Under a ``stair`` (q_step, k_step) — the one statement of that rule
    for the forward and the dq kernel — the block's queries all lie on
    step ``i * block_q // q_step`` and see ``k_step`` keys more a step:
    ``full == live``, no tile is crossed."""
    if stair is not None:
        q_step, k_step = stair
        seen = (i * block_q // q_step + 1) * (k_step // block_k)
        return seen, seen
    if not causal:
        return n_k, n_k
    q_first = q_offset + i * block_q
    return (q_first + 1) // block_k, (q_first + block_q - 1) // block_k + 1


def _kv_window(i, *, block_q: int, block_k: int, q_offset: int,
               window: int | None):
    """``(start, lo)`` for q block ``i`` under a ``window``: kv tiles
    ``[0, start)`` lie wholly left of the window (dead), ``[start, lo)``
    are crossed by its edge, and from ``lo`` on a tile is ``_block_unmasked``
    as far as ``_kv_span``'s ``full`` reaches (``lo`` may lie past ``full``:
    then edge and diagonal cross the same tiles and none is unmasked).
    ``(0, 0)`` without a window.  Counted in closed form like ``_kv_span``;
    ``i`` may be a Python int or a traced scalar."""
    if window is None:
        return 0, 0
    # floor divisions of what may be negative, clipped at tile 0: the
    # clip is taken first, so that no negative number is divided
    top = max if isinstance(i, int) else jnp.maximum
    q_first = q_offset + i * block_q
    return (top(q_first - window + 1, 0) // block_k,
            top(q_first + block_q - 1 - window + block_k, 0) // block_k)


def _q_span(j, *, block_q: int, block_k: int, q_offset: int,
            window: int | None, n_q: int):
    """``(first, last)`` q blocks (inclusive) whose tile with kv block
    ``j`` is ``_block_live`` — ``_kv_span`` and ``_kv_window`` read by
    column, for the dk/dv kernel's walk.  Causal only."""
    top, least = (max, min) if isinstance(j, int) else (jnp.maximum, jnp.minimum)
    first = top(j * block_k - q_offset, 0) // block_q
    if window is None:
        return first, n_q - 1
    hi = (j + 1) * block_k + window - 2 - q_offset
    return first, least(top(hi, 0) // block_q, n_q - 1)


class FwdPlan(NamedTuple):
    """The forward kernel's tiles, from shapes alone (``_fwd_plan``)."""

    block_q: int   # q rows a grid step owns
    block_k: int   # kv rows of one score tile, the inner loop's stride
    block_kv: int  # kv rows fetched a grid step (a multiple of block_k)


class TileCounts(NamedTuple):
    """Per (batch*head) row: score tiles run without a mask, with one,
    never entered; and the grid steps that row's q blocks launch."""

    unmasked: int
    masked: int
    skipped: int
    steps: int


#: K or V rows fetched a grid step stay under this (each is double
#: buffered, so K and V together hold four times it in VMEM)
_KV_FETCH_BYTES = 1 << 20


def _fwd_plan(Sq: int, Skv: int, D: int, itemsize: int,
              window: int | None = None,
              stair: tuple[int, int] | None = None) -> FwdPlan:
    """The forward's tile plan, chosen from what the operands show.

    Score tiles are ``_pick_block`` squares as in the backward kernels.
    K/V are fetched as one block for the whole q block where that fits
    ``_KV_FETCH_BYTES`` (all of Skv at the training shapes: no dead grid
    steps), else in the largest multiple of the tile that divides Skv.
    Under a ``window`` a fetch is at most half of it, so that what a q
    block fetches stays near what it can see.
    """
    block_q, block_k = _stair_blocks(Sq, Skv, stair)
    n_k = Skv // block_k
    fit = max(1, _KV_FETCH_BYTES // (block_k * D * itemsize))
    if window is not None:
        fit = min(fit, max(1, window // (2 * block_k)))
    per_fetch = max(r for r in range(1, n_k + 1) if n_k % r == 0 and r <= fit)
    return FwdPlan(block_q, block_k, per_fetch * block_k)


def _fetch_steps(Sq: int, Skv: int, q_offset: int, plan: FwdPlan,
                 window: int | None) -> int:
    """K/V fetches a q block's grid row makes: all of them without a
    window, else as many as the widest q block's live tiles touch."""
    per_fetch = plan.block_kv // plan.block_k
    if window is None:
        return Skv // plan.block_kv
    geom = dict(block_q=plan.block_q, block_k=plan.block_k, q_offset=q_offset)
    return max(
        (_kv_span(i, causal=True, n_k=0, **geom)[1] - 1) // per_fetch
        - _kv_window(i, window=window, **geom)[0] // per_fetch + 1
        for i in range(Sq // plan.block_q)
    )


def fwd_tile_counts(Sq: int, Skv: int, causal: bool, q_offset: int,
                    plan: FwdPlan, window: int | None = None,
                    stair: tuple[int, int] | None = None) -> TileCounts:
    """How often each path of the forward kernel engages — static, like
    the mechanism: ``_kv_span`` and ``_kv_window`` summed over the q
    blocks."""
    n_q, n_k = Sq // plan.block_q, Skv // plan.block_k
    geom = dict(block_q=plan.block_q, block_k=plan.block_k, q_offset=q_offset)
    unmasked = live = 0
    for i in range(n_q):
        full, end = _kv_span(i, causal=causal, n_k=n_k, stair=stair, **geom)
        start, lo = _kv_window(i, window=window, **geom)
        unmasked += max(full - lo, 0)
        live += end - start
    return TileCounts(
        unmasked, live - unmasked, n_q * n_k - live,
        n_q * _fetch_steps(Sq, Skv, q_offset, plan, window),
    )


def _causal_mask_scores(s, i, j, *, block_q: int, block_k: int, q_offset: int,
                        window: int | None = None, transposed: bool = False):
    """Mask the (BQ, BK) score tile — (BK, BQ) if ``transposed`` — above
    the diagonal, and left of the ``window``, with NEG_INF — the single
    in-kernel statement of the position convention (one copy, so forward
    and backward can never drift)."""
    q_pos = q_offset + i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, int(transposed)
    )
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1 - int(transposed)
    )
    seen = k_pos <= q_pos
    if window is not None:
        seen = seen & (q_pos - k_pos < window)
    return jnp.where(seen, s, NEG_INF)


def _lanes(x, n: int):
    """A lane-replicated (rows, 128) statistic at width ``n``: a lane slice
    down, a lane repeat up — never through a 1-D row vector."""
    if n <= _LANES:
        return x if n == _LANES else x[:, :n]
    if n % _LANES == 0:
        return jnp.concatenate([x] * (n // _LANES), axis=1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _flash_kernel(
    q_ref, k_ref, v_ref,  # (1, BQ, D), (1, BKV, D), (1, BKV, D)
    o_ref,                # (1, BQ, D)
    lse_ref,              # (1, 8, BQ) — lse broadcast over 8 sublanes to
                          # satisfy the TPU (8, 128) block-tiling minimum
    m_ref, l_ref, acc_ref,  # VMEM scratch: (BQ, 128), (BQ, 128), (BQ, D)
    *, causal: bool, block_k: int, scale: float, q_offset: int,
    window: int | None = None, stair: tuple[int, int] | None = None,
):
    """One grid step owns one q block of one (batch*head) row and one
    fetched K/V block of ``BKV`` rows, and walks that block in score tiles
    of ``block_k`` rows: first the tiles below the diagonal (no mask),
    then the tiles the diagonal crosses; tiles above it are never entered.
    Under a ``window`` the row's first step fetches the block that holds
    the window's first tile, and the walk starts there: the tiles its edge
    crosses (mask), the tiles between edge and diagonal (none), the
    diagonal's (mask).

    The row statistics m, l, the correction and lse are (BQ, 128)
    lane-replicated from the reduction (``keepdims``) to the store.  The
    scratch has no initial state: the first live kv tile (tile 0 without a
    window), which every q block sees, writes it (``opening``) where the
    others update it.
    """
    _, block_q, D = q_ref.shape
    per_fetch = k_ref.shape[1] // block_k
    i = pl.program_id(1)   # q block index
    jf = pl.program_id(2)  # fetched K/V block index
    nf = pl.num_programs(2)
    geom = dict(block_q=block_q, block_k=block_k, q_offset=q_offset)
    full, live = _kv_span(i, causal=causal, n_k=per_fetch * nf, stair=stair,
                          **geom)
    first = jf * per_fetch  # the kv tile this fetch starts at
    if window is not None:
        start, lo = _kv_window(i, window=window, **geom)
        first = (start // per_fetch + jf) * per_fetch
        geom["window"] = window

    q = q_ref[0]  # (BQ, D)
    # scale moves onto q where that is bit-exact (a power of two, 2^-3 at
    # D = 64): one (BQ, D) multiply a step, not one (BQ, BK) a tile
    fold = math.frexp(scale)[0] == 0.5
    if fold:
        q = (q.astype(jnp.float32) * scale).astype(q.dtype)

    def tile(j, *, masked: bool, opening: bool = False):
        rows = pl.ds(pl.multiple_of((j - first) * block_k, block_k), block_k)
        s = jax.lax.dot_general(
            q, k_ref[0, rows, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, BK)
        if not fold:
            s = s * scale
        if masked:
            s = _causal_mask_scores(s, i, j, **geom)
        m_cur = jnp.max(s, axis=1, keepdims=True)           # (BQ, 1)
        if opening:
            m_new = jnp.broadcast_to(m_cur, m_ref.shape)    # (BQ, 128)
        else:
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - _lanes(m_new, block_k))             # (BQ, BK)
        l_cur = jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, rows, :],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BQ, D)
        if opening:
            l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)
            acc_ref[...] = pv
        else:
            correction = jnp.exp(m_prev - m_new)            # (BQ, 128)
            l_ref[...] = correction * l_ref[...] + l_cur
            acc_ref[...] = acc_ref[...] * _lanes(correction, D) + pv
        m_ref[...] = m_new

    def run(lo, hi, *, masked: bool):
        """kv tiles [lo, hi) that lie in this step's fetched block."""
        lo = jnp.maximum(lo, first)
        hi = jnp.minimum(hi, first + per_fetch)
        jax.lax.fori_loop(lo, hi, lambda j, _: tile(j, masked=masked), None)

    @pl.when(jf == 0)
    def _open():
        if window is not None:
            jax.lax.cond(
                (lo <= start) & (start < full),
                lambda: tile(start, masked=False, opening=True),
                lambda: tile(start, masked=True, opening=True),
            )
        elif causal:
            jax.lax.cond(
                full > 0,
                lambda: tile(0, masked=False, opening=True),
                lambda: tile(0, masked=True, opening=True),
            )
        else:
            tile(0, masked=False, opening=True)

    if window is not None:
        run(start + 1, jnp.minimum(lo, full), masked=True)
        run(jnp.maximum(lo, start + 1), full, masked=False)
        run(jnp.maximum(full, start + 1), live, masked=True)
    else:
        run(1, full, masked=False)
        if causal:
            run(jnp.maximum(full, 1), live, masked=True)

    @pl.when(jf == nf - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / _lanes(l_safe, D)).astype(o_ref.dtype)
        lse = m_ref[...] + jnp.log(l_safe)  # (BQ, 128)
        lse_ref[0] = lse.T[:8]              # lanes are equal: (8, BQ)


def _gqa_kv_row(b, *, H: int, Hkv: int):
    """Flat kv row for flat q row ``b``: query head h of batch n reads kv
    head h // (H // Hkv) — the GQA group mapping, done in the BlockSpec
    index map so the repeated kv never materializes."""
    group = H // Hkv
    return (b // H) * Hkv + (b % H) // group


def _flash_fwd_impl(q, k, v, *, causal: bool, interpret: bool,
                    scale: float | None = None, window: int | None = None,
                    stair: tuple[int, int] | None = None):
    if window is not None and not causal:
        raise ValueError("a window bounds causal attention only")
    if stair is not None and (causal or window is not None):
        raise ValueError("a staircase is a rule of its own: not causal, no window")
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    Hkv = k.shape[2]
    if H % Hkv:
        raise ValueError(f"num_heads {H} not a multiple of kv heads {Hkv}")
    if _pick_block(Sq) is None or _pick_block(Skv) is None:
        raise ValueError(f"seq lens ({Sq}, {Skv}) not divisible by 128")
    if causal and Sq > Skv:
        raise ValueError(
            f"causal flash attention requires Sq <= Skv (queries align to "
            f"the END of the kv sequence); got Sq={Sq} > Skv={Skv}, which "
            f"leaves rows with no visible keys"
        )
    # (B, S, H, D) -> (B*H, S, D): one grid row per (batch, head); kv
    # stays at its own (smaller) head count.
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    out, lse = _fwd_launch(
        qf, kf, vf, H=H, Hkv=Hkv, causal=causal, interpret=interpret,
        scale=scale, window=window, stair=stair,
    )
    out = out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    # lse stays in its (B*H, 8, Sq) sublane-broadcast layout: the backward
    # kernels consume exactly this shape, so saving it unsliced avoids a
    # slice here and a re-broadcast (extra HBM copy) per backward pass.
    return out, lse


@functools.partial(
    jax.jit,
    static_argnames=("H", "Hkv", "causal", "interpret", "scale", "window",
                     "stair"),
)
def _fwd_launch(qf, kf, vf, *, H: int, Hkv: int, causal: bool, interpret: bool,
                scale: float | None = None, window: int | None = None,
                stair: tuple[int, int] | None = None):
    """The forward's one ``pallas_call``, on flat (rows, S, D) operands.

    Jitted on its own so that a model's layers share one trace and one
    lowering of the kernel (every layer calls it with the same shapes;
    without this each call traces and lowers the kernel again, which is
    seconds of a 24-layer step's set-up).  Only the kernel is inside: the
    transposes round it stay in the caller's scope.
    """
    rows, Sq, D = qf.shape
    Skv = kf.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    q_offset = Skv - Sq
    plan = _fwd_plan(Sq, Skv, D, qf.dtype.itemsize, window, stair)
    counts = fwd_tile_counts(Sq, Skv, causal, q_offset, plan, window, stair)
    block_q, block_k, block_kv = plan
    per_fetch = block_kv // block_k
    kv_row = functools.partial(_gqa_kv_row, H=H, Hkv=Hkv)

    def kv_index(b, i, jf):
        if window is not None:
            # the row's steps name the blocks from the window's first tile
            # to the diagonal's; a step past it names that last one again
            geom = dict(block_q=block_q, block_k=block_k, q_offset=q_offset)
            start, _ = _kv_window(i, window=window, **geom)
            _, live = _kv_span(i, causal=True, n_k=Skv // block_k, **geom)
            jf = jnp.minimum(start // per_fetch + jf, (live - 1) // per_fetch)
        elif (causal or stair is not None) and Skv > block_kv:
            # a step above the diagonal (past the staircase's span) names
            # the block already in VMEM: no DMA is issued for K/V it will
            # not read
            _, live = _kv_span(
                i, causal=causal, block_q=block_q, block_k=block_k,
                q_offset=q_offset, n_k=Skv // block_k, stair=stair,
            )
            jf = jnp.minimum(jf, (live - 1) // per_fetch)
        return (kv_row(b), jf, 0)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal, block_k=block_k, scale=scale, q_offset=q_offset,
        **({} if window is None else {"window": window}),
        **({} if stair is None else {"stair": stair}),
    )
    from jax.experimental.pallas import tpu as pltpu

    tiles = rows * (counts.unmasked + counts.masked)
    return pl.pallas_call(
        kernel,
        grid=(rows, Sq // block_q,
              _fetch_steps(Sq, Skv, q_offset, plan, window)),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, jf: (b, i, 0)),
            pl.BlockSpec((1, block_kv, D), kv_index),
            pl.BlockSpec((1, block_kv, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, jf: (b, i, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, i, jf: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, Sq, D), qf.dtype),
            jax.ShapeDtypeStruct((rows, 8, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=tiles * 4 * block_q * block_k * D,
            transcendentals=tiles * block_q * block_k,
            bytes_accessed=(2 * qf.size + kf.size + vf.size) * qf.dtype.itemsize
            + rows * 8 * Sq * 4,
        ),
        interpret=interpret,
        name=scopes.FLASH_FWD,
    )(qf, kf, vf)


def flash_attention(q, k, v, causal: bool = True, interpret: bool = False,
                    scale: float | None = None, window: int | None = None,
                    *, return_lse: bool = False,
                    stair: tuple[int, int] | None = None):
    """Flash attention: q,k,v (B,S,H,D) -> (B,S,H,D), causal by default;
    ``scale`` multiplies the scores (None: 1/sqrt(D)); under a ``window``
    a query sees its own key and the ``window - 1`` before it; under a
    ``stair`` (q_step, k_step), with ``causal`` False, query i sees keys
    ``[0, (i // q_step + 1) * k_step)``.  With ``return_lse`` the result
    is ``(out, lse)``, ``lse`` (B, S, H) float32 the log of each row's
    summed exponentials, differentiable like ``out`` — what a caller
    needs to merge this softmax with another over other keys.  Without
    it (and without a stair) the traced program is what it always was."""
    if return_lse or stair is not None:
        out, lse = _flash_lse(q, k, v, causal, interpret, scale, window, stair)
        return (out, lse) if return_lse else out
    return _flash_out(q, k, v, causal, interpret, scale, window)


def _out_alone(q, k, v, causal, interpret, scale, window):
    out, _ = _flash_fwd_impl(
        q, k, v, causal=causal, interpret=interpret, scale=scale,
        window=window,
    )
    return out


# the name this call has always carried in a jaxpr
_out_alone.__name__ = _out_alone.__qualname__ = "flash_attention"
_flash_out = jax.custom_vjp(_out_alone, nondiff_argnums=(3, 4, 5, 6))


def _fwd(q, k, v, causal, interpret, scale, window):
    out, lse = _flash_fwd_impl(
        q, k, v, causal=causal, interpret=interpret, scale=scale,
        window=window,
    )
    return out, (q, k, v, out, lse)


def _rows_of(lse8, B: int, H: int):
    """The kernels' (B*H, 8, Sq) sublane-broadcast statistic as (B, Sq, H)."""
    return lse8[:, 0, :].reshape(B, H, -1).transpose(0, 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, interpret, scale, window, stair):
    return _fwd_lse(q, k, v, causal, interpret, scale, window, stair)[0]


def _fwd_lse(q, k, v, causal, interpret, scale, window, stair):
    out, lse8 = _flash_fwd_impl(
        q, k, v, causal=causal, interpret=interpret, scale=scale,
        window=window, stair=stair,
    )
    return (out, _rows_of(lse8, q.shape[0], q.shape[2])), (q, k, v, out, lse8)


def _bwd_lse(causal, interpret, scale, window, stair, res, cotangents):
    do, dlse = cotangents
    return _bwd(causal, interpret, scale, window, res, do, dlse=dlse,
                stair=stair)


class BwdPlan(NamedTuple):
    """The backward kernels' tiles and fetches, from shapes alone
    (``_bwd_plan``)."""

    block_q: int
    block_k: int
    dq_whole: bool   # dq: a (batch*head) row arrives whole, one grid step
    dkv_whole: bool  # dk/dv: a kv head and its group's rows likewise
    unrolled: bool   # a whole row's walk is unrolled at trace time


class BwdTileCounts(NamedTuple):
    """``bwd_tile_counts``: the dq kernel's tiles and steps per
    (batch*head) row, the dk/dv kernel's per (batch*kv head) row (every
    member of the group counted)."""

    dq: TileCounts
    dkv: TileCounts


#: a whole row's walk is unrolled up to this many tiles (group members
#: counted): straight-line code lets the compiler overlap one tile's
#: vector work with the next one's products; past it, loops
_UNROLL_TILES = 16


def _bwd_plan(Sq: int, Skv: int, D: int, itemsize: int, group: int = 1,
              window: int | None = None,
              stair: tuple[int, int] | None = None) -> BwdPlan:
    """The backward's plan, chosen from what the operands show.

    Score tiles are ``_pick_block`` squares.  Without a window, a kernel's
    operands arrive a whole row at a time where each fits
    ``_KV_FETCH_BYTES`` (the forward's budget: all of S at the GPT-2
    shape): one grid step a row, no step that does nothing, and the live
    tiles walked inside — unrolled where they are few.  Else, and under a
    window, the grid walks a row's or a column's live tiles and a step
    past the last one names the block already in VMEM (no DMA).
    """
    block_q, block_k = _stair_blocks(Sq, Skv, stair)
    fits = lambda rows: rows * D * itemsize <= _KV_FETCH_BYTES  # noqa: E731
    whole = window is None and fits(Skv) and fits(Sq)
    tiles = (Sq // block_q) * (Skv // block_k) * group
    return BwdPlan(block_q, block_k, whole, whole and fits(group * Sq),
                   whole and tiles <= _UNROLL_TILES)


def _q_walk(j, *, causal: bool, block_q: int, block_k: int, q_offset: int,
            window: int | None, n_q: int,
            stair: tuple[int, int] | None = None):
    """``(first, free, end)`` for kv block ``j``: q blocks ``[first, end)``
    are ``_block_live``; without a window those from ``free`` on are
    ``_block_unmasked`` and the ones before it crossed by the diagonal
    (under a window ``free`` is not used: the edge crosses tiles further
    down, and the grid tests each).  ``end <= first``: a column no query
    sees.  ``_kv_span`` read by column; ``j`` static or traced.  Under a
    ``stair`` the column's keys belong to step ``j * block_k // k_step``,
    which every q block from that step on sees whole."""
    if stair is not None:
        q_step, k_step = stair
        first = (j * block_k // k_step) * (q_step // block_q)
        return first, first, n_q
    if not causal:
        return 0, 0, n_q
    first, last = _q_span(j, block_q=block_q, block_k=block_k,
                          q_offset=q_offset, window=window, n_q=n_q)
    top, least = (max, min) if isinstance(j, int) else (jnp.maximum, jnp.minimum)
    end = last + 1
    if window is not None:
        # ``_q_span`` clips at q block 0: a column wholly left of the
        # first query's window has no live tile, not that one
        seen = (j + 1) * block_k + window - 2 - q_offset >= 0
        end = (end if seen else first) if isinstance(j, int) else jnp.where(
            seen, end, first)
    # the first q block whose first query sees the column's last key
    free = top((j + 1) * block_k - 1 - q_offset + block_q - 1, 0) // block_q
    return first, least(free, n_q), end


def _bwd_steps(Sq: int, Skv: int, causal: bool, q_offset: int, plan: BwdPlan,
               window: int | None,
               stair: tuple[int, int] | None = None) -> tuple[int, int]:
    """Inner grid extents ``(kv_steps, q_steps)`` of the two kernels where
    the grid walks: as many steps as the widest row or column has live
    tiles."""
    n_q, n_k = Sq // plan.block_q, Skv // plan.block_k
    geom = dict(block_q=plan.block_q, block_k=plan.block_k, q_offset=q_offset)
    kv_steps = max(
        _kv_span(i, causal=causal, n_k=n_k, stair=stair, **geom)[1]
        - _kv_window(i, window=window, **geom)[0] for i in range(n_q)
    )
    walks = [_q_walk(j, causal=causal, window=window, n_q=n_q, stair=stair,
                     **geom) for j in range(n_k)]
    return kv_steps, max(1, max(end - first for first, _, end in walks))


def bwd_tile_counts(Sq: int, Skv: int, causal: bool, q_offset: int,
                    plan: BwdPlan, window: int | None = None,
                    group: int = 1,
                    stair: tuple[int, int] | None = None) -> BwdTileCounts:
    """How often each path of the two backward kernels engages — static,
    like ``fwd_tile_counts``: tiles run without a mask / with one / never
    entered, and the grid steps launched."""
    n_q, n_k = Sq // plan.block_q, Skv // plan.block_k
    # the tiles are the forward's at the same squares, whatever it fetches
    tiles = fwd_tile_counts(
        Sq, Skv, causal, q_offset,
        FwdPlan(plan.block_q, plan.block_k, plan.block_k), window, stair)[:3]
    kv_steps, q_steps = _bwd_steps(Sq, Skv, causal, q_offset, plan, window,
                                   stair)
    return BwdTileCounts(
        TileCounts(*tiles, 1 if plan.dq_whole else n_q * kv_steps),
        TileCounts(*(group * t for t in tiles),
                   1 if plan.dkv_whole else n_k * q_steps * group),
    )


def _nt(a, b):
    """``a @ b.T`` on the MXU, f32 out."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _recompute_p_ds(rows, cols, d_rows, d_cols, lse, delta, *, scale, mask):
    """Shared blockwise backward math for one score tile, in either
    orientation.

    Recomputes the probability tile from the saved log-sum-exp and applies
    the flash-attention identities:

        p  = exp(s - lse)               (exact softmax row, no renorm pass)
        dp = do vᵀ
        ds = p * (dp - delta)           delta = rowsum(do * out), saved

    The dq kernel passes ``(q, k, do, v)`` and the statistics as (BQ, 1)
    columns: tiles are (BQ, BK).  The dk/dv kernel passes ``(k, q, v, do)``
    and the statistics as (1, BQ) rows: tiles are (BK, BQ).  ``scale`` is
    None where the caller folded it into an operand; ``mask`` masks the
    tile or is None.
    """
    s = _nt(rows, cols)
    if scale is not None:
        s = s * scale
    if mask is not None:
        s = mask(s)
    p = jnp.exp(s - lse)  # masked entries: exp(NEG_INF - lse) = 0
    ds = p * (_nt(d_rows, d_cols) - delta)
    return p, ds


def _fold(x, scale: float):
    """``(x * scale, None)`` where the multiply is bit-exact on the
    operand (a power of two: 2^-3 at D = 64), else ``(x, scale)``: one
    (rows, D) multiply a tile, not one (BQ, BK)."""
    if math.frexp(scale)[0] == 0.5:
        return (x.astype(jnp.float32) * scale).astype(x.dtype), None
    return x, scale


def _tile_rows(x, block: int):
    """Rows ``[x * block, (x + 1) * block)``; ``x`` static or traced."""
    if isinstance(x, int):
        return pl.ds(x * block, block)
    return pl.ds(pl.multiple_of(x * block, block), block)


def _walk(lo, hi, body, unrolled: bool):
    """``body(x)`` for ``x`` in ``[lo, hi)``, ascending: at trace time
    (the bounds are then Python ints) or as a loop."""
    if unrolled:
        for x in range(lo, hi):
            body(x)
    else:
        jax.lax.fori_loop(lo, hi, lambda x, _: body(x), None)


def _bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref,  # (1, BQ | Sq, D), (1, BK | Skv, D) x2, as q
    lse_ref, delta_ref,           # (1, 8, BQ | Sq)
    dq_ref,                       # as q
    dq_acc,                       # VMEM (BQ, D) f32
    *, causal: bool, block_q: int, block_k: int, scale: float, q_offset: int,
    n_q: int, n_k: int, whole: bool, unrolled: bool, window: int | None = None,
    stair: tuple[int, int] | None = None,
):
    """Every q block of a (batch*head) row against its live kv tiles, kv
    ascending.  ``whole``: grid (B*H,), the row's operands in VMEM, and
    per q block two walks — the tiles below the diagonal without a mask,
    then the ones it crosses; no step is dead.  Else grid (B*H, q blocks,
    kv steps): step ``y`` is the q block's ``y``-th live tile counted from
    the window's first (``_kv_window``; tile 0 without one), and a step
    past the diagonal's does nothing."""
    geom = dict(block_q=block_q, block_k=block_k, q_offset=q_offset)
    windowed = dict(geom, window=window)

    def tile(i, j, qs, ks, *, masked: bool):
        q, fold = _fold(q_ref[0, qs, :], scale)
        k = k_ref[0, ks, :]
        _, ds = _recompute_p_ds(
            q, k, do_ref[0, qs, :], v_ref[0, ks, :],
            lse_ref[0, 0, qs][:, None], delta_ref[0, 0, qs][:, None],
            scale=fold,
            mask=functools.partial(_causal_mask_scores, i=i, j=j, **windowed)
            if masked else None,
        )
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    def _open():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _finish(qs):
        dq_ref[0, qs, :] = (dq_acc[...] * scale).astype(dq_ref.dtype)

    def row(i):
        qs = _tile_rows(i, block_q)
        full, live = _kv_span(i, causal=causal, n_k=n_k, stair=stair, **geom)
        _open()
        _walk(0, full, lambda j: tile(
            i, j, qs, _tile_rows(j, block_k), masked=False), unrolled)
        if causal:
            _walk(full, live, lambda j: tile(
                i, j, qs, _tile_rows(j, block_k), masked=True), unrolled)
        _finish(qs)

    if whole:
        _walk(0, n_q, row, unrolled)
        return
    i, y = pl.program_id(1), pl.program_id(2)
    every = slice(None)
    pl.when(y == 0)(_open)
    if causal:
        full, live = _kv_span(i, causal=True, n_k=n_k, **geom)
        start, lo = _kv_window(i, window=window, **geom)
        j = start + y
        free = (lo <= j) & (j < full)
        pl.when(free)(lambda: tile(i, j, every, every, masked=False))
        pl.when((j < live) & ~free)(lambda: tile(i, j, every, every, masked=True))
    elif stair is not None:
        _, live = _kv_span(i, causal=False, n_k=n_k, stair=stair, **geom)
        pl.when(y < live)(lambda: tile(i, y, every, every, masked=False))
    else:
        tile(i, y, every, every, masked=False)
    pl.when(y == pl.num_programs(2) - 1)(lambda: _finish(every))


def _bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref,  # (1 | group, BQ | Sq, D), (1, BK | Skv, D) x2, as q
    lse_ref, delta_ref,           # (1 | group, 8, BQ | Sq)
    dk_ref, dv_ref,               # as k
    dk_acc, dv_acc,               # VMEM (BK, D) f32
    *, causal: bool, block_q: int, block_k: int, scale: float, q_offset: int,
    group: int, n_q: int, n_k: int, whole: bool, unrolled: bool,
    window: int | None = None, stair: tuple[int, int] | None = None,
):
    """Every kv block of one KV HEAD against its live (q block, group
    member) pairs — q block ascending, member inside — so GQA's shared kv
    gradients accumulate in one scratch pass.  The tile is computed
    TRANSPOSED, (BK, BQ): lse and delta are then wanted as rows broadcast
    down the sublanes, which is the (8, BQ) layout they arrive in, and
    ``dv += pᵀ do``, ``dk += dsᵀ q`` are plain products.  ``whole``: grid
    (B*Hkv,), K / V and the group's q / do / lse / delta in VMEM, and per
    kv block two walks — the q blocks the diagonal crosses, then the ones
    below it; no step is dead.  Else grid (B*Hkv, kv blocks, q steps *
    group): step ``t`` is the column's ``t``-th live pair counted from its
    first q block (``_q_span``), and a step past the last does nothing."""
    geom = dict(block_q=block_q, block_k=block_k, q_offset=q_offset)
    windowed = dict(geom, window=window)

    def tile(i, j, g, qs, ks, *, masked: bool):
        k, fold = _fold(k_ref[0, ks, :], scale)
        q, do = q_ref[g, qs, :], do_ref[g, qs, :]
        p, ds = _recompute_p_ds(
            k, q, v_ref[0, ks, :], do,
            lse_ref[g, :1, qs], delta_ref[g, :1, qs],
            scale=fold,
            mask=functools.partial(_causal_mask_scores, i=i, j=j,
                                   transposed=True, **windowed)
            if masked else None,
        )
        dv_acc[...] += jnp.dot(p.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dk_acc[...] += jnp.dot(ds.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    def _open():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _finish(ks):
        dk_ref[0, ks, :] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0, ks, :] = dv_acc[...].astype(dv_ref.dtype)

    def column(j):
        ks = _tile_rows(j, block_k)
        first, free, end = _q_walk(j, causal=causal, window=None, n_q=n_q,
                                   stair=stair, **geom)

        def pair(t, *, masked: bool):
            i, g = (t // group, t % group) if group > 1 else (t, 0)
            tile(i, j, g, _tile_rows(i, block_q), ks, masked=masked)

        _open()
        _walk(first * group, free * group,
              lambda t: pair(t, masked=True), unrolled)
        _walk(free * group, end * group,
              lambda t: pair(t, masked=False), unrolled)
        _finish(ks)

    if whole:
        _walk(0, n_k, column, unrolled)
        return
    j, t = pl.program_id(1), pl.program_id(2)
    every = slice(None)
    pl.when(t == 0)(_open)
    if causal:
        first, _, end = _q_walk(j, causal=True, window=window, n_q=n_q, **geom)
        i = first + t // group
        free = _block_unmasked(i, j, causal=True, **windowed)
        pl.when((i < end) & free)(
            lambda: tile(i, j, 0, every, every, masked=False))
        pl.when((i < end) & ~free)(
            lambda: tile(i, j, 0, every, every, masked=True))
    elif stair is not None:
        first, _, end = _q_walk(j, causal=False, window=None, n_q=n_q,
                                stair=stair, **geom)
        i = first + t // group
        pl.when(i < end)(lambda: tile(i, j, 0, every, every, masked=False))
    else:
        tile(t // group, j, 0, every, every, masked=False)
    pl.when(t == pl.num_programs(2) - 1)(lambda: _finish(every))


def _bwd(causal, interpret, scale, window, res, do, *, dlse=None, stair=None):
    """Blockwise flash backward: two Pallas kernels, O(S) peak memory.

    Probability tiles are recomputed per (q block, kv block) pair from the
    saved lse — the (S, S) matrix never exists.  dq runs with kv blocks
    innermost (accumulating dq_i in VMEM); dk/dv run with q blocks
    innermost (accumulating dk_j/dv_j).  ``delta = rowsum(do * out)`` is a
    cheap O(S·D) XLA reduction done once up front.  ``dlse`` (B, Sq, H) is
    the cotangent of the row statistic where it was handed out: ``d s =
    p * (dp - delta) + p * dlse``, so it is taken off ``delta``.
    """
    q, k, v, out, lse = res
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    Hkv = k.shape[2]

    # (B, S, H, D) -> (B*H, S, D) flat layout, matching the forward; kv
    # stays at its own head count (GQA shares it across the group).
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * Hkv, Skv, D)
    dof = do.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    outf = out.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)

    delta = jnp.sum(
        dof.astype(jnp.float32) * outf.astype(jnp.float32), axis=-1
    )  # (B*H, Sq)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32).transpose(0, 2, 1).reshape(
            B * H, Sq)
    # Row vectors enter the kernels broadcast over 8 sublanes (the TPU
    # (8, 128) tiling minimum).  lse arrives from the forward already in
    # that layout; only delta needs the broadcast.
    delta8 = jnp.broadcast_to(delta[:, None, :], (B * H, 8, Sq))
    dq, dk, dv = _bwd_launch(
        qf, kf, vf, dof, lse, delta8, H=H, Hkv=Hkv, causal=causal,
        interpret=interpret, scale=scale, window=window, stair=stair,
    )
    dq = dq.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    dk = dk.reshape(B, Hkv, Skv, D).transpose(0, 2, 1, 3)
    dv = dv.reshape(B, Hkv, Skv, D).transpose(0, 2, 1, 3)
    return dq, dk, dv


@functools.partial(
    jax.jit,
    static_argnames=("H", "Hkv", "causal", "interpret", "scale", "window",
                     "stair"),
)
def _bwd_launch(qf, kf, vf, dof, lse8, delta8, *, H: int, Hkv: int,
                causal: bool, interpret: bool, scale: float | None = None,
                window: int | None = None,
                stair: tuple[int, int] | None = None):
    """The backward's two ``pallas_call``s, on flat (rows, S, D) operands
    and (rows, 8, Sq) statistics; jitted on its own for ``_fwd_launch``'s
    reason (a model's layers share one trace and lowering of each kernel).

    Neither call carries a ``CostEstimate``, as in the parent: with one,
    XLA prefetches other operands round the kernels and re-tiles the
    matmuls that read them (one fusion in the GPT-2 step, eleven in two
    layers of the trinity step, AOT), which moves the low digits of every
    cell's gradients; without, the compiled steps are the parent's to the
    last instruction outside the two custom calls.  ``bwd_tile_counts``
    is the count it would be fed from.
    """
    from jax.experimental.pallas import tpu as pltpu

    rows, Sq, D = qf.shape
    kv_rows, Skv, _ = kf.shape
    group = H // Hkv
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    q_offset = Skv - Sq
    plan = _bwd_plan(Sq, Skv, D, qf.dtype.itemsize, group, window, stair)
    block_q, block_k = plan.block_q, plan.block_k
    n_q, n_k = Sq // block_q, Skv // block_k
    kv_steps, q_steps = _bwd_steps(Sq, Skv, causal, q_offset, plan, window,
                                   stair)
    geom = dict(block_q=block_q, block_k=block_k, q_offset=q_offset)
    kw = dict(causal=causal, scale=scale, n_q=n_q, n_k=n_k, window=window,
              unrolled=plan.unrolled, **geom,
              **({} if stair is None else {"stair": stair}))
    kv_row = functools.partial(_gqa_kv_row, H=H, Hkv=Hkv)

    if plan.dq_whole:
        grid = (rows,)
        q_spec = pl.BlockSpec((1, Sq, D), lambda b: (b, 0, 0))
        stat_spec = pl.BlockSpec((1, 8, Sq), lambda b: (b, 0, 0))
        kv_spec = pl.BlockSpec((1, Skv, D), lambda b: (kv_row(b), 0, 0))
    else:
        # a q block's steps name its live kv blocks in order and, past the
        # last one, that one again (no DMA)
        def kv_blk(x, y):
            start, _ = _kv_window(x, window=window, **geom)
            _, live = _kv_span(x, causal=causal, n_k=n_k, stair=stair, **geom)
            return jnp.minimum(start + y, live - 1)

        grid = (rows, n_q, kv_steps)
        q_spec = pl.BlockSpec((1, block_q, D), lambda b, x, y: (b, x, 0))
        stat_spec = pl.BlockSpec((1, 8, block_q), lambda b, x, y: (b, 0, x))
        kv_spec = pl.BlockSpec(
            (1, block_k, D), lambda b, x, y: (kv_row(b), kv_blk(x, y), 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, whole=plan.dq_whole, **kw),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, qf.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
        name=scopes.FLASH_BWD_DQ,
    )(qf, kf, vf, dof, lse8, delta8)

    # dk/dv: one grid row per KV head; its group's q heads are the
    # ``group`` flat rows from ``b * group``
    if plan.dkv_whole:
        grid = (kv_rows,)
        q_spec = pl.BlockSpec((group, Sq, D), lambda b: (b, 0, 0))
        stat_spec = pl.BlockSpec((group, 8, Sq), lambda b: (b, 0, 0))
        kv_spec = pl.BlockSpec((1, Skv, D), lambda b: (b, 0, 0))
    else:
        # a kv block's steps name its live (q block, member) pairs in
        # order and, past the last pair, that one again (no DMA)
        def q_at(b, y, t):
            first, _, end = _q_walk(
                y, causal=causal, window=window, n_q=n_q, stair=stair, **geom)
            t = jnp.clip(t, 0, jnp.maximum((end - first) * group - 1, 0))
            return b * group + t % group, first + t // group

        def q_index(b, y, t):
            row, blk = q_at(b, y, t)
            return (row, blk, 0)

        def stat_index(b, y, t):
            row, blk = q_at(b, y, t)
            return (row, 0, blk)

        grid = (kv_rows, n_k, q_steps * group)
        q_spec = pl.BlockSpec((1, block_q, D), q_index)
        stat_spec = pl.BlockSpec((1, 8, block_q), stat_index)
        kv_spec = pl.BlockSpec((1, block_k, D), lambda b, y, t: (b, y, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, group=group, whole=plan.dkv_whole,
                          **kw),
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[
            jax.ShapeDtypeStruct(kf.shape, kf.dtype),
            jax.ShapeDtypeStruct(vf.shape, vf.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
        name=scopes.FLASH_BWD_DKV,
    )(qf, kf, vf, dof, lse8, delta8)
    return dq, dk, dv


_flash_out.defvjp(_fwd, _bwd)
_flash_lse.defvjp(_fwd_lse, _bwd_lse)
