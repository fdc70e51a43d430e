"""State-space duality (Mamba-2) scan, in its chunked form.

The recurrence, for each head (P channels, an N-wide state)::

    h_t = exp(dt_t * A) * h_{t-1} + dt_t * x_t (outer) B_t      (P, N)
    y_t = h_t . C_t  [+ D * x_t]

is linear in ``h``, so a sequence cut into chunks of ``L`` steps needs
the step-by-step form nowhere (Dao & Gu 2024, "Transformers are SSMs",
section 6).  With ``cum_i`` the running sum of ``dt * A`` inside a chunk:

1. within a chunk: ``y_i += sum_{j<=i} exp(cum_i - cum_j) dt_j (C_i . B_j)
   x_j`` — the masked decay matrix times ``C B^T``, applied to ``x``;
2. a state per chunk: ``sum_j exp(cum_L - cum_j) dt_j x_j (outer) B_j`` —
   what the chunk alone leaves behind at its end;
3. the states carried chunk to chunk: ``h_in(c+1) = exp(cum_L(c)) *
   h_in(c) + state(c)`` (a scan over S / L steps);
4. their contribution: ``y_i += exp(cum_i) * C_i . h_in``.

Four matrix products (``C B^T``, the masked matrix times ``x``, ``B^T x``
and ``C h``) take bf16 operands under bf16 activations and accumulate in
f32; ``dt``, ``A``, the running sums, the exponentials and the carried
state stay f32.

**What is a kernel.**  Steps 1 and 4 and the ``D * x`` skip are one Pallas
forward kernel (``ssd_fwd``) and one backward kernel (``ssd_bwd``) under a
``jax.custom_vjp`` (``_within_chunk``).  A grid step owns one (batch,
chunk) and a block of heads of one group; it forms ``C B^T`` once for all
heads of the group and builds each head's masked decay tile in VMEM, so no
(L, L) tile reaches HBM.  The tile is walked in 128-square sub-tiles;
those above the diagonal are never computed (``tile_counts``).  The
backward saves nothing but the kernel's inputs, recomputes the tile, and
leaves every gradient of the chunk in one launch; the sums over a group's
heads for ``dB`` and ``dC`` are kept in VMEM across the head blocks.

The kernels read and write ``x``, ``y``, ``dy``, ``dx`` sequence minor,
(batch, heads * dim, seq): that, not (batch, seq, heads * dim), is how the
compiled step holds the mixer's activations (XLA gives every activation
of this model the layout ``{1,2,0}``), so the logical transposes round
``_within_chunk`` cost nothing and no transposed copy reaches HBM either.
Asked for (batch, seq, heads * dim) operands instead, XLA moved the
mixer's projections to that layout and they lost more than the scan
gained (PERF.md section 6, PR 30).

**What stays ``jax.numpy``.**  Steps 2 and 3 (the chunk's own state and
``_carry_states``), the running sums and the padding, differentiated by
JAX; and ``_plain_within_chunk``, steps 1 and 4 as PR 28 wrote them.
What comes before the scan in the mixer, the causal convolution that
makes ``x``, ``B`` and ``C``, is an op of its own (``ops/causal_conv.py``,
PR 32), whose kernels write the three arrays sequence minor as
``_within_chunk`` reads them: no copy lies between the two custom calls.

**Which shapes take which path.**  ``ssd_chunked`` picks from what it is
given (``supported``): the kernels on a TPU backend where the chunk is a
multiple of 128, the state fills the lanes (N a multiple of 128), a
head's channels fill sublane tiles (P a multiple of 16) and a grid step's
heads do (8, 16, ... or all of them), in bf16 or f32; anything else — the
CPU, chunk 20, a state of 16 — takes the plain form, which is also what
the tests hold the kernels against (``_interpret=True`` runs them on the
CPU).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributeddataparallel_tpu.observability import cost_model, scopes

_LANES = 128
#: channels of ``x`` (heads * dim) a grid step owns: its block's rows
_BLOCK_ROWS = 1024
#: the masked exponent; exp gives 0 exactly
_MASKED = -1e30


def _carry_states(states, chunk_decay):
    """``h_in`` of every chunk, (b, c, g, r, p, n) f32: the state before the
    first chunk is nought, and chunk ``c`` hands ``exp(cum_L) * h_in +
    state`` to chunk ``c + 1``."""

    def step(h_in, chunk):
        state, decay = chunk
        return decay[..., None, None] * h_in + state, h_in

    _, h_in = jax.lax.scan(
        step, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)),
    )
    return jnp.moveaxis(h_in, 0, 1)


# ------------------------------------------------------------ the tile plan

class Plan(NamedTuple):
    """The kernels' tiles, from shapes alone (``_plan``)."""

    tile: int   # edge of a sub-tile of the (L, L) chunk tile
    heads: int  # heads a grid step owns


class TileCounts(NamedTuple):
    """Sub-tiles of a launch's (L, L) chunk tiles: computed, and never
    entered because they lie above the diagonal."""

    live: int
    skipped: int


def _tile(L: int) -> int:
    return _LANES if L % _LANES == 0 else L


def _plan(L: int, per_group: int, head_dim: int) -> Plan:
    """A 128-square sub-tile where the chunk divides (else the chunk
    whole: the small shapes of the CPU tests); as many heads of one
    group to a grid step as ``_BLOCK_ROWS`` channels hold."""
    heads = max(k for k in range(1, per_group + 1)
                if per_group % k == 0
                and (k == 1 or k * head_dim <= _BLOCK_ROWS))
    return Plan(_tile(L), heads)


def tile_counts(batch: int, seq: int, heads: int, chunk: int) -> TileCounts:
    """How many sub-tiles one launch (forward or backward) computes and
    how many it never enters — static, like the mechanism.  At the
    published chunk of 256 three of a chunk tile's four are live."""
    L = min(chunk, seq)
    n = L // _tile(L)
    tiles = batch * -(-seq // L) * heads
    return TileCounts(tiles * n * (n + 1) // 2, tiles * n * (n - 1) // 2)


def supported(x, B, chunk: int) -> bool:
    """True where the kernels run natively: a TPU backend, a chunk of
    whole 128-square sub-tiles, a state that fills the lanes, heads whose
    channels fill sublane tiles, a dtype the MXU takes.  ``x``
    (b, s, h, p), ``B`` (b, s, g, n); only shapes and dtypes are read."""
    if jax.default_backend() != "tpu":
        return False
    _, s, h, p = x.shape
    g, n = B.shape[2:]
    if h % g or x.dtype not in (jnp.bfloat16, jnp.float32):
        return False
    heads = _plan(min(chunk, s), h // g, p).heads
    return (
        min(chunk, s) % _LANES == 0
        and n % _LANES == 0
        and p % 16 == 0
        # a block of per-head rows is whole sublane tiles, or all of them
        and (heads % 8 == 0 or heads == h)
    )


# -------------------------------------------------------------- the kernels
#
# Both kernels hold a head's channels along sublanes and the chunk's steps
# along lanes, (P, L): the layout XLA gives the mixer's activations in the
# compiled step (sequence minor), so ``x``, ``y``, ``dy``, ``dx`` come and go
# with no relayout, and a head is a sublane slice.  The tile is held with
# rows ``i`` and columns ``j``, ``m = S * decay * dt_j``; every product
# with it is a plain one or an ``a b^T``.

def _contract(a, b, dims):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32
    )


_ROWS = ((1,), (1,))  # a b^T
_COLS = ((0,), (0,))  # a^T b


def _sub_tile(s_ref, cumc_ref, cumr_ref, k: int, ib: int, jb: int, T: int):
    """Head ``k``'s ``(decay, S)`` on sub-tile (ib, jb) of the chunk tile:
    ``exp(cum_i - cum_j)`` with the exponent masked above the diagonal
    (the exponential of a step ahead would overflow), and ``C_i . B_j``."""
    cols, steps = slice(ib * T, (ib + 1) * T), slice(jb * T, (jb + 1) * T)
    diff = cumc_ref[0, 0, 0, cols, k:k + 1] - cumr_ref[0, k:k + 1, steps]
    if ib == jb:
        i = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        diff = jnp.where(i >= j, diff, _MASKED)
    return jnp.exp(diff), s_ref[cols, steps]


def _fwd_kernel(
    d_ref,                       # SMEM (h,) f32
    x_ref,                       # (1, HB * P, L)
    dtr_ref, cumr_ref,           # (1, HB, L) f32: steps along lanes
    cumc_ref,                    # (1, 1, 1, L, HB) f32: steps along sublanes
    b_ref, c_ref,                # (1, N, L)
    h_ref,                       # (1, 1, HB * P, N) f32
    y_ref,                       # (1, HB * P, L)
    s_ref,                       # VMEM (L, L) f32: C B^T, rows i, columns j
    z_ref,                       # VMEM (HB * P, L) f32: h_in C^T
    *, plan: Plan, head_dim: int, per_group: int,
):
    """One (batch, chunk) and ``plan.heads`` heads of one group:
    ``y^T = x^T m^T + exp(cum_i) * h_in C^T + D x^T``."""
    T, HB = plan
    P = head_dim
    nt = x_ref.shape[2] // T
    f32 = jnp.float32
    dtype = x_ref.dtype
    hb = pl.program_id(2)

    @pl.when(hb % per_group == 0)
    def _scores():
        s_ref[...] = _contract(c_ref[0], b_ref[0], _COLS)

    # every head's state read out in one product: the MXU loads C^T once
    z_ref[...] = jnp.dot(h_ref[0, 0].astype(dtype), c_ref[0],
                         preferred_element_type=f32)
    for k in range(HB):
        rows = slice(k * P, (k + 1) * P)
        skip = d_ref[hb * HB + k]
        for ib in range(nt):
            cols = slice(ib * T, (ib + 1) * T)
            acc = jnp.exp(cumr_ref[0, k:k + 1, cols]) * z_ref[rows, cols]
            for jb in range(ib + 1):
                steps = slice(jb * T, (jb + 1) * T)
                decay, s = _sub_tile(s_ref, cumc_ref, cumr_ref, k, ib, jb, T)
                m = (s * decay * dtr_ref[0, k:k + 1, steps]).astype(dtype)
                acc += _contract(x_ref[0, rows, steps], m, _ROWS)
            y_ref[0, rows, cols] = (
                acc + skip * x_ref[0, rows, cols].astype(f32)
            ).astype(dtype)


def _bwd_kernel(
    d_ref,                       # SMEM (h,) f32
    x_ref, dy_ref,               # (1, HB * P, L)
    dtr_ref, cumr_ref,           # (1, HB, L) f32
    cumc_ref,                    # (1, 1, 1, L, HB) f32
    b_ref, c_ref,                # (1, N, L)
    h_ref,                       # (1, 1, HB * P, N) f32
    dx_ref,                      # (1, HB * P, L)
    ddt_ref,                     # (1, HB, L) f32: through the tile's dt_j
    dcum_ref,                    # (1, HB, L) f32: through both exponents
    dd_ref,                      # (1, HB, L) f32: sum over p of dy * x
    db_ref, dc_ref,              # (1, N, L)
    dh_ref,                      # (1, 1, HB * P, N) f32
    s_ref,                       # VMEM (L, L) f32: C B^T, rows i, columns j
    ds_ref,                      # VMEM (L, L) f32: its gradient, summed over heads
    dcs_ref,                     # VMEM (N, L) f32: dC^T through the state
    dz_ref,                      # VMEM (HB * P, L): exp(cum_i) * dy
    z_ref,                       # VMEM (HB * P, L) f32: h_in C^T
    *, plan: Plan, head_dim: int, per_group: int,
):
    """The backward of ``_fwd_kernel``'s program, tile recomputed: with
    ``dm = dy x^T`` (contracted over the head's channels),

        dx^T    = dy^T m
        dS      = sum over heads of dm * decay * dt_j
        d dt_j  = colsum(dm * decay * S)
        d cum   = rowsum(dm * m) at i, - colsum(dm * m) at j

    Neither sum is taken over the tile: the first is ``sum over p of dy *
    (x^T m^T)``, through the forward's own product, the second ``sum over
    p of x * (dy^T m)``, through ``dx``'s — sums over sublanes of (P, T)
    blocks, where summing the tile along its lanes cost more than
    everything else here together.  Both then take ``m`` as the products
    do, rounded to the compute dtype: their totals cancel (``dA`` is what
    is left of them), and they only do if both round alike.  ``dy``
    enters each product in the compute dtype; every sum is f32."""
    T, HB = plan
    P = head_dim
    nt = x_ref.shape[2] // T
    f32 = jnp.float32
    dtype = x_ref.dtype
    hb = pl.program_id(2)

    @pl.when(hb % per_group == 0)
    def _open():
        s_ref[...] = _contract(c_ref[0], b_ref[0], _COLS)
        ds_ref[...] = jnp.zeros_like(ds_ref)
        dcs_ref[...] = jnp.zeros_like(dcs_ref)

    hs = h_ref[0, 0].astype(dtype)                                 # (HB * P, N)
    z_ref[...] = jnp.dot(hs, c_ref[0], preferred_element_type=f32)
    for k in range(HB):
        rows = slice(k * P, (k + 1) * P)
        skip = d_ref[hb * HB + k]
        dyf = dy_ref[0, rows, :].astype(f32)                       # (P, L)
        dd_ref[0, k:k + 1, :] = jnp.sum(
            dyf * x_ref[0, rows, :].astype(f32), axis=0, keepdims=True
        )
        y = []     # y^T without the skip, a (P, T) block of steps
        back = []  # what leaves cum at j, a (1, T) block
        for ib in range(nt):
            cols = slice(ib * T, (ib + 1) * T)
            e = jnp.exp(cumr_ref[0, k:k + 1, cols])                # (1, T)
            dz_ref[rows, cols] = (e * dyf[:, cols]).astype(dtype)
            y.append(e * z_ref[rows, cols])
        for jb in range(nt):
            steps = slice(jb * T, (jb + 1) * T)
            xs = x_ref[0, rows, steps]
            dt_j = dtr_ref[0, k:k + 1, steps]                      # (1, T)
            dx = jnp.zeros((P, T), f32)
            ddt = jnp.zeros((1, T), f32)
            for ib in range(jb, nt):
                cols = slice(ib * T, (ib + 1) * T)
                decay, s = _sub_tile(s_ref, cumc_ref, cumr_ref, k, ib, jb, T)
                m = (s * decay * dt_j).astype(dtype)
                dys = dy_ref[0, rows, cols]
                dx += jnp.dot(dys, m, preferred_element_type=f32)
                y[ib] += _contract(xs, m, _ROWS)
                dm = decay * _contract(dys, xs, _COLS)             # (T, T)
                ds_ref[cols, steps] += dm * dt_j
                ddt += jnp.sum(dm * s, axis=0, keepdims=True)
            back.append(jnp.sum(xs.astype(f32) * dx, axis=0, keepdims=True))
            ddt_ref[0, k:k + 1, steps] = ddt
            dx_ref[0, rows, steps] = (dx + skip * dyf[:, steps]).astype(dtype)
        for ib in range(nt):
            cols = slice(ib * T, (ib + 1) * T)
            dcum_ref[0, k:k + 1, cols] = jnp.sum(
                dyf[:, cols] * y[ib], axis=0, keepdims=True
            ) - back[ib]

    # the read-out's two products, once for all heads of the step
    dh_ref[0, 0] = _contract(dz_ref[...], c_ref[0], _ROWS)
    dcs_ref[...] += _contract(hs, dz_ref[...], _COLS)

    @pl.when(hb % per_group == per_group - 1)
    def _close():
        ds = ds_ref[...].astype(dtype)
        db_ref[0] = jnp.dot(
            c_ref[0], ds, preferred_element_type=f32
        ).astype(db_ref.dtype)
        dc_ref[0] = (
            dcs_ref[...] + _contract(b_ref[0], ds, _ROWS)
        ).astype(dc_ref.dtype)


# ------------------------------------------------------------- the launches

class _Launch(NamedTuple):
    """What both launches make of their operands' shapes."""

    plan: Plan
    grid: tuple
    per_group: int   # grid steps along the heads that share one group
    columns: object  # (b, h, S) -> (b, c, h / HB, L, HB)
    specs: dict
    scores: object   # VMEM (L, L) f32: C B^T, or its gradient
    readout: object  # VMEM (HB * P, L) f32: h_in C^T
    cost: dict
    tiles: TileCounts


def _launch(x, B, h_in, head_dim: int) -> _Launch:
    from jax.experimental.pallas import tpu as pltpu

    b, inner, S = x.shape
    c, n = h_in.shape[0], h_in.shape[3]
    g = B.shape[1] // n
    h, L = inner // head_dim, S // c
    plan = _plan(L, h // g, head_dim)
    HB = plan.heads
    per_group = h // g // HB
    rows = HB * head_dim
    specs = {
        "wide": pl.BlockSpec((1, rows, L), lambda i, j, k: (i, k, j)),
        "row": pl.BlockSpec((1, HB, L), lambda i, j, k: (i, k, j)),
        "col": pl.BlockSpec((1, 1, 1, L, HB), lambda i, j, k: (i, j, k, 0, 0)),
        "group": pl.BlockSpec((1, n, L), lambda i, j, k: (i, k // per_group, j)),
        "skip": pl.BlockSpec(memory_space=pltpu.SMEM),
        "state": pl.BlockSpec((1, 1, rows, n), lambda i, j, k: (j, i, k, 0)),
    }

    def columns(a):
        return jnp.transpose(a.reshape(b, h // HB, HB, c, L), (0, 3, 1, 4, 2))

    return _Launch(
        plan, (b, c, h // HB), per_group, columns, specs,
        pltpu.VMEM((L, L), jnp.float32), pltpu.VMEM((rows, L), jnp.float32),
        cost_model.ssd_cost(b, S, h, head_dim, n, g, L),
        tile_counts(b, S, h, L),
    )


@functools.partial(jax.jit, static_argnames=("head_dim", "interpret"))
def _fwd_launch(x, dt, cum, B, C, D, h_in, *, head_dim: int, interpret: bool):
    """The forward's one ``pallas_call``.  Jitted on its own so that a
    model's mamba layers share one trace and one lowering of the kernel
    (``pallas_attention._fwd_launch``'s reason)."""
    z = _launch(x, B, h_in, head_dim)
    s = z.specs
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, plan=z.plan, head_dim=head_dim, per_group=z.per_group
        ),
        grid=z.grid,
        in_specs=[s["skip"], s["wide"], s["row"], s["row"], s["col"],
                  s["group"], s["group"], s["state"]],
        out_specs=s["wide"],
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[z.scores, z.readout],
        cost_estimate=pl.CostEstimate(
            flops=z.cost["flops"] // 3,
            transcendentals=z.tiles.live * z.plan.tile ** 2,
            bytes_accessed=z.cost["bytes"] // 2 + h_in.size * 4,
        ),
        interpret=interpret,
        name=scopes.SSD_FWD,
    )(D, x, dt, cum, z.columns(cum), B, C, h_in)


@functools.partial(jax.jit, static_argnames=("head_dim", "interpret"))
def _bwd_launch(x, dy, dt, cum, B, C, D, h_in, *, head_dim: int,
                interpret: bool):
    """The backward's one ``pallas_call``: every gradient of
    ``_within_chunk`` (``dD`` summed over batch and steps here)."""
    from jax.experimental.pallas import tpu as pltpu

    z = _launch(x, B, h_in, head_dim)
    s = z.specs
    f32 = jnp.float32
    row = jax.ShapeDtypeStruct(dt.shape, f32)
    dx, ddt, dcum, dyx, dB, dC, dh = pl.pallas_call(
        functools.partial(
            _bwd_kernel, plan=z.plan, head_dim=head_dim, per_group=z.per_group
        ),
        grid=z.grid,
        in_specs=[s["skip"], s["wide"], s["wide"], s["row"], s["row"],
                  s["col"], s["group"], s["group"], s["state"]],
        out_specs=[s["wide"], s["row"], s["row"], s["row"],
                   s["group"], s["group"], s["state"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype), row, row, row,
            jax.ShapeDtypeStruct(B.shape, B.dtype),
            jax.ShapeDtypeStruct(C.shape, C.dtype),
            jax.ShapeDtypeStruct(h_in.shape, f32),
        ],
        scratch_shapes=[
            z.scores, z.scores,
            pltpu.VMEM((h_in.shape[3], z.scores.shape[0]), f32),  # (N, L)
            pltpu.VMEM(z.readout.shape, x.dtype), z.readout,
        ],
        cost_estimate=pl.CostEstimate(
            flops=2 * z.cost["flops"] // 3,
            transcendentals=z.tiles.live * z.plan.tile ** 2,
            bytes_accessed=z.cost["bytes"] // 2 + 2 * h_in.size * 4
            + x.size * x.dtype.itemsize,
        ),
        interpret=interpret,
        name=scopes.SSD_BWD,
    )(D, x, dy, dt, cum, z.columns(cum), B, C, h_in)
    return dx, ddt, dcum, dB, dC, dyx.sum((0, 2)), dh


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _within_chunk(x, dt, cum, B, C, D, h_in, head_dim: int, interpret: bool):
    """Steps 1 and 4 and the skip, as kernels, sequence minor: ``x``
    (b, h * p, S) with S whole chunks; ``dt``, ``cum`` (b, h, S) f32;
    ``B``, ``C`` (b, g * n, S) in ``x``'s dtype; ``D`` (h,) f32; ``h_in``
    (c, b, h * p, n) f32, chunks first as the carry's scan stacks them.
    Returns ``y`` like ``x``."""
    return _fwd_launch(x, dt, cum, B, C, D, h_in, head_dim=head_dim,
                       interpret=interpret)


def _within_chunk_fwd(x, dt, cum, B, C, D, h_in, head_dim, interpret):
    y = _within_chunk(x, dt, cum, B, C, D, h_in, head_dim, interpret)
    return y, (x, dt, cum, B, C, D, h_in)


def _within_chunk_bwd(head_dim, interpret, res, dy):
    return _bwd_launch(res[0], dy, *res[1:], head_dim=head_dim,
                       interpret=interpret)


_within_chunk.defvjp(_within_chunk_fwd, _within_chunk_bwd)


# ---------------------------------------------------------------- the entry

def ssd_chunked(x, dt, A, B, C, D=None, *, chunk: int, _interpret: bool = False):
    """Chunked scan.  ``x`` (b, s, h, p) in the compute dtype; ``dt``
    (b, s, h) f32, after softplus; ``A`` (h,) f32, negative; ``B``, ``C``
    (b, s, g, n) with ``h`` a multiple of ``g`` (a group's heads share
    them); ``D`` (h,) the skip, or None.  Returns ``y`` (b, s, h, p) in
    ``x``'s dtype.  ``s`` need not divide by ``chunk``: the tail is
    padded with steps of ``dt`` = 0, which neither decay nor add.

    Steps 1 and 4 run as kernels where ``supported`` says so, else in the
    plain form; ``_interpret`` is the CPU tests' way into the kernels."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    if h % g:
        raise ValueError(f"heads {h} not a multiple of groups {g}")
    kernels = _interpret or supported(x, B, chunk)
    L = min(chunk, s)
    pad = -s % L
    if pad:
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, B, C)
        )
    S = s + pad
    c = S // L
    r = h // g
    dtype = x.dtype
    f32 = jnp.float32
    xc = x.reshape(b, c, L, g, r, p)
    Bc = B.reshape(b, c, L, g, n).astype(dtype)
    Cc = C.reshape(b, c, L, g, n).astype(dtype)
    # (b, c, g, r, L): a head's steps lie along the last axis
    dtc = jnp.moveaxis(dt.astype(f32).reshape(b, c, L, g, r), 2, -1)
    cum = jnp.cumsum(dtc * A.astype(f32).reshape(g, r, 1), axis=-1)

    # 2. what each chunk leaves behind at its end
    to_end = jnp.exp(cum[..., -1:] - cum) * dtc          # (b, c, g, r, j)
    weighted = xc * jnp.moveaxis(to_end, -1, 2)[..., None].astype(dtype)
    states = jnp.einsum(
        "bcjgn,bcjgrp->bcgrpn", Bc, weighted, preferred_element_type=f32
    )
    # 3. carried chunk to chunk
    h_in = _carry_states(states, jnp.exp(cum[..., -1]))

    if kernels:
        def minor(a, lead):  # (b, c, ..., L) -> (b, ..., S): steps last
            return jnp.moveaxis(a, 1, -2).reshape(b, lead, S)

        y = _within_chunk(
            jnp.swapaxes(x.reshape(b, S, h * p), 1, 2),
            minor(dtc, h), minor(cum, h),
            jnp.swapaxes(Bc.reshape(b, S, g * n), 1, 2),
            jnp.swapaxes(Cc.reshape(b, S, g * n), 1, 2),
            jnp.zeros((h,), f32) if D is None else D.astype(f32),
            jnp.moveaxis(h_in, 1, 0).reshape(c, b, h * p, n), p, _interpret,
        )
        return jnp.swapaxes(y, 1, 2).reshape(b, S, h, p)[:, :s]
    y = _plain_within_chunk(xc, dtc, cum, Bc, Cc, h_in)
    y = y.reshape(b, S, h, p)[:, :s]
    if D is not None:
        y = y + D.astype(f32)[:, None] * x[:, :s].astype(f32)
    return y.astype(dtype)


def _plain_within_chunk(xc, dtc, cum, Bc, Cc, h_in):
    """Steps 1 and 4 in ``jax.numpy``, differentiated by JAX: the path of
    every shape the kernels do not take, and the tests' second opinion.
    ``y`` (b, c, L, g, r, p) f32."""
    L = xc.shape[2]
    dtype = xc.dtype
    f32 = jnp.float32
    # 1. within a chunk.  The exponent is masked, not the exponential: a
    # step ahead of i would read exp(positive), and inf * 0 in the
    # backward pass.
    causal = jnp.tril(jnp.ones((L, L), bool))
    decay = jnp.exp(jnp.where(
        causal, cum[..., :, None] - cum[..., None, :], -jnp.inf
    ))                                                  # (b, c, g, r, i, j)
    scores = jnp.einsum(
        "bcign,bcjgn->bcgij", Cc, Bc, preferred_element_type=f32
    )
    mixed = scores[:, :, :, None] * decay * dtc[..., None, :]
    y = jnp.einsum(
        "bcgrij,bcjgrp->bcigrp", mixed.astype(dtype), xc,
        preferred_element_type=f32,
    )
    # 4. the carried state, read by every step of its chunk
    from_start = jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    return y + from_start * jnp.einsum(
        "bcign,bcgrpn->bcigrp", Cc, h_in.astype(dtype),
        preferred_element_type=f32,
    )
