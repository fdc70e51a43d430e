"""Grouped matrix product for an expert layer: row group g of ``rows``
times ``weights[g]`` — the dropless dispatch's three products
(``ops.moe.dropless``) and their gradients, as two Pallas TPU kernels.

The groups arrive **tile-aligned**: ``ops.moe.group_layout`` starts every
group at a multiple of ``ROW_TILE`` rows (the holes are zero rows), so a
row tile belongs to one expert, ``tile_group[t]``, and the first
``live`` tiles hold every row there is.  That is what keeps the kernels
plain: no tile is visited for two groups, nothing is masked.

- ``moe_gmm``: grid (column tiles, row tiles).  A step multiplies one
  (ROW_TILE, K) tile of rows by its expert's (K, tn) block — the whole
  contraction in one product, so there is no accumulator — and a tile past
  ``live`` stores zeros and names the blocks already in VMEM (no DMA).
  Row tiles are the inner axis: an expert's block stays resident while its
  tiles go by.  With ``transpose`` the block is (tn, K) of a (G, N, K)
  stack and the product contracts its second axis: ``dy W^T``, the
  gradient for the rows, from the same kernel.
- ``moe_tgmm``: grid (K tiles, N tiles, row tiles), for the weights'
  gradient ``rows_g^T dy_g``: a float32 (tk, tn) scratch accumulates over a
  group's consecutive row tiles, opened at its first and stored at its
  last.  Groups without a row are never visited; the caller zeroes them.

XLA has its own form, ``lax.ragged_dot``, which the TPU compiler lowers
to grouped kernels of its own; they reach the trace as ``ragged-dot-none``
with no program scope (so no span can read them) and visit a 512-row tile
once for every group that touches it.  It stays the path of the CPU and of
every shape ``supported`` refuses: one path, chosen from backend, shapes
and dtype.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributeddataparallel_tpu.observability import cost_model, scopes

#: rows of a tile, and what a group's start is aligned to on the kernels' path
ROW_TILE = 256
#: the alignment off that path: what the CPU tests walk holes with
PLAIN_TILE = 8


class Layout(NamedTuple):
    """Where the groups lie in a buffer of ``tile``-row tiles
    (``ops.moe.group_layout``)."""

    padded: jax.Array      # (G,) rows of each group, rounded up to the tile
    tile_group: jax.Array  # (tiles,) the expert a row tile belongs to
    live: jax.Array        # () tiles that hold rows; the rest are nought
    tile: int


def _col_tile(n: int) -> int | None:
    for t in (512, 256, 128):
        if n % t == 0:
            return t
    return None


def supported(rows, weights) -> bool:
    """True where the kernels can run: a TPU, bf16 or f32 operands, both
    weight axes multiples of 128."""
    if jax.default_backend() != "tpu":
        return False
    _, k, n = weights.shape
    return (
        rows.dtype == weights.dtype
        and rows.dtype in (jnp.bfloat16, jnp.float32)
        and _col_tile(k) is not None and _col_tile(n) is not None
    )


def row_tile(rows, weights) -> int:
    """The alignment ``ops.moe.group_layout`` gives the groups for these
    operands."""
    return ROW_TILE if supported(rows, weights) else PLAIN_TILE


def _here(t, live):
    """The row tile a grid step names: a step past the live tiles names the
    last of them again, so it moves nothing."""
    return jnp.maximum(jnp.minimum(t, live[0] - 1), 0)


# ---------------------------------------------------------------------------
# rows x weights[g] (and dy x weights[g]^T)
# ---------------------------------------------------------------------------

def _gmm_kernel(tile_group, live, rows_ref, w_ref, out_ref, *, transpose: bool):
    t = pl.program_id(1)

    @pl.when(t < live[0])
    def _live():
        contract = (((1,), (1,)), ((), ())) if transpose else (((1,), (0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            rows_ref[...], w_ref[0], contract,
            preferred_element_type=jnp.float32,
        ).astype(out_ref.dtype)

    @pl.when(t >= live[0])
    def _dead():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("transpose", "interpret"))
def _gmm_launch(rows, weights, tile_group, live, *, transpose: bool,
                interpret: bool):
    """One ``pallas_call``; jitted on its own so that the expert layers
    share one trace and one lowering of each shape."""
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows.shape
    n = weights.shape[1] if transpose else weights.shape[2]
    tn = _col_tile(n)
    tiles = m // ROW_TILE

    w_block = (1, tn, k) if transpose else (1, k, tn)

    def w_index(j, t, tile_group, live):
        g = tile_group[_here(t, live)]
        return (g, j, 0) if transpose else (g, 0, j)

    cost = cost_model.moe_cost(m, k, n, weights.shape[0])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose=transpose),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, tiles),
            in_specs=[
                pl.BlockSpec((ROW_TILE, k),
                             lambda j, t, tg, live: (_here(t, live), 0)),
                pl.BlockSpec(w_block, w_index),
            ],
            out_specs=pl.BlockSpec((ROW_TILE, tn), lambda j, t, tg, live: (t, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
        cost_estimate=pl.CostEstimate(  # one of the layer's nine products
            flops=cost["flops"] // 9, transcendentals=0,
            bytes_accessed=(rows.size + weights.size + m * n) * rows.dtype.itemsize,
        ),
        interpret=interpret,
        name=scopes.MOE_GMM,
    )(tile_group, live.reshape(1), rows, weights)


# ---------------------------------------------------------------------------
# rows_g^T x dy_g
# ---------------------------------------------------------------------------

def _tgmm_kernel(tile_group, live, rows_ref, dy_ref, out_ref, acc_ref):
    t = pl.program_id(2)
    last = live[0] - 1
    g = tile_group[jnp.minimum(t, jnp.maximum(last, 0))]
    opens = (t == 0) | (tile_group[jnp.maximum(t - 1, 0)] != g)
    closes = (t == last) | (
        tile_group[jnp.minimum(t + 1, tile_group.shape[0] - 1)] != g
    )

    @pl.when(t <= last)
    def _live():
        @pl.when(opens)
        def _open():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jax.lax.dot_general(
            rows_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

        @pl.when(closes)
        def _close():
            out_ref[0] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("groups", "interpret"))
def _tgmm_launch(rows, dy, tile_group, live, *, groups: int, interpret: bool):
    from jax.experimental.pallas import tpu as pltpu

    m, k = rows.shape
    n = dy.shape[1]
    tk = _col_tile(k)
    tn = 1024 if n % 1024 == 0 else _col_tile(n)
    tiles = m // ROW_TILE

    cost = cost_model.moe_cost(m, k, n, groups)
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // tk, n // tn, tiles),
            in_specs=[
                pl.BlockSpec((ROW_TILE, tk),
                             lambda i, j, t, tg, live: (_here(t, live), i)),
                pl.BlockSpec((ROW_TILE, tn),
                             lambda i, j, t, tg, live: (_here(t, live), j)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn),
                lambda i, j, t, tg, live: (tg[_here(t, live)], i, j),
            ),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), rows.dtype),
        cost_estimate=pl.CostEstimate(
            flops=cost["flops"] // 9, transcendentals=0,
            bytes_accessed=(rows.size + dy.size + groups * k * n)
            * rows.dtype.itemsize,
        ),
        interpret=interpret,
        name=scopes.MOE_TGMM,
    )(tile_group, live.reshape(1), rows, dy)


# ---------------------------------------------------------------------------
# The op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gmm(rows, weights, tile_group, live, interpret):
    return _gmm_launch(rows, weights, tile_group, live, transpose=False,
                       interpret=interpret)


def _gmm_fwd(rows, weights, tile_group, live, interpret):
    return _gmm(rows, weights, tile_group, live, interpret), (
        rows, weights, tile_group, live)


def _gmm_bwd(interpret, res, dy):
    rows, weights, tile_group, live = res
    groups = weights.shape[0]
    d_rows = _gmm_launch(dy, weights, tile_group, live, transpose=True,
                         interpret=interpret)
    d_w = _tgmm_launch(rows, dy, tile_group, live, groups=groups,
                       interpret=interpret)
    # a group without a row was never visited: its block holds no result
    seen = jnp.zeros((groups,), jnp.int32).at[tile_group].add(
        (jnp.arange(tile_group.shape[0]) < live).astype(jnp.int32)
    ) > 0
    return d_rows, jnp.where(seen[:, None, None], d_w, 0), None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(rows, weights, layout: Layout, *, _interpret: bool = False):
    """``rows`` (M, k) times ``weights`` (G, k, n), each row tile by the
    expert ``layout`` gives it; rows past the groups come out nought.
    The kernels where ``layout.tile`` is theirs, else ``lax.ragged_dot``
    over the padded groups.  ``_interpret`` is the CPU tests' way into the
    kernels."""
    if layout.tile == ROW_TILE and (_interpret or supported(rows, weights)):
        return _gmm(rows, weights, layout.tile_group, layout.live, _interpret)
    return jax.lax.ragged_dot(
        rows, weights, layout.padded, preferred_element_type=rows.dtype
    )
