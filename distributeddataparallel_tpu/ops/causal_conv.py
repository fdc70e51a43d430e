"""The Mamba-2 mixer's causal depthwise convolution, bias and SiLU as one
pass over HBM each way.

For every channel, with ``K`` taps and ``K - 1`` zeros to the left::

    pre_t = bias + sum_k taps[k] * xbc[t - (K - 1 - k)]
    y_t   = silu(pre_t)

Operands are upcast to f32, taps, bias, the sum and the SiLU stay f32 and
``y`` is rounded once, to ``xbc``'s dtype, at the store; ``d taps`` and
``d bias`` are summed in f32.

**What is a kernel.**  One Pallas forward kernel (``conv_fwd``) and one
backward kernel (``conv_bwd``) under a ``jax.custom_vjp`` (``_conv``)
whose residuals are its inputs: the backward recomputes ``pre`` and leaves
``d xbc``, ``d taps`` and ``d bias`` in one launch.  Both hold channels
along sublanes and steps along lanes, (channels, seq): that is how the
compiled step holds the mixer's activations (``ops/ssd.py``'s docstring),
so the logical transposes round ``_conv`` cost nothing.  A grid step owns
one batch row and a block of channels with the row's whole sequence — no
halo between grid steps — and walks it in chunks of lanes small enough for
the vector registers; a shift of ``d`` steps is a lane rotate of the chunk
with the 128 lanes before it (after it, in the backward) attached.

**No copies round it.**  XLA fuses a slice into a fusion, not into a
custom call.  So ``causal_conv_silu`` takes the array that *holds* ``xbc``
(the mixer's input projection, whole) and the channel ``xbc`` starts at,
and the block index map starts there; and it hands back ``x``, ``B`` and
``C`` as the three arrays the scan takes, each written by the grid steps
that own its channels.

**Which shapes take which path.**  ``causal_conv_silu`` picks from what
it is given (``supported``): the kernels on a TPU backend where the
sequence is whole 128-lane tiles, ``xbc``'s start and every split are
whole 128-channel blocks and a block of rows fits the kernels' VMEM, in
bf16 or f32; anything else — the CPU, a sequence of 36, a state of 16 —
takes ``_plain``, the ``jax.numpy`` form differentiated by JAX, which is
also what the tests hold the kernels against (``_interpret=True`` runs
them on the CPU, at any shape).
"""

from __future__ import annotations

import functools
import math
import operator
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from distributeddataparallel_tpu.observability import scopes

_LANES = 128
#: lanes of a row group computed at a time.  Every shift rotates the chunk
#: with its 128-lane halo, so a longer chunk rotates less for nothing, and a
#: longer one still spills more registers: on the chip, stand-alone at
#: (2, 4352, 4096) bf16, the forward program and the rest of a forward-and-
#: backward one took 0.288 + 1.076 ms at 512, 0.291 + 0.888 at 1024, 0.310 +
#: 0.818 at 2048 and 0.335 + 0.810 at 4096 (PERF.md section 6, PR 32)
_CHUNK = 2048
#: rows of a block computed at a time: one packed bf16 sublane tile
_GROUP = 16
#: what both kernels' double-buffered blocks may take of the 16 MiB of
#: VMEM a kernel is given
_VMEM_BLOCKS = 12 * 2 ** 20


def _plain(xbc, taps, bias):
    """The convolution in ``jax.numpy``, (b, s, c) -> (b, s, c): the path of
    every shape the kernels do not take, and the tests' second opinion."""
    K, S = taps.shape[0], xbc.shape[1]
    # tap k reads the step K - 1 - k back: K - 1 zeros to the left
    padded = jnp.pad(xbc.astype(jnp.float32), ((0, 0), (K - 1, 0), (0, 0)))
    return jax.nn.silu(bias + sum(
        taps[k] * padded[:, k:k + S] for k in range(K)
    )).astype(xbc.dtype)


# ------------------------------------------------------------ the tile plan

class Plan(NamedTuple):
    """The kernels' blocks, from shapes alone (``_plan``)."""

    rows: int    # channels a grid step owns
    group: int   # of them, computed at a time
    chunk: int   # lanes computed at a time
    halo: int    # lanes attached to a chunk for the shifts
    fold: int    # lanes the sums for d taps and d bias are kept in


def _rows(seq: int, itemsize: int, parts: int) -> int:
    """The largest block of rows whose buffers fit: the backward holds
    ``xbc``, ``dy`` once for each part and ``d xbc``, each twice."""
    for rows in (128, 64, 32, 16):
        if 2 * (parts + 2) * rows * seq * itemsize <= _VMEM_BLOCKS:
            return rows
    return 0


def _plan(seq: int, itemsize: int, taps: int, start: int, splits) -> Plan:
    """Whole 128-lane tiles where the sequence has them, walked in chunks;
    else (the small shapes of the CPU tests) the sequence whole.  Rows:
    the block every part and the start are multiples of."""
    rows = math.gcd(start, *splits)
    if rows % _LANES == 0:
        rows = _rows(seq, itemsize, len(splits)) or _LANES
    group = _GROUP if rows % _GROUP == 0 else rows
    if seq % _LANES:
        return Plan(rows, group, seq, taps - 1, seq)
    return Plan(rows, group, min(_CHUNK, seq), _LANES, _LANES)


def supported(xbc, taps, splits, start: int = 0) -> bool:
    """True where the kernels run natively: a TPU backend, a sequence of
    whole lane tiles, ``start`` and every part whole 128-channel blocks, a
    row block whose whole sequence fits the kernels' VMEM, taps that reach
    no further back than one lane tile, bf16 or f32.  ``xbc`` (b, s, ·)
    holds the convolution's input from channel ``start``; only shapes and
    dtypes are read."""
    if jax.default_backend() != "tpu":
        return False
    seq = xbc.shape[1]
    return (
        xbc.dtype in (jnp.bfloat16, jnp.float32)
        and seq % _LANES == 0
        and taps.shape[0] - 1 <= _LANES
        and all(n % _LANES == 0 for n in (start, *splits))
        and _rows(seq, xbc.dtype.itemsize, len(splits)) > 0
    )


# -------------------------------------------------------------- the kernels
#
# Both kernels see ``coef`` (rows, K + 1) f32: a channel's taps and, last,
# its bias, along lanes, so that a column of it broadcasts over the steps.

def _add(terms):
    """Their sum, without ``sum``'s leading ``0 +`` (an addition a vector
    register that the compiler may not drop: -0.0 + 0 is +0.0)."""
    return functools.reduce(operator.add, terms)


def _chunks(seq: int, chunk: int):
    return [(c0, min(chunk, seq - c0)) for c0 in range(0, seq, chunk)]


def _window(x_ref, rows, c0: int, w: int, halo: int):
    """Steps ``c0 - halo .. c0 + w`` of ``rows`` in f32: the chunk with the
    lanes before it, zeros before the first step."""
    cur = x_ref[0, rows, c0:c0 + w]
    before = (jnp.zeros((cur.shape[0], halo), cur.dtype) if c0 == 0
              else x_ref[0, rows, c0 - halo:c0])
    return jnp.concatenate([before, cur], axis=1).astype(jnp.float32)


def _back(win, d: int, halo: int):
    """``win``'s chunk read ``d`` steps back."""
    from jax.experimental.pallas import tpu as pltpu

    return (pltpu.roll(win, d, 1) if d else win)[:, halo:]


def _coef(coef_ref, rows, K: int):
    """The rows' taps and, last, bias: K + 1 columns, (group, 1) each."""
    return [coef_ref[rows, k:k + 1] for k in range(K + 1)]


def _pre(coef, win, halo: int):
    """``(pre, [the chunk as tap k reads it])`` for one window."""
    K = len(coef) - 1
    read = [_back(win, K - 1 - k, halo) for k in range(K)]
    return coef[K] + _add(coef[k] * read[k] for k in range(K)), read


def _owner(blocks, body):
    """Run ``body(part)`` for the part whose channels this grid step owns:
    ``blocks`` are the parts' (first block, blocks)."""
    j = pl.program_id(1)
    for part, (lo, n) in enumerate(blocks):
        pl.when((j >= lo) & (j < lo + n))(functools.partial(body, part))


def _fwd_kernel(x_ref, coef_ref, *y_refs, plan: Plan, K: int, blocks):
    """One batch row, ``plan.rows`` channels, every step: ``y`` into the
    part that owns the channels."""
    seq = x_ref.shape[2]

    def block(part):
        def group(r, _):
            rows = pl.ds(pl.multiple_of(r * plan.group, plan.group), plan.group)
            coef = _coef(coef_ref, rows, K)
            for c0, w in _chunks(seq, plan.chunk):
                win = _window(x_ref, rows, c0, w, plan.halo)
                pre, _ = _pre(coef, win, plan.halo)
                y_refs[part][0, rows, c0:c0 + w] = (
                    pre * jax.nn.sigmoid(pre)
                ).astype(y_refs[part].dtype)

        jax.lax.fori_loop(0, plan.rows // plan.group, group, None)

    _owner(blocks, block)


def _bwd_kernel(x_ref, coef_ref, *refs, plan: Plan, K: int, blocks):
    """The backward of ``_fwd_kernel``'s program, ``pre`` recomputed: with
    ``g = dy * silu'(pre)``,

        d xbc_t  = sum_k taps[k] * g[t + (K - 1 - k)]
        d taps_k = sum_t g_t * xbc[t - (K - 1 - k)],   d bias = sum_t g_t

    The chunks are walked from the last step to the first, so the ``g``
    that ``d xbc`` reads ahead of a chunk is the one just made.  The sums
    are kept ``plan.fold`` lanes wide and reduced along lanes once a row
    group."""
    from jax.experimental.pallas import tpu as pltpu

    dy_refs, (dx_ref, dcoef_ref) = refs[:len(blocks)], refs[len(blocks):]
    seq = x_ref.shape[2]
    f32 = jnp.float32
    halo, F = plan.halo, plan.fold

    def fold(a):  # (rows, w) -> (rows, F): lane tiles added up
        return _add(a[:, i:i + F] for i in range(0, a.shape[1], F))

    def block(part):
        def group(r, _):
            rows = pl.ds(pl.multiple_of(r * plan.group, plan.group), plan.group)
            coef = _coef(coef_ref, rows, K)
            ahead = jnp.zeros((plan.group, halo), f32)
            sums = [jnp.zeros((plan.group, F), f32) for _ in range(K + 1)]
            for c0, w in reversed(_chunks(seq, plan.chunk)):
                win = _window(x_ref, rows, c0, w, halo)
                pre, read = _pre(coef, win, halo)
                sig = jax.nn.sigmoid(pre)
                g = dy_refs[part][0, rows, c0:c0 + w].astype(f32) * (
                    sig * (1.0 + pre * (1.0 - sig))
                )
                for k in range(K):
                    sums[k] += fold(g * read[k])
                sums[K] += fold(g)
                ext = jnp.concatenate([g, ahead], axis=1)
                dx = _add(
                    coef[k] * (
                        pltpu.roll(ext, w + halo - (K - 1 - k), 1)[:, :w]
                        if k < K - 1 else g
                    )
                    for k in range(K)
                )
                dx_ref[0, rows, c0:c0 + w] = dx.astype(dx_ref.dtype)
                ahead = g[:, :halo]
            for k in range(K + 1):
                dcoef_ref[0, rows, k:k + 1] = jnp.sum(
                    sums[k], axis=1, keepdims=True
                )

        jax.lax.fori_loop(0, plan.rows // plan.group, group, None)

    _owner(blocks, block)


# ------------------------------------------------------------- the launches

class _Launch(NamedTuple):
    """What both launches make of their operands' shapes."""

    plan: Plan
    grid: tuple
    blocks: tuple    # every part's (first block, blocks) along the grid
    coef: object     # (c, K + 1) f32
    held: object     # BlockSpec of the array that holds xbc
    own: object      # BlockSpec of an array of xbc's own channels
    coefs: object    # BlockSpec of coef
    parts: list      # BlockSpecs of the parts
    cost: dict


def _launch(held, taps, bias, start: int, splits) -> _Launch:
    b, _, seq = held.shape
    K, c = taps.shape
    plan = _plan(seq, held.dtype.itemsize, K, start, splits)
    R = plan.rows
    first = start // R
    blocks, lo = [], 0
    for n in splits:
        blocks.append((lo, n // R))
        lo += n // R

    def part(lo, n):
        # a part's block stays the part's nearest while other parts' grid
        # steps run: it is written back once, after its own last step
        return pl.BlockSpec(
            (1, R, seq), lambda i, j: (i, jnp.clip(j - lo, 0, n - 1), 0)
        )

    elements = b * c * seq
    return _Launch(
        plan, (b, c // R), tuple(blocks),
        jnp.concatenate([taps.T, bias[:, None]], axis=1).astype(jnp.float32),
        pl.BlockSpec((1, R, seq), lambda i, j: (i, first + j, 0)),
        pl.BlockSpec((1, R, seq), lambda i, j: (i, j, 0)),
        pl.BlockSpec((R, K + 1), lambda i, j: (j, 0)),
        [part(lo, n) for lo, n in blocks],
        dict(flops=(2 * K + 4) * elements, transcendentals=elements,
             bytes_accessed=2 * elements * held.dtype.itemsize),
    )


@functools.partial(
    jax.jit, static_argnames=("start", "splits", "interpret")
)
def _fwd_launch(held, taps, bias, *, start: int, splits, interpret: bool):
    """The forward's one ``pallas_call``.  Jitted on its own so that a
    model's mamba layers share one trace and one lowering of the kernel
    (``ssd._fwd_launch``'s reason)."""
    z = _launch(held, taps, bias, start, splits)
    b, _, seq = held.shape
    return pl.pallas_call(
        functools.partial(
            _fwd_kernel, plan=z.plan, K=taps.shape[0], blocks=z.blocks
        ),
        grid=z.grid,
        in_specs=[z.held, z.coefs],
        out_specs=z.parts,
        out_shape=[
            jax.ShapeDtypeStruct((b, n, seq), held.dtype) for n in splits
        ],
        cost_estimate=pl.CostEstimate(**z.cost),
        interpret=interpret,
        name=scopes.CONV_FWD,
    )(held, z.coef)


@functools.partial(
    jax.jit, static_argnames=("start", "splits", "interpret")
)
def _bwd_launch(held, taps, bias, dys, *, start: int, splits,
                interpret: bool):
    """The backward's one ``pallas_call``: ``d xbc`` (b, c, S), ``d taps``
    and ``d bias`` (a batch row's sums are added up here)."""
    z = _launch(held, taps, bias, start, splits)
    b, _, seq = held.shape
    K, c = taps.shape
    cost = dict(z.cost, flops=2 * z.cost["flops"],
                bytes_accessed=3 * z.cost["bytes_accessed"] // 2)
    dx, dcoef = pl.pallas_call(
        functools.partial(_bwd_kernel, plan=z.plan, K=K, blocks=z.blocks),
        grid=z.grid,
        in_specs=[z.held, z.coefs, *z.parts],
        out_specs=[
            z.own,
            pl.BlockSpec((1, z.plan.rows, K + 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, c, seq), held.dtype),
            jax.ShapeDtypeStruct((b, c, K + 1), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(**cost),
        interpret=interpret,
        name=scopes.CONV_BWD,
    )(held, z.coef, *dys)
    dcoef = dcoef.sum(0)
    return dx, dcoef[:, :K].T, dcoef[:, K]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv(held, taps, bias, start: int, splits: tuple, interpret: bool):
    """The kernels, sequence minor: ``held`` (b, ·, S) holds ``xbc`` from
    channel ``start``; ``taps`` (K, c) f32, ``bias`` (c,) f32.  Returns
    ``silu(conv(xbc))`` as its parts, a (b, n, S) array for each ``n`` of
    ``splits``."""
    return tuple(_fwd_launch(held, taps, bias, start=start, splits=splits,
                             interpret=interpret))


def _conv_fwd(held, taps, bias, start, splits, interpret):
    return _conv(held, taps, bias, start, splits, interpret), (held, taps, bias)


def _conv_bwd(start, splits, interpret, res, dys):
    held, taps, _ = res
    dx, dtaps, dbias = _bwd_launch(
        *res, dys, start=start, splits=splits, interpret=interpret
    )
    after = held.shape[1] - start - taps.shape[1]
    return jnp.pad(dx, ((0, 0), (start, after), (0, 0))), dtaps, dbias


_conv.defvjp(_conv_fwd, _conv_bwd)


# ---------------------------------------------------------------- the entry

def causal_conv_silu(xbc, taps, bias, splits, *, start: int = 0,
                     _interpret: bool = False):
    """``silu(bias + causal depthwise conv(xbc))``, split.  ``xbc``
    (b, s, ·) in the compute dtype holds the convolution's input in its
    channels ``start .. start + c`` (a caller whose ``xbc`` is a slice of a
    wider array hands that array whole: the kernels then read the slice in
    place, where a sliced operand would be copied first); ``taps`` (K, c)
    f32, tap ``k`` reading the step ``K - 1 - k`` back; ``bias`` (c,) f32;
    ``splits`` the parts' widths, which add up to ``c``.  Returns the parts,
    (b, s, n) each, in ``xbc``'s dtype.

    Runs as kernels where ``supported`` says so, else in the plain form;
    ``_interpret`` is the CPU tests' way into the kernels."""
    c = taps.shape[1]
    splits = tuple(splits)
    if sum(splits) != c or bias.shape != (c,) or start + c > xbc.shape[2]:
        raise ValueError(
            f"taps {taps.shape}, bias {bias.shape} and parts {splits} do not "
            f"describe channels {start}.. of {xbc.shape}"
        )
    if _interpret or supported(xbc, taps, splits, start):
        parts = _conv(jnp.swapaxes(xbc, 1, 2), taps, bias, start, splits,
                      _interpret)
        return tuple(jnp.swapaxes(p, 1, 2) for p in parts)
    y = _plain(xbc[..., start:start + c], taps, bias)
    bounds = [sum(splits[:i]) for i in range(1, len(splits))]
    return tuple(jnp.split(y, bounds, axis=-1))
