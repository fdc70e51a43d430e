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
state stay f32.  Plain ``jax.numpy``, differentiated by JAX — the
supported baseline; a kernel would replace this function and nothing
round it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


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


def ssd_chunked(x, dt, A, B, C, D=None, *, chunk: int):
    """Chunked scan.  ``x`` (b, s, h, p) in the compute dtype; ``dt``
    (b, s, h) f32, after softplus; ``A`` (h,) f32, negative; ``B``, ``C``
    (b, s, g, n) with ``h`` a multiple of ``g`` (a group's heads share
    them); ``D`` (h,) the skip, or None.  Returns ``y`` (b, s, h, p) in
    ``x``'s dtype.  ``s`` need not divide by ``chunk``: the tail is
    padded with steps of ``dt`` = 0, which neither decay nor add."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    if h % g:
        raise ValueError(f"heads {h} not a multiple of groups {g}")
    L = min(chunk, s)
    pad = -s % L
    if pad:
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (x, dt, B, C)
        )
    c = (s + pad) // L
    r = h // g
    dtype = x.dtype
    f32 = jnp.float32
    xc = x.reshape(b, c, L, g, r, p)
    Bc = B.reshape(b, c, L, g, n).astype(dtype)
    Cc = C.reshape(b, c, L, g, n).astype(dtype)
    # (b, c, g, r, L): a head's steps lie along the last axis
    dtc = jnp.moveaxis(dt.astype(f32).reshape(b, c, L, g, r), 2, -1)
    cum = jnp.cumsum(dtc * A.astype(f32).reshape(g, r, 1), axis=-1)

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

    # 2. what each chunk leaves behind at its end
    to_end = jnp.exp(cum[..., -1:] - cum) * dtc          # (b, c, g, r, j)
    weighted = xc * jnp.moveaxis(to_end, -1, 2)[..., None].astype(dtype)
    states = jnp.einsum(
        "bcjgn,bcjgrp->bcgrpn", Bc, weighted, preferred_element_type=f32
    )

    # 3. carried chunk to chunk, and 4. read by every step of the next
    h_in = _carry_states(states, jnp.exp(cum[..., -1]))
    from_start = jnp.moveaxis(jnp.exp(cum), -1, 2)[..., None]
    y = y + from_start * jnp.einsum(
        "bcign,bcgrpn->bcigrp", Cc, h_in.astype(dtype),
        preferred_element_type=f32,
    )

    y = y.reshape(b, s + pad, h, p)[:, :s]
    if D is not None:
        y = y + D.astype(f32)[:, None] * x[:, :s].astype(f32)
    return y.astype(dtype)

