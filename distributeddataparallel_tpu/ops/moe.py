"""Token-choice MoE dispatch primitives (GShard / Switch convention).

The dense-einsum MoE path (``models.transformer.MoEMLP``) pushes every
token through every local expert — correct and MXU-friendly at tiny E,
but FLOPs scale with E instead of K.  This module supplies the
token-choice alternative: each token is materialised in at most K expert
slots bounded by a per-expert ``capacity``, so expert FLOPs stay
~``K * T`` regardless of E (the property expert parallelism exists for;
reference stake: SURVEY.md §2c EP build scope).

Convention (Lepikhin et al. arXiv 2006.16668 / Fedus et al. 2101.03961):

- ``capacity = ceil(K * T / E * capacity_factor)`` slots per expert.
- Priority is token order: when an expert overflows, LATER tokens drop
  (their MoE contribution is zero — the residual connection carries
  them through, "dropped-through-residual").
- Dispatch/combine here is SORT-based, not the quadratic ``(T, E, C)``
  one-hot einsum of the original GShard: an ``argsort`` by expert id
  plus two O(T*K) gathers/scatters.  On TPU the einsum costs
  ``T * (K*T) * d`` MXU FLOPs (quadratic in T — it dwarfs the expert
  compute it feeds at training sequence lengths) while the sort path is
  a VPU-side reshuffle linear in T*K.

The dropless form (``sort_by_expert`` / ``dropless``) is for a layer that
holds a share ``[first, first + count)`` of the router's experts: every
(token, choice) whose expert is held is computed — no capacity, no drop —
by a sort, grouped products over the held experts' row groups
(``ops.grouped_matmul``, which walks live row tiles only) and a weighted
scatter-add back.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from distributeddataparallel_tpu.observability import scopes


def moe_capacity(num_tokens: int, num_experts: int, top_k: int,
                 capacity_factor: float) -> int:
    """Static per-expert slot count for a (sub-)batch of ``num_tokens``."""
    import math

    return max(1, math.ceil(top_k * num_tokens / num_experts
                            * capacity_factor))


def token_choice_slots(idx, gates, num_experts: int, capacity: int):
    """Assign each (token, k) routing pair to an expert slot.

    idx: (T, K) int32 expert choices; gates: (T, K) combine weights.
    Returns ``(tok_for_slot, gate_for_slot)`` of shape (E*C,): slot
    ``e*C + p`` holds the token id routed to expert ``e`` at position
    ``p`` and its gate.  Empty / overflowed slots keep gate 0 (token id
    0 — harmless: the combine multiplies by the gate), so no separate
    validity mask is needed and no spurious gradient flows.

    Differentiable in ``gates`` (gather + scatter-set); ``idx`` is
    integer routing, no gradient path by construction.
    """
    T, K = idx.shape
    E, C = num_experts, capacity
    flat_e = idx.reshape(-1)  # token-major: (t0 k0, t0 k1, t1 k0, ...)
    flat_g = gates.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)
    # Stable sort by expert id keeps token order within each expert —
    # that ordering IS the drop priority.
    order = jnp.argsort(flat_e)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]]
    )
    pos = jnp.arange(T * K, dtype=jnp.int32) - starts[se]
    # Overflow -> sentinel index E*C, dropped by the scatters below.
    slot = jnp.where(pos < C, se * C + pos, E * C)
    tok_for_slot = (
        jnp.zeros((E * C,), jnp.int32).at[slot].set(st, mode="drop")
    )
    gate_for_slot = (
        jnp.zeros((E * C,), flat_g.dtype).at[slot].set(sg, mode="drop")
    )
    return tok_for_slot, gate_for_slot


def dispatch(xt, tok_for_slot):
    """Gather tokens into the slot buffer: (T, d) -> (E*C, d).

    Empty slots gather token 0; their gate is 0 so neither the forward
    combine nor any backward cotangent sees the duplicate.
    """
    return jnp.take(xt, tok_for_slot, axis=0)


def combine(y_flat, tok_for_slot, gate_for_slot, num_tokens: int):
    """Scatter-add expert outputs back to token positions with gates.

    y_flat: (E*C, d) expert outputs in slot order.  Returns (T, d);
    dropped tokens receive zero (the caller's residual carries them).
    """
    weighted = y_flat * gate_for_slot[:, None].astype(y_flat.dtype)
    out = jnp.zeros((num_tokens, y_flat.shape[-1]), y_flat.dtype)
    return out.at[tok_for_slot].add(weighted)


# --- dropless: a held share of the experts, no capacity -------------------

def sort_by_expert(idx, first: int, count: int):
    """Sort the T*K routing choices by expert, the ones this layer does
    not hold last.  idx: (T, K) int32 choices over all experts.  Returns
    ``(order, sizes)``: ``order`` (T*K,) the choices' flat positions
    (``t * K + k``) in sorted order — stable, so token order is kept
    inside an expert — and ``sizes`` (count,) int32, the rows each held
    expert received; the first ``sizes.sum()`` of ``order`` are the held
    ones."""
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.zeros((count + 1,), jnp.int32).at[key].add(1)[:count]
    return order, sizes


def group_layout(sizes, tile: int, rows: int):
    """Lay ``sizes`` (G,) row groups out in a buffer of ``rows`` rows
    (a multiple of ``tile``, and at least ``sizes.sum() + G * tile``), each
    group starting at a multiple of ``tile``.  Returns ``(layout, rank)``:
    the ``grouped_matmul.Layout`` and, for every buffer row, its rank among
    the sorted choices (``sort_by_expert``'s order), -1 in a hole."""
    from distributeddataparallel_tpu.ops.grouped_matmul import Layout

    groups = sizes.shape[0]
    padded = -(-sizes // tile) * tile
    ends, starts = jnp.cumsum(padded), jnp.cumsum(sizes) - sizes
    row = jnp.arange(rows, dtype=jnp.int32)
    g = jnp.searchsorted(ends, row, side="right").astype(jnp.int32)
    inside = jnp.minimum(g, groups - 1)
    offset = row - (ends - padded)[inside]
    rank = jnp.where(
        (g < groups) & (offset < sizes[inside]), starts[inside] + offset, -1
    )
    layout = Layout(
        padded, inside[::tile], (ends[-1] // tile).astype(jnp.int32), tile
    )
    return layout, rank


def dropless_bound(num_choices: int, num_experts: int, count: int) -> int:
    """Choices the buffer of the usual step has room for: twice the mean
    load of a share of ``count`` experts (a multiple of 8), or all
    ``num_choices`` where that is no less."""
    twice = -(-2 * num_choices * count // num_experts)
    return min(num_choices, -(-twice // 8) * 8)


def dropless(xt, gates, idx, num_experts: int, first: int, count: int,
             expert_fn, tile: int):
    """The held experts' part of the layer's result, (T, d): for every
    (token, choice) whose expert lies in ``[first, first + count)``,
    ``gate * expert(x)``, summed per token.  ``expert_fn(rows, layout)``
    maps the buffer's rows (M, d) to (M, d), each row tile by its group
    (``grouped_matmul``); groups start at multiples of ``tile`` and the
    holes between them are zero rows.  Also returns ``sizes``, the rows
    each held expert received.

    The buffer has room for ``dropless_bound`` choices where the held ones
    fit — a step's usual case, decided on the device by their count — and
    for all T*K otherwise: nothing is ever dropped.  Differentiable in
    ``xt``, ``gates`` and what ``expert_fn`` closes over; ``idx`` is
    integer routing."""
    T, K = idx.shape
    with jax.named_scope(scopes.MOE_DISPATCH):
        order, sizes = sort_by_expert(idx, first, count)
        live = jnp.sum(sizes)

    def at(bound: int):
        rows = -(-bound // tile) * tile + count * tile

        def run(xt, gates, order, sizes):
            with jax.named_scope(scopes.MOE_DISPATCH):
                layout, rank = group_layout(sizes, tile, rows)
                choice = jnp.take(order, jnp.maximum(rank, 0))
                # a hole reads past the tokens: a zero row, no gate
                tok = jnp.where(rank >= 0, choice // K, T)
                x_rows = jnp.take(xt, tok, axis=0, mode="fill", fill_value=0)
            with jax.named_scope(scopes.MOE_EXPERTS):
                y = expert_fn(x_rows, layout)
            with jax.named_scope(scopes.MOE_COMBINE):
                g = jnp.where(rank >= 0, jnp.take(gates.reshape(-1), choice), 0.0)
                out = jnp.zeros((T, y.shape[-1]), y.dtype)
                return out.at[tok].add(
                    y * g[:, None].astype(y.dtype), mode="drop"
                )
        return run

    usual = dropless_bound(T * K, num_experts, count)
    args = (xt, gates, order, sizes)
    if usual == T * K:
        return at(usual)(*args), sizes
    # the worst case keeps nothing for its backward but its arguments: a
    # cond's branches hand back each other's residuals, zero-filled, and
    # the usual step would fill the worst case's every time
    worst = jax.checkpoint(at(T * K))
    return jax.lax.cond(live <= usual, at(usual), worst, *args), sizes
