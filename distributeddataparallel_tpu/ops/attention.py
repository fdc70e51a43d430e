"""Attention ops: causal multi-head / grouped-query attention + RoPE.

The reference has no attention model at all (image classifier only,
ref dpp.py:11-18); these ops exist for the BASELINE LM configs (GPT-2 124M,
Llama-3 8B — configs 4-5) and for the long-context path
(``parallel.context_parallel`` ring attention reuses the same blockwise
math).

TPU-first design notes:

- All matmuls are batched ``einsum``s that XLA tiles onto the MXU; softmax
  and scaling fuse into the surrounding HLO.
- Logits are computed in float32 even under bf16 activations (softmax
  stability on the VPU), then cast back for the value matmul.
- The causal mask is built with ``iota`` comparisons — no materialized
  (S, S) boolean from Python, so the same code works under any jit/scan.
- ``attention()`` dispatches between this XLA reference implementation and
  the Pallas flash kernel (``ops.pallas_attention``) via ``impl=``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30  # softmax-safe -inf that survives bf16 casts


def rope_frequencies(
    head_dim: int, max_len: int, *, theta: float = 10000.0
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute RoPE cos/sin tables of shape (max_len, head_dim // 2)."""
    inv_freq = 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # (max_len, head_dim/2)
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(
    x: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    *,
    positions: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Rotate query/key halves by position-dependent angles.

    x: (B, S, H, D); cos/sin: (max_len, D/2); positions: (S,) or (B, S)
    int positions into the tables (defaults to arange(S) — pass explicit
    positions for sequence-parallel shards, where the local chunk starts at
    a nonzero offset).
    """
    B, S, H, D = x.shape
    if positions is None:
        positions = jnp.arange(S)
    c = cos[positions]  # (..., S, D/2)
    s = sin[positions]
    if c.ndim == 2:  # (S, D/2) -> broadcast over batch
        c = c[None]
        s = s[None]
    c = c[:, :, None, :]  # (B|1, S, 1, D/2)
    s = s[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    rotated = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return rotated.astype(x.dtype)


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand KV heads for grouped-query attention: (B,S,Hkv,D) -> (B,S,Hkv*n,D)."""
    if n_rep == 1:
        return x
    B, S, H, D = x.shape
    return jnp.broadcast_to(
        x[:, :, :, None, :], (B, S, H, n_rep, D)
    ).reshape(B, S, H * n_rep, D)


def causal_mask_bias(
    q_len: int,
    kv_len: int,
    *,
    q_offset: jnp.ndarray | int = 0,
    kv_offset: jnp.ndarray | int = 0,
    dtype=jnp.float32,
    window: int | None = None,
) -> jnp.ndarray:
    """(q_len, kv_len) additive bias: 0 where kv_pos <= q_pos, NEG_INF above
    and, under a ``window``, where ``q_pos - kv_pos >= window``.

    Offsets give the *global* position of each chunk's first element, which
    is what ring attention needs to mask cross-chunk blocks correctly.
    """
    q_pos = q_offset + jnp.arange(q_len)[:, None]
    kv_pos = kv_offset + jnp.arange(kv_len)[None, :]
    seen = kv_pos <= q_pos
    if window is not None:
        seen = seen & (q_pos - kv_pos < window)
    return jnp.where(seen, 0.0, NEG_INF).astype(dtype)


def dot_product_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    bias: jnp.ndarray | None = None,
    scale: float | None = None,
    window: int | None = None,
    return_lse: bool = False,
) -> jnp.ndarray:
    """XLA reference attention. q: (B,Sq,H,D); k/v: (B,Skv,H,D) -> (B,Sq,H,D).

    Softmax in float32; matmuls in the input dtype (bf16 on TPU hits the
    MXU; the f32 softmax runs on the VPU and fuses with the scale/mask).
    ``scale`` multiplies the scores; None is 1/sqrt(D).  A ``window``
    (causal only) hides the keys ``window`` or more behind a query.
    With ``return_lse`` the result is ``(out, lse)``, ``lse`` (B, Sq, H)
    float32 the log of each row's summed exponentials.
    """
    if window is not None and not causal:
        raise ValueError("a window bounds causal attention only")
    *_, Sq, H, D = q.shape
    Skv = k.shape[1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        # Sq != Skv (decode / chunked queries): queries are the LAST Sq
        # positions of the kv sequence, so a 1-token query sees everything.
        logits = logits + causal_mask_bias(
            Sq, Skv, q_offset=Skv - Sq, window=window
        )[None, None]
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", weights, v)
    if not return_lse:
        return out
    lse = jax.scipy.special.logsumexp(logits, axis=-1)  # (B, H, Sq)
    return out, lse.transpose(0, 2, 1)


def attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    impl: str = "auto",
    scale: float | None = None,
    window: int | None = None,
    return_lse: bool = False,
) -> jnp.ndarray:
    """Dispatch: 'xla' reference, 'pallas' flash kernel, or 'auto'.
    ``scale`` multiplies the scores; None is 1/sqrt(head_dim).  A static
    ``window`` (causal only; None: none) lets a query see its own key and
    the ``window - 1`` before it.  With ``return_lse`` the result is
    ``(out, lse)``, ``lse`` (B, Sq, H) float32, differentiable on either
    path (``ops.eva`` merges two softmaxes by it).

    'auto' uses the Pallas flash kernel on TPU whenever the shapes are
    ``supported()`` and the XLA reference otherwise; the choice is made
    from backend and shapes alone, so a kernel that fails to compile
    fails the jit — it never quietly becomes the O(S^2) path.  'pallas'
    additionally rejects unsupported shapes; 'xla' is the explicit way
    to ask for the reference.

    GQA: k/v may carry fewer heads than q (H % Hkv == 0).  The flash
    kernel consumes them natively (the shared kv head is indexed per
    query-head group — the repeated tensor never materializes); the XLA
    path expands via ``repeat_kv`` here.
    """
    if impl not in ("auto", "xla", "pallas"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl in ("auto", "pallas"):
        from distributeddataparallel_tpu.ops import pallas_attention

        if pallas_attention.supported(q, k, v):
            return pallas_attention.flash_attention(
                q, k, v, causal, False, scale, window, return_lse=return_lse
            )
        if impl == "pallas":
            raise ValueError(
                f"pallas flash attention unsupported for shapes "
                f"q={q.shape} k={k.shape} on {jax.default_backend()}"
            )
    if k.shape[2] != q.shape[2]:
        H, Hkv = q.shape[2], k.shape[2]
        if H % Hkv:
            raise ValueError(
                f"num_heads {H} not a multiple of kv heads {Hkv}"
            )
        k = repeat_kv(k, H // Hkv)
        v = repeat_kv(v, H // Hkv)
    return dot_product_attention(
        q, k, v, causal=causal, scale=scale, window=window,
        return_lse=return_lse,
    )
