"""Losses/metrics: the ``nn.CrossEntropyLoss`` analog (ref dpp.py:40,51).

Mean-reduced softmax cross entropy over integer labels — identical math to
torch's default CrossEntropyLoss reduction. Computed in float32 regardless
of activation dtype (logits are upcast) for numerical parity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import optax

from distributeddataparallel_tpu.observability import scopes


def per_example_cross_entropy(
    logits: jnp.ndarray, labels: jnp.ndarray
) -> jnp.ndarray:
    """Per-row CE: (B, C)/(B,) -> (B,); LM (B, S, V)/(B, S) -> (B,) mean
    over positions.  Row-resolved so evaluation can mask sampler-padded
    duplicate rows exactly (see ``make_eval_step(masked=True)``)."""
    logits = logits.astype(jnp.float32)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels)
    return ce if ce.ndim == 1 else ce.mean(axis=tuple(range(1, ce.ndim)))


def per_example_accuracy(
    logits: jnp.ndarray, labels: jnp.ndarray
) -> jnp.ndarray:
    """Per-row accuracy; trailing (sequence) axes are averaged per row."""
    hit = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    return hit if hit.ndim == 1 else hit.mean(axis=tuple(range(1, hit.ndim)))


@jax.named_scope(scopes.LOSS)
def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean softmax CE with integer labels; logits (B, C), labels (B,)."""
    return per_example_cross_entropy(logits, labels).mean()


@jax.named_scope(scopes.METRICS)
def accuracy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return per_example_accuracy(logits, labels).mean()


@jax.named_scope(scopes.LOSS)
def lm_cross_entropy(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Next-token CE for LMs: logits (B, S, V), targets (B, S) int.

    ``mask`` (B, S) in {0,1} excludes padding positions; mean is over
    unmasked tokens so per-batch loss is comparable across packing.
    """
    logits = logits.astype(jnp.float32)
    ce = optax.softmax_cross_entropy_with_integer_labels(logits, targets)
    if mask is None:
        return ce.mean()
    mask = mask.astype(jnp.float32)
    return (ce * mask).sum() / jnp.maximum(mask.sum(), 1.0)


@jax.named_scope(scopes.LOSS)
def multi_token_cross_entropy(
    logits: jnp.ndarray, ids: jnp.ndarray
) -> jnp.ndarray:
    """Several tokens predicted a position: logits (B, S, P, V), ids
    (B, S + 1) int.  Head ``h`` at position ``t`` is scored on ``ids[t + 1
    + h]`` where the row has one (``t + 1 + h <= S``), masked elsewhere;
    the loss is the mean over the P heads of each head's mean cross
    entropy over its unmasked positions.  At P = 1 it is
    ``lm_cross_entropy(logits[:, :, 0], ids[:, 1:])``."""
    B, S, P, _ = logits.shape
    ahead = jnp.arange(S)[:, None] + 1 + jnp.arange(P)[None, :]   # (S, P)
    scored = (ahead <= S).astype(jnp.float32)
    targets = ids[:, jnp.minimum(ahead, S)]                       # (B, S, P)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), targets
    )
    per_head = (ce * scored).sum(axis=(0, 1)) / (B * scored.sum(axis=0))
    return per_head.mean()
