"""Plain reference for the EvaByte configurations (``model_type``
``evabyte``, ``attention_class`` ``eva``): forward, the multi-byte loss,
gradients and AdamW in ``jax.numpy``, float32, matmuls at "highest"
precision.

No kernels, no merge of two softmaxes, and nothing imported from the
program.  Weights are the benchmark's own, as a flat dict in this layout:

    token_embed/embedding (V, d)   lm_head/kernel (d, P V)   final_norm/offset (d,)
    layer_i/{attn_norm,mlp_norm}/offset (d,)
    layer_i/attn/{q,k,v}_proj/kernel (d, H, D)    layer_i/attn/o_proj/kernel (H, D, d)
    layer_i/attn/adaptive_phi (H, D)              layer_i/attn/adaptive_mu_k (H, D)
    layer_i/mlp/{gate,up}_proj/kernel (d, f)      layer_i/mlp/down_proj/kernel (f, d)

``arch`` is the configuration file itself, read by the published keys
(``window_size``, ``chunk_size``, ``num_pred_heads``, ``rope_theta``,
``rms_norm_eps``).  The equations are the issue of PR 36's reading of the
published description (Zheng et al., arXiv:2302.04542; the EvaByte release
notes); what no key of the config fixes is listed under ``assumed`` in the
configuration's file and marked (a), (b), (c) where it happens below:

    h = embed[ids]                                   (float32 throughout)
    h = h + attn(N(h));  h = h + W_down(silu(W_gate x) * W_up x), x = N(h)
    N(x) = x * rsqrt(mean(x^2) + eps) * (1 + offset)
    logits = N(h) W_head, viewed (B, S, P, V)
    attn, per head, sigma = D^-1/2, W = window_size, c = chunk_size:
      q, k rotated (RoPE, half-split) at positions 0..S-1          (a)
      chunk j = positions [c j, c j + c), in window j // (W / c)
      a_jt = softmax_t(sigma <k_t, phi>) over the chunk            (b)
      ksum_j = sum_t a_jt k_t + mu;  vsum_j = sum_t a_jt v_t       (b)
      query i in window w = i // W sees key t iff W w <= t <= i, and
        summary j iff j < (W / c) w
      p = ONE softmax of sigma <q_i, .> over the keys and summaries seen
      out_i = sum_t p_t v_t + sum_j p_j vsum_j;  then W_o
    loss: head h at position t is scored on ids[t + 1 + h] where
      t + 1 + h <= S; the mean over the P heads of each head's mean
      cross entropy                                                (c)

Departures, each because the program's layout was taken over so that the
two trees have the same leaves: q, k, v and o keep a head axis; the eight
heads are one matrix of P V columns; a norm's learned leaf is its
``offset``.  With ``S <= W`` there is one window and no summary: plain
causal attention.

``quant`` is the control's hook (``gpt2.fake_fp8``): applied to both
operands of every matmul — projections, scores, the two value products,
the MLP, the head.  The pooling's 16-term sums are not matmuls and stay
as they are.

It has to fit beside nothing else on one chip at the published widths
and 16,384 positions: weights and AdamW's two moments stay on the device,
the starting weights wait on the host, gradients are made and applied a
layer at a time (AdamW is per leaf and nothing is clipped, so the order
does not matter); attention is computed one head and one window at a
time, the MLP in blocks of rows, each recomputed in the backward pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.afmoe import _rope
from benchmarks.reference.gpt2 import _mm, adamw_update, fake_fp8  # noqa: F401
from benchmarks.reference.granite_hybrid import (  # noqa: F401
    _Frozen,
    _norms,
    _silu,
    layers_of,
)

#: rows of the MLP computed (and recomputed) at a time
MLP_ROWS = 2048


def _norm(x, offset, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + offset)


def summaries(k, v, phi, mu, chunk: int, sigma: float):
    """(b, s, H, D) keys and values -> one summary a chunk, (b, s / chunk,
    H, D) each."""
    b, s, H, D = k.shape
    kc = k.reshape(b, s // chunk, chunk, H, D)
    vc = v.reshape(b, s // chunk, chunk, H, D)
    # (b): the pooling's logits carry sigma; mu is added after the pooling,
    # to the key alone
    a = jax.nn.softmax(sigma * jnp.sum(kc * phi, axis=-1), axis=2)[..., None]
    return jnp.sum(a * kc, axis=2) + mu, jnp.sum(a * vc, axis=2)


def attention(x, p, arch, quant):
    q, k, v = (
        _mm("bsd,dhe->bshe", x, p[f"attn/{n}_proj/kernel"], quant)
        for n in ("q", "k", "v")
    )
    # (a): rotated before the pooling, so summaries are of rotated keys
    q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    b, s, H, D = q.shape
    W = min(arch["window_size"], s)
    c = arch["chunk_size"]
    sigma = D ** -0.5
    ksum, vsum = summaries(
        k, v, p["attn/adaptive_phi"], p["attn/adaptive_mu_k"], c, sigma)
    near = jnp.arange(W)[None, :] <= jnp.arange(W)[:, None]        # (W, W)
    chunk_window = jnp.arange(s // c) // (W // c)                  # (J,)

    @jax.checkpoint
    def window(q, k, v, ksum, vsum, w):
        """One head's window ``w``: (b, W, D) queries over their own
        window's keys and every earlier window's summaries — ONE softmax
        over the two sets side by side."""
        scores = sigma * jnp.concatenate([
            _mm("bqe,bke->bqk", q, k, quant),
            _mm("bqe,bje->bqj", q, ksum, quant),
        ], axis=-1)
        seen = jnp.concatenate([
            near, jnp.broadcast_to(chunk_window < w, (W, s // c)),
        ], axis=-1)
        a = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return (_mm("bqk,bke->bqe", a[..., :W], v, quant)
                + _mm("bqj,bje->bqe", a[..., W:], vsum, quant))

    def head(args):
        q, k, v, ksum, vsum = args       # (n_w, b, W, D) x3, (b, J, D) x2
        return jax.lax.map(
            lambda a: window(a[0], a[1], a[2], ksum, vsum, a[3]),
            (q, k, v, jnp.arange(s // W)),
        )

    def by_head_and_window(t):           # (b, s, H, D) -> (H, n_w, b, W, D)
        return t.reshape(b, s // W, W, H, D).transpose(3, 1, 0, 2, 4)

    o = jax.lax.map(head, (
        by_head_and_window(q), by_head_and_window(k), by_head_and_window(v),
        jnp.moveaxis(ksum, 2, 0), jnp.moveaxis(vsum, 2, 0),
    ))                                   # (H, n_w, b, W, D)
    o = o.transpose(2, 1, 3, 0, 4).reshape(b, s, H, D)
    return _mm("bshe,hed->bsd", o, p["attn/o_proj/kernel"], quant)


def gated_mlp(x, p, quant):
    """The MLP in blocks of ``MLP_ROWS`` rows, each recomputed in the
    backward pass."""
    b, s, d = x.shape
    rows = MLP_ROWS if s % MLP_ROWS == 0 else s

    @jax.checkpoint
    def block(x):
        gate = _mm("bsd,df->bsf", x, p["mlp/gate_proj/kernel"], quant)
        up = _mm("bsd,df->bsf", x, p["mlp/up_proj/kernel"], quant)
        return _mm("bsf,fd->bsd", _silu(gate) * up,
                   p["mlp/down_proj/kernel"], quant)

    y = jax.lax.map(block, x.reshape(b, s // rows, rows, d).swapaxes(0, 1))
    return y.swapaxes(0, 1).reshape(b, s, d)


def layer(x, p, arch, quant):
    """One layer; ``p`` holds its leaves without the ``layer_i/``."""
    eps = arch["rms_norm_eps"]
    x = x + jax.checkpoint(
        lambda x, p: attention(_norm(x, p["attn_norm/offset"], eps), p, arch, quant)
    )(x, p)
    return x + gated_mlp(_norm(x, p["mlp_norm/offset"], eps), p, quant)


def logits_of(x, p, arch, quant):
    x = _norm(x, p["final_norm/offset"], arch["rms_norm_eps"])
    logits = _mm("bsd,dv->bsv", x, p["lm_head/kernel"], quant)
    return logits.reshape(x.shape[:2] + (arch["num_pred_heads"], -1))


def head_loss(x, p, rows, arch, quant):
    """The multi-byte loss of the last layer's output; ``rows`` (b, s + 1)
    are the ids, inputs and every target."""
    logp = jax.nn.log_softmax(logits_of(x, p, arch, quant), axis=-1)
    s = x.shape[1]
    total = 0.0
    for h in range(arch["num_pred_heads"]):
        # (c): positions 0 .. s - 1 - h have a byte 1 + h ahead; the heads
        # weigh alike
        targets = rows[:, 1 + h:]
        total = total - jnp.mean(jnp.take_along_axis(
            logp[:, :s - h, h], targets[..., None], axis=-1))
    return total / arch["num_pred_heads"]


def forward(w: dict, tokens, arch, quant=None):
    """tokens (B, S) int32 -> logits (B, S, P, V) float32 (the CPU tests)."""
    x = w["token_embed/embedding"][tokens]
    for p in layers_of(w):
        x = layer(x, p, arch, quant)
    return logits_of(x, w, arch, quant)


@functools.partial(jax.jit, static_argnames=("arch", "quant"))
def _layer_fwd(x, p, arch, quant):
    return layer(x, p, arch, quant)


@functools.partial(jax.jit, static_argnames=("arch", "quant"))
def _layer_bwd(x, p, dy, arch, quant):
    _, vjp = jax.vjp(lambda x, p: layer(x, p, arch, quant), x, p)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("arch", "quant"))
def _head_bwd(x, p, rows, arch, quant):
    return jax.value_and_grad(head_loss, argnums=(0, 1))(
        x, p, rows, arch, quant
    )


@jax.jit
def _embed_bwd(table, tokens, dx):
    return jnp.zeros_like(table).at[tokens].add(dx)


def train_steps(w0: dict, batches, opt: dict, arch: dict, quant=None,
                progress=None, devices=None):
    """Follow ``len(batches)`` AdamW steps from ``w0`` on ``batches`` (each
    (B, S+1) int32).  Returns ``{"loss": [per step], "grad_norm": {leaf:
    norm of the first gradient}, "update_norm": {leaf: ||w_n - w0||}}`` as
    Python numbers — ``gpt2.train_steps``'s result.  ``devices`` is taken
    for that interface's sake: a step's one row stays on the default
    device."""
    arch = _Frozen(arch)
    start = {k: np.asarray(v, np.float32) for k, v in w0.items()}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w0.items()}
    del w0
    mu = {k: jnp.zeros_like(v) for k, v in w.items()}
    nu = {k: jnp.zeros_like(v) for k, v in w.items()}
    hyper = (opt["lr"], opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"])
    out = {"loss": [], "grad_norm": {}}

    def apply(grads: dict, count, first: bool):
        """AdamW on the leaves of ``grads`` (full names), in place."""
        keys = list(grads)
        if first:
            out["grad_norm"].update(
                {k: float(v) for k, v in _norms(grads).items()}
            )
        new_w, new_mu, new_nu, _ = adamw_update(
            {k: w[k] for k in keys}, grads, {k: mu[k] for k in keys},
            {k: nu[k] for k in keys}, count, *hyper,
        )
        w.update(new_w)
        mu.update(new_mu)
        nu.update(new_nu)

    for i, rows in enumerate(batches):
        rows = jnp.asarray(rows)
        tokens = rows[:, :-1]
        count = jnp.float32(i)
        xs = [w["token_embed/embedding"][tokens]]
        stack = layers_of(w)
        for p in stack:
            xs.append(_layer_fwd(xs[-1], p, arch, quant))
        top = {k: w[k] for k in ("final_norm/offset", "lm_head/kernel")}
        loss, (dx, dtop) = _head_bwd(xs.pop(), top, rows, arch, quant)
        out["loss"].append(float(loss))
        if progress is not None:
            progress(f"reference step {i + 1}: forward and head done")
        apply(dtop, count, i == 0)
        del top, dtop
        while stack:  # a layer's old leaves go as soon as it is updated
            p = stack.pop()
            dx, dp = _layer_bwd(xs.pop(), p, dx, arch, quant)
            del p
            apply({f"layer_{len(stack)}/{k}": v for k, v in dp.items()},
                  count, i == 0)
            del dp
        apply({"token_embed/embedding": _embed_bwd(
            w["token_embed/embedding"], tokens, dx)}, count, i == 0)
        del dx
        if progress is not None:
            progress(f"reference step {i + 1} done")
    out["update_norm"] = {
        k: float(jnp.sqrt(jnp.sum(jnp.square(w[k] - start[k])))) for k in w
    }
    return out
