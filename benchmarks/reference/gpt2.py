"""Plain reference for the GPT-2 configurations: forward, loss, gradients
and AdamW in ``jax.numpy``, float32, matmuls at "highest" precision.

No kernels, no cache, no batching tricks, and nothing imported from the
program.  Weights are the benchmark's own (``harness.make_weights``), as
a flat ``{"layer_0/attn/q_proj/kernel": array, ...}`` dict in this layout:

    token_embed/embedding (V, d)      pos_embed (S, d)
    layer_i/attn_norm/{scale,bias} (d,)
    layer_i/attn/{q,k,v}_proj/kernel (d, H, D)   .../bias (H, D)
    layer_i/attn/o_proj/kernel (H, D, d)         .../bias (d,)
    layer_i/mlp_norm/{scale,bias} (d,)
    layer_i/mlp/up_proj/kernel (d, f)            .../bias (f,)
    layer_i/mlp/down_proj/kernel (f, d)          .../bias (d,)
    final_norm/{scale,bias} (d,)
    (the output head is the token embedding, tied)

Follows Radford et al. 2019 / the public ``gpt2`` modelling code: pre-norm
blocks, LayerNorm eps 1e-5, tanh-approximated GELU (``gelu_new``), learned
positions, causal softmax attention scaled by 1/sqrt(head_dim).

``quant`` is the control's hook: a function applied to BOTH operands of
every matmul (``fake_fp8`` below rounds them to float8 e4m3, and the
matmuls' incoming gradients to float8 e5m2) — the reference computed one
precision step below what the cells state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5


def _round_to(x, dtype, top: float):
    """Round to ``dtype`` with one scale per tensor (absmax -> ``top``)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_gradient(y):
    return y


_fp8_gradient.defvjp(
    lambda y: (y, None),
    lambda _, g: (_round_to(g, jnp.float8_e5m2, 57344.0),),
)


def fake_fp8(x):
    """The control's matmul operand: rounded to float8 e4m3 on the way
    forward (straight-through, so the backward products see the rounded
    operands); ``_mm`` rounds the products' incoming gradients to float8
    e5m2 — the usual fp8 training recipe, one step below bfloat16."""
    return x + jax.lax.stop_gradient(
        _round_to(x, jnp.float8_e4m3fn, 448.0) - x
    )


def _mm(spec, a, b, quant):
    if quant is None:
        return jnp.einsum(spec, a, b, precision="highest")
    return _fp8_gradient(
        jnp.einsum(spec, quant(a), quant(b), precision="highest")
    )


def _layer_norm(x, scale, bias):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)
    ))


def num_layers(w: dict) -> int:
    return 1 + max(
        int(k.split("/")[0][len("layer_"):])
        for k in w if k.startswith("layer_")
    )


BLOCK_LEAVES = (
    "attn_norm/scale", "attn_norm/bias",
    "attn/q_proj/kernel", "attn/q_proj/bias",
    "attn/k_proj/kernel", "attn/k_proj/bias",
    "attn/v_proj/kernel", "attn/v_proj/bias",
    "attn/o_proj/kernel", "attn/o_proj/bias",
    "mlp_norm/scale", "mlp_norm/bias",
    "mlp/up_proj/kernel", "mlp/up_proj/bias",
    "mlp/down_proj/kernel", "mlp/down_proj/bias",
)


def block(x, p: dict, causal, quant):
    """One pre-norm GPT-2 block; ``p`` holds one layer's leaves."""
    y = _layer_norm(x, p["attn_norm/scale"], p["attn_norm/bias"])
    q, k, v = (
        _mm("bsd,dhe->bshe", y, p[f"attn/{n}_proj/kernel"], quant)
        + p[f"attn/{n}_proj/bias"]
        for n in "qkv"
    )
    s = _mm("bqhe,bkhe->bhqk", q, k, quant) / (q.shape[-1] ** 0.5)
    s = jnp.where(causal[None, None], s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhe->bqhe", a, v, quant)
    x = x + _mm("bshe,hed->bsd", o, p["attn/o_proj/kernel"], quant) \
        + p["attn/o_proj/bias"]
    y = _layer_norm(x, p["mlp_norm/scale"], p["mlp_norm/bias"])
    h = _gelu_new(
        _mm("bsd,df->bsf", y, p["mlp/up_proj/kernel"], quant)
        + p["mlp/up_proj/bias"]
    )
    return x + _mm("bsf,fd->bsd", h, p["mlp/down_proj/kernel"], quant) \
        + p["mlp/down_proj/bias"]


def forward(w: dict, tokens, quant=None):
    """tokens (B, S) int32 -> logits (B, S, V) float32.  The blocks are
    one ``block`` scanned over the layers' leaves stacked (the same
    arithmetic as a Python loop, one compiled body instead of L)."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    B, S = tokens.shape
    x = w["token_embed/embedding"][tokens] + w["pos_embed"][:S][None]
    causal = jnp.tril(jnp.ones((S, S), bool))
    stacked = {
        leaf: jnp.stack([w[f"layer_{i}/{leaf}"] for i in range(num_layers(w))])
        for leaf in BLOCK_LEAVES
    }
    # checkpointed: the backward pass keeps one activation a layer and
    # recomputes the block, so that a row's gradient fits beside the
    # weights, the gradient sum and AdamW's moments
    body = jax.checkpoint(lambda x, p: block(x, p, causal, quant))
    x, _ = jax.lax.scan(lambda x, p: (body(x, p), None), x, stacked)
    x = _layer_norm(x, w["final_norm/scale"], w["final_norm/bias"])
    return _mm("bsd,vd->bsv", x, w["token_embed/embedding"], quant)


def loss_sum(w: dict, tokens, quant=None):
    """Summed next-token cross entropy of rows (B, S+1)."""
    logits = forward(w, tokens[:, :-1], quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.sum(picked)


@functools.partial(jax.jit, static_argnames=("quant", "block"))
def loss_and_grad(w: dict, tokens, quant=None, block: int = 1):
    """Mean loss and its gradient over rows (B, S+1), computed ``block``
    rows at a time so that it fits beside nothing else on one chip."""
    B, S1 = tokens.shape
    blocks = tokens.reshape(B // block, block, S1)
    zero = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), w)

    def body(carry, rows):
        tot, acc = carry
        l, g = jax.value_and_grad(loss_sum)(w, rows, quant)
        return (tot + l, jax.tree.map(jnp.add, acc, g)), None

    (tot, acc), _ = jax.lax.scan(body, (jnp.float32(0.0), zero), blocks)
    n = B * (S1 - 1)
    return tot / n, jax.tree.map(lambda g: g / n, acc)


@jax.jit
def adamw_update(w, g, mu, nu, count, lr, b1, b2, eps, weight_decay):
    """One AdamW step (Loshchilov & Hutter 2019, as optax.adamw orders it:
    bias-corrected moments, decay added to the update, then -lr)."""
    count = count + 1
    mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count
    new_w = jax.tree.map(
        lambda p, m, v: p - lr * (
            (m / c1) / (jnp.sqrt(v / c2) + eps) + weight_decay * p
        ),
        w, mu, nu,
    )
    return new_w, mu, nu, count


@jax.jit
def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


@jax.jit
def diff_norms(a: dict, b: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        a[k].astype(jnp.float32) - b[k].astype(jnp.float32)
    ))) for k in a}


@jax.jit
def _as_float32(w: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in w.items()}


@jax.jit
def _mean_of(trees: list):
    return jax.tree.map(lambda *xs: sum(xs) / len(xs), *trees)


def loss_and_grad_on(devices, w: dict, tokens, quant=None, block: int = 1):
    """``loss_and_grad`` with the rows dealt evenly over ``devices``: each
    device computes the plain mean loss and gradient of its rows from its
    own copy of the weights, and the first device averages them.  (Equal
    shares, so the mean of the means is the mean.)  It only saves time
    where a cell holds several chips."""
    import numpy as np

    if not devices or len(devices) == 1:
        return loss_and_grad(w, jnp.asarray(tokens), quant=quant, block=block)
    tokens = np.asarray(tokens)
    if len(tokens) % len(devices):
        raise ValueError("rows do not divide evenly over the devices")
    parts = [
        loss_and_grad(jax.device_put(w, d), jax.device_put(rows, d),
                      quant=quant, block=block)
        for d, rows in zip(devices, np.split(tokens, len(devices)))
    ]
    return _mean_of([jax.device_put(p, devices[0]) for p in parts])


def train_steps(w0: dict, batches, opt: dict, quant=None, block: int = 1,
                progress=None, devices=None):
    """Follow ``len(batches)`` AdamW steps from ``w0`` on ``batches`` (each
    (B, S+1) int32), the rows of a step dealt over ``devices`` if given.
    Returns ``{"loss": [per step], "grad_norm": {leaf:
    norm of the first gradient}, "update_norm": {leaf: ||w_n - w0||}}``
    as Python floats."""
    w = _as_float32(w0)  # a jit's output, as every later step's weights are
    mu = jax.tree.map(jnp.zeros_like, w)
    nu = jax.tree.map(jnp.zeros_like, w)
    count = jnp.zeros((), jnp.float32)
    out = {"loss": []}
    start = w
    for i, tokens in enumerate(batches):
        l, g = loss_and_grad_on(devices, w, tokens, quant=quant, block=block)
        out["loss"].append(float(l))
        if progress is not None:
            progress(f"reference step {i + 1} done")
        if i == 0:
            out["grad_norm"] = {
                k: float(v) for k, v in leaf_norms(g).items()
            }
        w, mu, nu, count = adamw_update(
            w, g, mu, nu, count, opt["lr"], opt["b1"], opt["b2"],
            opt["eps"], opt["weight_decay"],
        )
        del g
    out["update_norm"] = {
        k: float(v) for k, v in diff_norms(w, start).items()
    }
    return out


@functools.partial(jax.jit, static_argnames=("quant",))
def _logits_one(w, tokens, quant=None):
    return forward(w, tokens, quant)[0]


def served_gaps(w: dict, prompt, generated, pad_to: int, quant=None):
    """For one finished request: the reference's logits over prompt +
    served tokens, and per served token the gap by which its logit lies
    below the reference's best (0 where the served token IS the best).

    With ``quant`` set it reads the control instead: the token that the
    lower-precision forward puts first at each position, and that token's
    gap under the full-precision logits ``ref_logits`` must then be read
    by the caller — so this returns the logits rows too.
    Returns (gaps (n,), logits rows (n, V))."""
    import numpy as np

    prompt = np.asarray(prompt, np.int32)
    gen = np.asarray(generated, np.int32)
    seq = np.concatenate([prompt, gen[:-1]])
    toks = np.zeros((1, pad_to), np.int32)
    toks[0, : seq.size] = seq
    rows = _logits_one(w, jnp.asarray(toks), quant=quant)
    rows = rows[prompt.size - 1: prompt.size - 1 + gen.size]
    best = jnp.max(rows, axis=-1)
    served = jnp.take_along_axis(rows, jnp.asarray(gen)[:, None], axis=-1)[:, 0]
    return np.asarray(best - served), rows
