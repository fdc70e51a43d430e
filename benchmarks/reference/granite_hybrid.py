"""Plain reference for the Granite 4.0-H configurations (``model_type``
``granitemoehybrid`` without experts): forward, loss, gradients and AdamW
in ``jax.numpy``, float32, matmuls at "highest" precision.

No kernels, no chunked algebra, and nothing imported from the program.
Weights are the benchmark's own, as a flat dict in this layout (a layer
has ``mamba`` or ``attn``, which is how its kind is told):

    token_embed/embedding (V, d)            (the output head too, tied)
    layer_i/mamba_norm/scale (d,)   layer_i/mamba/in_proj/kernel (d, 2 I + 2 G N + H)
    layer_i/mamba/conv_kernel (K, I + 2 G N)   .../conv_bias (I + 2 G N,)
    layer_i/mamba/{dt_bias,A_log,D} (H,)    layer_i/mamba/norm/scale (I,)
    layer_i/mamba/out_proj/kernel (I, d)                    I = H * P
    layer_i/attn_norm/scale (d,)    layer_i/attn/{q,k,v}_proj/kernel (d, heads, D)
    layer_i/attn/o_proj/kernel (heads, D, d)
    layer_i/mlp_norm/scale (d,)     layer_i/mlp/{gate,up}_proj/kernel (d, f)
    layer_i/mlp/down_proj/kernel (f, d)     final_norm/scale (d,)

``arch`` is the configuration file itself, read by the published keys
(``mamba_n_heads``, ``attention_multiplier``, ...).  The equations follow
the public ``GraniteMoeHybrid`` and Mamba-2 modelling code:

    x = embed[tokens] * embedding_multiplier           (no positions)
    x = x + residual_multiplier * mixer(rms_norm(x))
    x = x + residual_multiplier * mlp(rms_norm(x))     (each layer)
    logits = rms_norm(x) embed^T / logits_scaling
    mlp(x) = (silu(x W_gate) * (x W_up)) W_down
    attention: causal, grouped queries, scores * attention_multiplier
    mamba: z, xBC, dt = split(x W_in); xBC = silu(conv(xBC) + b), a
      causal depthwise convolution (K taps, K - 1 zeros to the left);
      x, B, C = split(xBC); dt = softplus(dt + dt_bias); A = -exp(A_log);
      h_t = exp(dt_t A) h_{t-1} + dt_t x_t (outer) B_t;  y_t = h_t . C_t + D x_t
      out = (rms_norm(y * silu(z)) * w) W_out     (one group: all of I)

Departures, each because the program's layout was taken over so that the
two trees have the same leaves: ``gate_proj`` and ``up_proj`` are the two
halves of the published ``shared_mlp.input_linear``; the convolution's
taps are stored (K, channels), not (channels, 1, K); q, k, v and o keep a
head axis.  The recurrence is written as the recurrence, a ``lax.scan``
over time (in blocks that the backward pass recomputes, so that its
states fit), never as the chunked products the program runs.

``quant`` is the control's hook (``gpt2.fake_fp8``): applied to both
operands of every matmul and, since the recurrence has no matmul, to the
scan's ``x``, ``B`` and ``C``.

It has to fit beside nothing else on one chip at the published widths:
weights and AdamW's two moments stay on the device, the starting weights
wait on the host, and gradients are made and applied a layer at a time
(AdamW is per leaf and nothing is clipped, so the order does not matter).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.gpt2 import _mm, adamw_update, fake_fp8  # noqa: F401

#: time steps a block of the recurrence: the backward pass keeps one
#: state a block and recomputes the block's own
SCAN_BLOCK = 64


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def mlp(x, p, quant):
    gate = _mm("bsd,df->bsf", x, p["mlp/gate_proj/kernel"], quant)
    up = _mm("bsd,df->bsf", x, p["mlp/up_proj/kernel"], quant)
    return _mm("bsf,fd->bsd", _silu(gate) * up, p["mlp/down_proj/kernel"], quant)


def attention(x, p, arch, quant):
    """Causal grouped-query attention, one key/value head (and the query
    heads that share it) at a time: the (S, S) scores of all heads at once
    would not fit at the cell's length."""
    S = x.shape[1]
    q, k, v = (
        _mm("bsd,dhe->bshe", x, p[f"attn/{n}_proj/kernel"], quant)
        for n in "qkv"
    )
    kv_heads = k.shape[2]
    q = q.reshape(q.shape[:2] + (kv_heads, -1, q.shape[-1]))  # (b, s, kv, r, e)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def group(qkv):
        q, k, v = qkv  # (b, s, r, e), (b, s, e), (b, s, e)
        s = _mm("bqre,bke->brqk", q, k, quant) * arch["attention_multiplier"]
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm("brqk,bke->bqre", a, v, quant)

    o = jax.lax.map(group, (
        jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)
    ))                                                   # (kv, b, s, r, e)
    o = jnp.moveaxis(o, 0, 2).reshape(x.shape[:2] + p["attn/o_proj/kernel"].shape[:2])
    return _mm("bshe,hed->bsd", o, p["attn/o_proj/kernel"], quant)


def ssm_scan(x, dt, A, B, C, D):
    """The recurrence, a step at a time.  ``x`` (b, s, h, p); ``dt``
    (b, s, h), after softplus; ``A``, ``D`` (h,); ``B``, ``C``
    (b, s, g, n), a group's heads sharing them.  Returns (b, s, h, p)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    rep = h // B.shape[2]
    pad = -s % SCAN_BLOCK
    # a padded step has dt = 0: it neither decays the state nor adds to it

    def blocks(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((-1, SCAN_BLOCK) + a.shape[1:])

    def step(state, inputs):
        x_t, dt_t, B_t, C_t = inputs  # (b,h,p) (b,h) (b,g,n) (b,g,n)
        B_t = jnp.repeat(B_t, rep, axis=1)
        C_t = jnp.repeat(C_t, rep, axis=1)
        state = (
            jnp.exp(dt_t * A)[..., None, None] * state
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, :, None, :]
        )
        return state, jnp.sum(state * C_t[:, :, None, :], axis=-1)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(step, state, inputs)

    _, y = jax.lax.scan(
        block, jnp.zeros((b, h, p, n), jnp.float32),
        (blocks(x), blocks(dt), blocks(B), blocks(C)),
    )
    y = jnp.moveaxis(y.reshape((-1,) + y.shape[2:]), 0, 1)[:, :s]
    return y + D[:, None] * x


def mamba(u, p, arch, quant):
    H, P = arch["mamba_n_heads"], arch["mamba_d_head"]
    G, N = arch["mamba_n_groups"], arch["mamba_d_state"]
    K = arch["mamba_d_conv"]
    inner, bc = H * P, G * N
    b, s, _ = u.shape
    z, xbc, dt = jnp.split(
        _mm("bsd,de->bse", u, p["mamba/in_proj/kernel"], quant),
        [inner, 2 * inner + 2 * bc], axis=-1,
    )
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = _silu(p["mamba/conv_bias"] + sum(
        p["mamba/conv_kernel"][k] * padded[:, k:k + s] for k in range(K)
    ))
    x, B, C = jnp.split(xbc, [inner, inner + bc], axis=-1)
    if quant is not None:
        x, B, C = quant(x), quant(B), quant(C)
    y = ssm_scan(
        x.reshape(b, s, H, P), jax.nn.softplus(dt + p["mamba/dt_bias"]),
        -jnp.exp(p["mamba/A_log"]), B.reshape(b, s, G, N),
        C.reshape(b, s, G, N), p["mamba/D"],
    ).reshape(b, s, inner)
    y = _rms_norm(y * _silu(z), p["mamba/norm/scale"], arch["rms_norm_eps"])
    return _mm("bse,ed->bsd", y, p["mamba/out_proj/kernel"], quant)


def layer(x, p, arch, quant):
    """One layer; ``p`` holds its leaves without the ``layer_i/``."""
    eps, rm = arch["rms_norm_eps"], arch["residual_multiplier"]
    if "mamba/A_log" in p:
        x = x + rm * mamba(_rms_norm(x, p["mamba_norm/scale"], eps), p, arch, quant)
    else:
        x = x + rm * attention(_rms_norm(x, p["attn_norm/scale"], eps), p, arch, quant)
    return x + rm * mlp(_rms_norm(x, p["mlp_norm/scale"], eps), p, quant)


def embed(table, tokens, arch):
    return table[tokens] * arch["embedding_multiplier"]


def head_loss(x, p, targets, arch, quant):
    """Summed next-token cross entropy of the last layer's output."""
    x = _rms_norm(x, p["final_norm/scale"], arch["rms_norm_eps"])
    logits = _mm("bsd,vd->bsv", x, p["token_embed/embedding"], quant)
    logp = jax.nn.log_softmax(logits / arch["logits_scaling"], axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def layers_of(w: dict) -> list:
    """``[{leaf without its layer_i/: array}, ...]`` in order."""
    out: dict = {}
    for k, v in w.items():
        if k.startswith("layer_"):
            name, leaf = k.split("/", 1)
            out.setdefault(int(name[len("layer_"):]), {})[leaf] = v
    return [out[i] for i in range(len(out))]


def forward(w: dict, tokens, arch, quant=None):
    """tokens (B, S) int32 -> logits (B, S, V) float32 (the CPU tests)."""
    x = embed(w["token_embed/embedding"], tokens, arch)
    for p in layers_of(w):
        x = layer(x, p, arch, quant)
    x = _rms_norm(x, w["final_norm/scale"], arch["rms_norm_eps"])
    return _mm(
        "bsd,vd->bsv", x, w["token_embed/embedding"], quant
    ) / arch["logits_scaling"]


class _Frozen(dict):
    """``arch`` as a static argument of a jit."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


@functools.partial(jax.jit, static_argnames=("arch", "quant"))
def _layer_fwd(x, p, arch, quant):
    return layer(x, p, arch, quant)


@functools.partial(jax.jit, static_argnames=("arch", "quant"))
def _layer_bwd(x, p, dy, arch, quant):
    _, vjp = jax.vjp(lambda x, p: layer(x, p, arch, quant), x, p)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("arch", "quant"))
def _head_bwd(x, p, targets, arch, quant):
    return jax.value_and_grad(head_loss, argnums=(0, 1))(
        x, p, targets, arch, quant
    )


@functools.partial(jax.jit, static_argnames=("arch",))
def _embed_bwd(table_grad, tokens, dx, arch):
    return table_grad.at[tokens].add(dx * arch["embedding_multiplier"])


@jax.jit
def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in tree.items()}


def train_steps(w0: dict, batches, opt: dict, arch: dict, quant=None,
                progress=None, devices=None):
    """Follow ``len(batches)`` AdamW steps from ``w0`` on ``batches`` (each
    (B, S+1) int32).  Returns ``{"loss": [per step], "grad_norm": {leaf:
    norm of the first gradient}, "update_norm": {leaf: ||w_n - w0||}}`` as
    Python floats — ``gpt2.train_steps``'s result.  ``devices`` is taken
    for that interface's sake: a step's rows are few and stay on the
    default device."""
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
        tokens, targets = rows[:, :-1], rows[:, 1:]
        n = targets.size
        count = jnp.float32(i)
        table = w["token_embed/embedding"]
        xs = [embed(table, tokens, arch)]
        stack = layers_of(w)
        for p in stack:
            xs.append(_layer_fwd(xs[-1], p, arch, quant))
        top = {k: w[k] for k in ("final_norm/scale", "token_embed/embedding")}
        loss, (dx, dtop) = _head_bwd(xs.pop(), top, targets, arch, quant)
        out["loss"].append(float(loss) / n)
        if progress is not None:
            progress(f"reference step {i + 1}: forward and head done")
        dx = dx / n
        table_grad = dtop.pop("token_embed/embedding") / n
        apply({"final_norm/scale": dtop["final_norm/scale"] / n}, count, i == 0)
        del top, table
        while stack:  # a layer's old leaves go as soon as it is updated
            p = stack.pop()
            dx, dp = _layer_bwd(xs.pop(), p, dx, arch, quant)
            del p
            apply({f"layer_{len(stack)}/{k}": v for k, v in dp.items()},
                  count, i == 0)
            del dp
        table_grad = _embed_bwd(table_grad, tokens, dx, arch)
        apply({"token_embed/embedding": table_grad}, count, i == 0)
        del table_grad, dx
        if progress is not None:
            progress(f"reference step {i + 1} done")
    out["update_norm"] = {
        k: float(jnp.sqrt(jnp.sum(jnp.square(w[k] - start[k])))) for k in w
    }
    return out
