"""Plain reference for the Trinity configurations (``model_type``
``afmoe``): forward, loss, gradients and AdamW in ``jax.numpy``, float32,
matmuls at "highest" precision.

No kernels, no sort, no grouped product, and nothing imported from the
program.  Weights are the benchmark's own, as a flat dict in this layout
(a layer's feed-forward has ``mlp/router`` or it is dense):

    token_embed/embedding (V, d)    lm_head/kernel (d, V)    final_norm/scale (d,)
    layer_i/{attn_norm,post_attn_norm,mlp_norm,post_mlp_norm}/scale (d,)
    layer_i/attn/{q,gate}_proj/kernel (d, H, D)   layer_i/attn/{k,v}_proj/kernel (d, Hkv, D)
    layer_i/attn/{q,k}_norm/scale (D,)            layer_i/attn/o_proj/kernel (H, D, d)
    layer_i/mlp/{gate,up}_proj/kernel (d, f)      layer_i/mlp/down_proj/kernel (f, d)
    layer_i/mlp/router/kernel (d, E)              layer_i/mlp/expert_bias (E,)
    layer_i/mlp/experts_{gate,up} (held, d, fe)   layer_i/mlp/experts_down (held, fe, d)
    layer_i/mlp/shared/{gate,up}_proj/kernel (d, fs)   layer_i/mlp/shared/down_proj/kernel (fs, d)

``arch`` is the configuration file itself, read by the published keys
(``layer_types``, ``sliding_window``, ``route_scale``, ...) and by
``experts_held`` = [first, count]: which of the router's ``E`` experts
this chip holds.  The equations follow the public ``afmoe`` modelling
code as the issue of PR 33 wrote them down; what the published keys do
not pin down is listed under ``assumed`` in the configuration's file:

    h = embed[tokens] * sqrt(hidden_size)                      (mup_enabled)
    h = h + rms_norm(attn(rms_norm(h)))                        (four norms
    h = h + rms_norm(ffn(rms_norm(h)))                          a layer)
    logits = rms_norm(h) W_head                                (untied)
    attn: q, k = rms_norm over each head's D; v; g = x W_gate
      sliding_attention: RoPE (half-split) on q and k after their norms;
        key j is visible to query i iff j <= i and i - j < sliding_window
      full_attention: no positions at all; key j visible iff j <= i
      p = softmax(q k^T / sqrt(D)) over the visible keys, grouped queries
      out = ((p v) * sigmoid(g)) W_o
    dense ffn (layer < num_dense_layers): (silu(x W_gate) * (x W_up)) W_down
    expert ffn: s = sigmoid(x W_r) over all E experts
      S = the num_experts_per_tok largest of s + expert_bias   (bias in the
      w_e = route_scale * s_e / (sum_{S} s + 1e-20)             selection only)
      y = shared(x) + sum_{e in S, e held here} w_e expert_e(x)

The sum over ``S`` normalises over all chosen experts, held here or not;
what the absent ones would have added is left out.  No auxiliary loss;
``expert_bias`` gets no gradient (``top_k``'s indices carry none): only
AdamW's decoupled weight decay moves it, by lr x wd a step, here as in the
program (the trainer's balancing rule is part of neither).

Departures, each because the program's layout was taken over so that the
two trees have the same leaves: q, k, v, gate and o keep a head axis; the
held experts' matrices are stacked on a leading axis, ``experts_gate`` and
``experts_up`` apart (the published experts are modules of their own);
the router's matrix is stored (d, E).  The expert layer is a plain loop
over the experts held, each computed on every row and weighted by that
row's gate for it, which is nought where the row did not choose it.

``quant`` is the control's hook (``gpt2.fake_fp8``): applied to both
operands of every matmul, the router's too.

It has to fit beside nothing else on one chip at the published widths:
weights and AdamW's two moments stay on the device, the starting weights
wait on the host, and gradients are made and applied a layer at a time
(AdamW is per leaf and nothing is clipped, so the order does not matter);
attention is computed one key/value head at a time and, inside it, one
query head at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.gpt2 import _mm, adamw_update, fake_fp8  # noqa: F401
from benchmarks.reference.granite_hybrid import (  # noqa: F401
    _Frozen,
    _norms,
    _rms_norm,
    _silu,
    layers_of,
)

SLIDING = "sliding_attention"  # any other kind is full attention


def _rope(x, theta: float):
    """Half-split rotary embedding of (b, s, heads, D) at positions 0..s-1."""
    s, dim = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def visible(s: int, window):
    """(s, s) bool: key j is visible to query i."""
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = j <= i
    if window is not None:
        mask = mask & (i - j < window)
    return mask


def attention(x, p, arch, kind, quant):
    eps = arch["rms_norm_eps"]
    q, k, v, g = (
        _mm("bsd,dhe->bshe", x, p[f"attn/{n}_proj/kernel"], quant)
        for n in ("q", "k", "v", "gate")
    )
    q = _rms_norm(q, p["attn/q_norm/scale"], eps)
    k = _rms_norm(k, p["attn/k_norm/scale"], eps)
    if kind == SLIDING:
        q, k = _rope(q, arch["rope_theta"]), _rope(k, arch["rope_theta"])
    mask = visible(x.shape[1], arch["sliding_window"] if kind == SLIDING else None)
    kv_heads = k.shape[2]
    q = q.reshape(q.shape[:2] + (kv_heads, -1, q.shape[-1]))  # (b, s, kv, r, e)

    @jax.checkpoint
    def head(q, k, v):  # (b, s, e) each
        s = _mm("bqe,bke->bqk", q, k, quant) / (q.shape[-1] ** 0.5)
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _mm("bqk,bke->bqe", a, v, quant)

    def group(qkv):
        q, k, v = qkv  # (b, s, r, e), (b, s, e), (b, s, e)
        o = jax.lax.map(lambda q: head(q, k, v), jnp.moveaxis(q, 2, 0))
        return jnp.moveaxis(o, 0, 2)                       # (b, s, r, e)

    o = jax.lax.map(group, (
        jnp.moveaxis(q, 2, 0), jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)
    ))                                                     # (kv, b, s, r, e)
    o = jnp.moveaxis(o, 0, 2).reshape(g.shape)
    return _mm("bshe,hed->bsd", o * jax.nn.sigmoid(g),
               p["attn/o_proj/kernel"], quant)


def gated_mlp(x, gate, up, down, quant):
    return _mm("bsf,fd->bsd",
               _silu(_mm("bsd,df->bsf", x, gate, quant))
               * _mm("bsd,df->bsf", x, up, quant), down, quant)


def route(x, p, arch, quant):
    """``(idx, w)``: each row's chosen experts (b, s, K) and their gate
    weights."""
    logits = _mm("bsd,de->bse", x, p["mlp/router/kernel"], quant)
    if arch["score_func"] != "sigmoid":
        raise ValueError(f"score_func {arch['score_func']!r}")
    s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + p["mlp/expert_bias"], arch["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if arch["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * arch["route_scale"]


def expert_ffn(x, p, arch, quant):
    """``(y, load)``: the layer's result and the rows each held expert
    received (count,) int32."""
    idx, w = route(x, p, arch, quant)
    first, count = arch["experts_held"]
    y = gated_mlp(x, p["mlp/shared/gate_proj/kernel"],
                  p["mlp/shared/up_proj/kernel"],
                  p["mlp/shared/down_proj/kernel"], quant)

    @jax.checkpoint
    def one(y, expert):  # the held experts, one after the other
        e, gate_w, up_w, down_w = expert
        chose = idx == first + e                           # (b, s, K)
        gate = jnp.sum(jnp.where(chose, w, 0.0), axis=-1)  # 0: not chosen
        y = y + gate[..., None] * gated_mlp(x, gate_w, up_w, down_w, quant)
        return y, jnp.sum(chose)

    y, load = jax.lax.scan(one, y, (
        jnp.arange(count), p["mlp/experts_gate"], p["mlp/experts_up"],
        p["mlp/experts_down"]))
    return y, load.astype(jnp.int32)


def layer(x, p, arch, kind, quant):
    """One layer; ``p`` holds its leaves without the ``layer_i/``.
    Returns ``(x, load)``; a dense layer's load is empty."""
    eps = arch["rms_norm_eps"]
    a = attention(_rms_norm(x, p["attn_norm/scale"], eps), p, arch, kind, quant)
    x = x + _rms_norm(a, p["post_attn_norm/scale"], eps)
    y = _rms_norm(x, p["mlp_norm/scale"], eps)
    if "mlp/router/kernel" in p:
        f, load = expert_ffn(y, p, arch, quant)
    else:
        f = gated_mlp(y, p["mlp/gate_proj/kernel"], p["mlp/up_proj/kernel"],
                      p["mlp/down_proj/kernel"], quant)
        load = jnp.zeros((0,), jnp.int32)
    return x + _rms_norm(f, p["post_mlp_norm/scale"], eps), load


def embed(table, tokens, arch):
    x = table[tokens]
    return x * arch["hidden_size"] ** 0.5 if arch["mup_enabled"] else x


def head_loss(x, p, targets, arch, quant):
    """Summed next-token cross entropy of the last layer's output."""
    x = _rms_norm(x, p["final_norm/scale"], arch["rms_norm_eps"])
    logits = _mm("bsd,dv->bsv", x, p["lm_head/kernel"], quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))


def forward(w: dict, tokens, arch, quant=None, with_load: bool = False):
    """tokens (B, S) int32 -> logits (B, S, V) float32 (the CPU tests);
    with ``with_load`` also every layer's load."""
    x = embed(w["token_embed/embedding"], tokens, arch)
    loads = []
    for p, kind in zip(layers_of(w), arch["layer_types"]):
        x, load = layer(x, p, arch, kind, quant)
        loads.append(load)
    x = _rms_norm(x, w["final_norm/scale"], arch["rms_norm_eps"])
    logits = _mm("bsd,dv->bsv", x, w["lm_head/kernel"], quant)
    return (logits, loads) if with_load else logits


@functools.partial(jax.jit, static_argnames=("arch", "kind", "quant"))
def _layer_fwd(x, p, arch, kind, quant):
    return layer(x, p, arch, kind, quant)


@functools.partial(jax.jit, static_argnames=("arch", "kind", "quant"))
def _layer_bwd(x, p, dy, arch, kind, quant):
    _, vjp = jax.vjp(lambda x, p: layer(x, p, arch, kind, quant)[0], x, p)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=("arch", "quant"))
def _head_bwd(x, p, targets, arch, quant):
    return jax.value_and_grad(head_loss, argnums=(0, 1))(
        x, p, targets, arch, quant
    )


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed_bwd(table, tokens, dx, scale):
    return jnp.zeros_like(table).at[tokens].add(dx * scale)


def train_steps(w0: dict, batches, opt: dict, arch: dict, quant=None,
                progress=None, devices=None):
    """Follow ``len(batches)`` AdamW steps from ``w0`` on ``batches`` (each
    (B, S+1) int32).  Returns ``{"loss": [per step], "grad_norm": {leaf:
    norm of the first gradient}, "update_norm": {leaf: ||w_n - w0||},
    "load": [per step, per expert layer: rows each held expert got]}`` as
    Python numbers — ``gpt2.train_steps``'s result and the load.
    ``devices`` is taken for that interface's sake: a step's rows are few
    and stay on the default device."""
    arch = _Frozen(arch)
    kinds = tuple(arch["layer_types"])
    start = {k: np.asarray(v, np.float32) for k, v in w0.items()}
    w = {k: jnp.asarray(v, jnp.float32) for k, v in w0.items()}
    del w0
    mu = {k: jnp.zeros_like(v) for k, v in w.items()}
    nu = {k: jnp.zeros_like(v) for k, v in w.items()}
    hyper = (opt["lr"], opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"])
    out = {"loss": [], "grad_norm": {}, "load": []}
    scale = float(arch["hidden_size"]) ** 0.5 if arch["mup_enabled"] else 1.0

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
        xs = [embed(w["token_embed/embedding"], tokens, arch)]
        stack = layers_of(w)
        loads = []
        for p, kind in zip(stack, kinds):
            y, load = _layer_fwd(xs[-1], p, arch, kind, quant)
            xs.append(y)
            if load.size:
                loads.append(np.asarray(load).tolist())
        out["load"].append(loads)
        top = {k: w[k] for k in ("final_norm/scale", "lm_head/kernel")}
        loss, (dx, dtop) = _head_bwd(xs.pop(), top, targets, arch, quant)
        out["loss"].append(float(loss) / n)
        if progress is not None:
            progress(f"reference step {i + 1}: forward and head done")
        dx = dx / n
        apply({k: v / n for k, v in dtop.items()}, count, i == 0)
        del top, dtop
        while stack:  # a layer's old leaves go as soon as it is updated
            p = stack.pop()
            dx, dp = _layer_bwd(xs.pop(), p, dx, arch, kinds[len(stack)], quant)
            del p
            apply({f"layer_{len(stack)}/{k}": v for k, v in dp.items()},
                  count, i == 0)
            del dp
        apply({"token_embed/embedding": _embed_bwd(
            w["token_embed/embedding"], tokens, dx, scale)}, count, i == 0)
        del dx
        if progress is not None:
            progress(f"reference step {i + 1} done")
    out["update_norm"] = {
        k: float(jnp.sqrt(jnp.sum(jnp.square(w[k] - start[k])))) for k in w
    }
    return out
