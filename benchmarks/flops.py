"""Operations and bytes a configuration's work needs, from its shapes.

The transformer arithmetic is copied from the program's
``observability/cost_model.transformer_fwd_flops`` (matmuls only: qkv,
scores and values, output projection, MLP, logits) so that no later PR
can move the yardstick; ``causal=True`` counts the half of the score and
value products that a causal model needs.  Sizes are read from the
configuration file's ``overrides``.
"""

from __future__ import annotations


def sizes(config: dict) -> dict:
    o = config["overrides"]
    heads = o["num_heads"]
    return {
        "L": o["num_layers"], "d": o["d_model"], "H": heads,
        "D": o.get("head_dim") or o["d_model"] // heads,
        "f": o["d_ff"], "V": o["vocab_size"], "S": o["max_seq_len"],
    }


def param_count(config: dict) -> int:
    """Parameters of the GPT-2 layout: tied embedding, learned positions,
    biases, two LayerNorms a block and a final one."""
    z = sizes(config)
    d, f, a = z["d"], z["f"], z["H"] * z["D"]
    block = (3 * (d * a + a)) + (a * d + d) + (d * f + f) + (f * d + d) + 4 * d
    return z["V"] * d + z["S"] * d + z["L"] * block + 2 * d


def matmul_param_count(config: dict) -> int:
    """Weights that a token is multiplied by: blocks and the tied head."""
    z = sizes(config)
    d, f, a = z["d"], z["f"], z["H"] * z["D"]
    return z["L"] * (4 * d * a + 2 * d * f) + z["V"] * d


def attention_flops(config: dict, q_len: int, kv_len: int) -> float:
    """Score and value products of all layers for ``q_len`` queries that
    each see ``kv_len`` keys (one sequence)."""
    z = sizes(config)
    return z["L"] * 2 * 2 * z["H"] * z["D"] * q_len * kv_len


def forward_flops(config: dict, batch: int, seq_len: int,
                  causal: bool = False) -> float:
    tokens = batch * seq_len
    attn = batch * attention_flops(config, seq_len, seq_len)
    if causal:
        attn = attn * (seq_len + 1) / (2 * seq_len)
    return 2 * tokens * matmul_param_count(config) + attn


def train_step_flops(config: dict, batch: int, seq_len: int) -> float:
    """Forward plus backward (2 x forward), attention counted causal,
    nothing recomputed."""
    return 3 * forward_flops(config, batch, seq_len, causal=True)


def flash_attention_cost(config: dict, batch: int, seq_len: int,
                         dtype_bytes: int = 2) -> dict:
    """FLOPs and least HBM bytes of the three flash kernels of one train
    step, all layers: forward (QK^T, PV), dq (recompute S, dP, dQ) and
    dk/dv (recompute S, dP, dV, dK) — 2 + 3 + 4 = 9 causal (S x S x D)
    products a head; bytes: each of q, k, v, o, do, dq, dk, dv once per
    kernel that reads or writes it (fwd 4, dq 6, dkdv 7 tensors), plus
    the float32 row statistics."""
    z = sizes(config)
    per_product = 2 * seq_len * seq_len * z["D"] * (seq_len + 1) / (2 * seq_len)
    flops = z["L"] * batch * z["H"] * 9 * per_product
    tensor = batch * seq_len * z["H"] * z["D"] * dtype_bytes
    stats = batch * seq_len * z["H"] * 4
    bytes_ = z["L"] * ((4 + 6 + 7) * tensor + 5 * stats)
    return {"flops": flops, "bytes": bytes_}


def decode_step_bytes(config: dict, context_lens, dtype_bytes: int = 2) -> float:
    """Least HBM bytes of one decode step: every weight once, and the keys
    and values of the real contexts of the active slots."""
    z = sizes(config)
    weights = (matmul_param_count(config)) * dtype_bytes
    kv_per_token = z["L"] * 2 * z["H"] * z["D"] * dtype_bytes
    return weights + kv_per_token * float(sum(context_lens))


def serve_flops(config: dict, prefill_chunks, decode_contexts) -> float:
    """Model FLOPs of served work: ``prefill_chunks`` is [(start, n)] — n
    prompt tokens that see start..start+n keys; ``decode_contexts`` is the
    context length of every decoded token."""
    mm = 2 * matmul_param_count(config)
    fl = 0.0
    for start, n in prefill_chunks:
        fl += n * mm + attention_flops(config, n, start + (n + 1) / 2)
    for ctx in decode_contexts:
        fl += mm + attention_flops(config, 1, ctx + 1)
    return fl
