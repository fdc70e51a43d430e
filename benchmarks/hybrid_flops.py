"""Operations and bytes of a ``layer_types`` model (Mamba-2 mixers and
grouped-query attention layers, every layer with a gated MLP, tied head,
RMSNorm, no positions), from the configuration file's published keys —
``flops.py``'s counterpart for the hybrid configurations.  Matmuls only;
``causal=True`` counts the half of attention's score and value products
that a causal model needs; ``flash_attention_cost`` is the flash kernels'
own work at their head counts.  ``ssd_cost`` is the benchmark's own copy of
the program's ``observability/cost_model.ssd_cost``, so that no later PR
can move the yardstick.
"""

from __future__ import annotations


def sizes(config: dict) -> dict:
    c = config
    heads = c["num_attention_heads"]
    return {
        "kinds": list(c["layer_types"]), "d": c["hidden_size"],
        "f": c["shared_intermediate_size"], "V": c["vocab_size"],
        "H": heads, "Hkv": c["num_key_value_heads"],
        "D": c["hidden_size"] // heads,
        "ssm_H": c["mamba_n_heads"], "ssm_P": c["mamba_d_head"],
        "ssm_N": c["mamba_d_state"], "ssm_G": c["mamba_n_groups"],
        "ssm_K": c["mamba_d_conv"], "chunk": c["mamba_chunk_size"],
    }


def _mixer_matmul(z: dict, kind: str) -> int:
    """Weights of a mixer that a token is multiplied by."""
    d = z["d"]
    if kind == "attention":
        return d * z["D"] * (2 * z["H"] + 2 * z["Hkv"])
    inner = z["ssm_H"] * z["ssm_P"]
    return d * (2 * inner + 2 * z["ssm_G"] * z["ssm_N"] + z["ssm_H"]) + inner * d


def _mixer_other(z: dict, kind: str) -> int:
    """A mixer's parameters that no matmul reads."""
    if kind == "attention":
        return 0
    inner = z["ssm_H"] * z["ssm_P"]
    conv = inner + 2 * z["ssm_G"] * z["ssm_N"]
    # taps and bias of the convolution; dt_bias, A_log, D; the gated norm
    return conv * z["ssm_K"] + conv + 3 * z["ssm_H"] + inner


def param_count(config: dict) -> int:
    """Tied embedding, a final RMSNorm, and per layer: the mixer, the
    gated MLP (3 d f) and two RMSNorms."""
    z = sizes(config)
    layers = sum(
        _mixer_matmul(z, k) + _mixer_other(z, k) + 3 * z["d"] * z["f"]
        + 2 * z["d"] for k in z["kinds"]
    )
    return z["V"] * z["d"] + z["d"] + layers


def matmul_param_count(config: dict) -> int:
    """Weights that a token is multiplied by: layers and the tied head."""
    z = sizes(config)
    return z["V"] * z["d"] + sum(
        _mixer_matmul(z, k) + 3 * z["d"] * z["f"] for k in z["kinds"]
    )


def attention_flops(config: dict, q_len: int, kv_len: int) -> float:
    """Score and value products of the attention layers for ``q_len``
    queries that each see ``kv_len`` keys (one sequence)."""
    z = sizes(config)
    return z["kinds"].count("attention") * 2 * 2 * z["H"] * z["D"] * q_len * kv_len


def flash_attention_cost(config: dict, batch: int, seq_len: int,
                         dtype_bytes: int = 2) -> dict:
    """``flops.flash_attention_cost`` for the attention layers of a
    ``layer_types`` model under grouped-query attention: the three flash
    kernels' 2 + 3 + 4 = 9 causal (S x S x D) products a query head; the
    least bytes with q, o, do, dq at the query heads (fwd 2, dq 4, dk/dv
    3 tensors), k, v, dk, dv at the shared key/value heads (2, 2, 4) and
    the float32 row statistics (1, 2, 2).  The forward is counted once:
    remat's second launch is time without work."""
    z = sizes(config)
    per_product = 2 * seq_len * seq_len * z["D"] * (seq_len + 1) / (2 * seq_len)
    rows = batch * seq_len * dtype_bytes * z["D"]
    n = z["kinds"].count("attention")
    return {
        "flops": n * batch * z["H"] * 9 * per_product,
        "bytes": n * (9 * rows * z["H"] + 8 * rows * z["Hkv"]
                      + 5 * batch * seq_len * z["H"] * 4),
    }


def ssd_cost(batch: int, seq: int, heads: int, head_dim: int, state: int,
             groups: int, chunk: int) -> dict:
    """FLOPs and least HBM bytes of one layer's state-space scan in one
    train step, from shapes alone — whatever implements the scan.  The
    chunked form's four products, forward (``C B^T`` 2 L N a group,
    the masked matrix on ``x`` 2 L H P, ``B^T x`` and ``C h`` 2 N H P
    each, a token), times 3 for forward and backward; bytes: ``x`` and
    ``y`` (bf16), ``B`` and ``C`` (bf16), ``dt`` (f32) and their
    gradients, once each."""
    chunk = min(chunk, seq)
    tokens = batch * chunk * -(-seq // chunk)
    inner = heads * head_dim
    per_token = 2 * chunk * (groups * state + inner) + 4 * state * inner
    bytes_ = batch * seq * 2 * (
        2 * 2 * inner + 2 * 2 * groups * state + 4 * heads
    )
    return {"flops": 3 * tokens * per_token, "bytes": bytes_}


def scan_cost(config: dict, batch: int, seq_len: int) -> dict:
    """``ssd_cost`` of all the configuration's mamba layers."""
    z = sizes(config)
    one = ssd_cost(batch, seq_len, z["ssm_H"], z["ssm_P"], z["ssm_N"],
                   z["ssm_G"], z["chunk"])
    n = z["kinds"].count("mamba")
    return {k: n * v for k, v in one.items()}


def forward_flops(config: dict, batch: int, seq_len: int,
                  causal: bool = False) -> float:
    attn = batch * attention_flops(config, seq_len, seq_len)
    if causal:
        attn = attn * (seq_len + 1) / (2 * seq_len)
    scan = scan_cost(config, batch, seq_len)["flops"] / 3
    return 2 * batch * seq_len * matmul_param_count(config) + attn + scan


def train_step_flops(config: dict, batch: int, seq_len: int) -> float:
    """Forward plus backward (2 x forward), attention counted causal,
    nothing recomputed."""
    return 3 * forward_flops(config, batch, seq_len, causal=True)
