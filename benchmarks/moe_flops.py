"""Operations and bytes of an ``afmoe`` model (window and full attention
layers with q/k norms and an output gate, leading dense layers, then
expert layers of which this chip holds a share), from the configuration
file's published keys — ``flops.py``'s counterpart for the Trinity
configurations.  Matmuls only.  Attention is counted by the pairs a query
can see: all earlier keys in a full layer, the window's in a sliding one.
Routed rows are counted at their expectation, ``T K held / E``.
``moe_cost`` is the benchmark's own copy of the program's
``observability/cost_model.moe_cost``, so that no later PR can move the
yardstick.
"""

from __future__ import annotations

SLIDING = "sliding_attention"


def sizes(config: dict) -> dict:
    c = config
    return {
        "kinds": list(c["layer_types"]), "d": c["hidden_size"],
        "f": c["intermediate_size"], "fe": c["moe_intermediate_size"],
        "V": c["vocab_size"], "H": c["num_attention_heads"],
        "Hkv": c["num_key_value_heads"], "D": c["head_dim"],
        "window": c["sliding_window"], "dense": c["num_dense_layers"],
        "E": c["published"]["num_experts"], "held": c["experts_held"][1],
        "K": c["num_experts_per_tok"], "shared": c["num_shared_experts"],
    }


def _attn_matmul(z: dict) -> int:
    """q, gate and o at the query heads, k and v at the shared ones."""
    return z["d"] * z["D"] * (3 * z["H"] + 2 * z["Hkv"])


def _expert_layers(z: dict) -> int:
    return len(z["kinds"]) - z["dense"]


def param_count(config: dict) -> int:
    """Embedding, untied head and a final RMSNorm; per layer attention
    with its two head norms and four RMSNorms; a dense FFN (3 d f) in the
    leading layers, else the router (d E), its bias (E), the shared
    experts and the ``held`` routed ones (3 d fe each)."""
    z = sizes(config)
    attn = _attn_matmul(z) + 2 * z["D"] + 4 * z["d"]
    expert = (z["d"] * z["E"] + z["E"]
              + 3 * z["d"] * z["fe"] * (z["shared"] + z["held"]))
    return (2 * z["V"] * z["d"] + z["d"] + len(z["kinds"]) * attn
            + z["dense"] * 3 * z["d"] * z["f"] + _expert_layers(z) * expert)


def matmul_weights_per_token(config: dict) -> float:
    """Weights a token is multiplied by on this chip: attention, the FFN
    (a routed expert counts ``K held / E`` times: the expectation of the
    choices that land here) and the head."""
    z = sizes(config)
    expert = (z["d"] * z["E"] + 3 * z["d"] * z["fe"]
              * (z["shared"] + z["K"] * z["held"] / z["E"]))
    return (z["V"] * z["d"] + len(z["kinds"]) * _attn_matmul(z)
            + z["dense"] * 3 * z["d"] * z["f"] + _expert_layers(z) * expert)


def visible_pairs(seq_len: int, window: int | None) -> int:
    """(query, key) pairs of one causal sequence, each query seeing at
    most ``window`` keys."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def _pairs(z: dict, seq_len: int) -> int:
    """Visible pairs of all layers, one head, one sequence."""
    return sum(
        visible_pairs(seq_len, z["window"] if k == SLIDING else None)
        for k in z["kinds"]
    )


def attention_flops(config: dict, batch: int, seq_len: int) -> float:
    """Score and value products over the visible pairs, all layers."""
    z = sizes(config)
    return batch * 2 * 2 * z["H"] * z["D"] * _pairs(z, seq_len)


def flash_attention_cost(config: dict, batch: int, seq_len: int,
                         dtype_bytes: int = 2) -> dict:
    """``hybrid_flops.flash_attention_cost`` with the pairs a window
    leaves: the three flash kernels' 2 + 3 + 4 = 9 products a query head
    over the visible pairs; the least bytes with q, o, do, dq at the query
    heads (fwd 2, dq 4, dk/dv 3 tensors), k, v, dk, dv at the shared
    key/value heads (2, 2, 4) and the float32 row statistics (1, 2, 2).
    The forward is counted once."""
    z = sizes(config)
    rows = batch * seq_len * dtype_bytes * z["D"]
    n = len(z["kinds"])
    return {
        "flops": batch * z["H"] * 9 * 2 * z["D"] * _pairs(z, seq_len),
        "bytes": n * (9 * rows * z["H"] + 8 * rows * z["Hkv"]
                      + 5 * batch * seq_len * z["H"] * 4),
    }


def moe_cost(rows: int, d: int, f: int, groups: int) -> dict:
    """FLOPs and least HBM bytes of one expert layer's grouped products
    (gate, up and down of a gated MLP, ``d -> f -> d``) in one train step,
    from shapes alone — whatever implements them.  ``rows`` (token,
    choice) pairs over ``groups`` held experts: three products of
    ``2 rows d f`` forward, times 3 for forward and backward; bytes: the
    experts' three matrices read forward, read backward and their
    gradients written (bf16), the rows in and out and their gradients
    (bf16), once each."""
    return {
        "flops": 3 * 3 * 2 * rows * d * f,
        "bytes": 2 * (3 * 3 * groups * d * f + 4 * rows * d),
    }


def expected_rows(config: dict, tokens: int) -> int:
    """(token, choice) pairs a step routes to the experts held here, at
    their expectation."""
    z = sizes(config)
    return tokens * z["K"] * z["held"] // z["E"]


def experts_cost(config: dict, batch: int, seq_len: int) -> dict:
    """``moe_cost`` of all the configuration's expert layers."""
    z = sizes(config)
    one = moe_cost(expected_rows(config, batch * seq_len), z["d"], z["fe"],
                   z["held"])
    return {k: _expert_layers(z) * v for k, v in one.items()}


def forward_flops(config: dict, batch: int, seq_len: int) -> float:
    return (2 * batch * seq_len * matmul_weights_per_token(config)
            + attention_flops(config, batch, seq_len))


def train_step_flops(config: dict, batch: int, seq_len: int) -> float:
    """Forward plus backward (2 x forward) of what this chip computes,
    nothing recomputed."""
    return 3 * forward_flops(config, batch, seq_len)
