"""Operations and bytes of an ``evabyte`` model (chunk-summarised
attention: exact inside blocks of ``window_size`` positions, one summary
a ``chunk_size`` keys of every earlier block; a gated MLP; eight
prediction heads), from the configuration file's published keys —
``flops.py``'s counterpart for the EvaByte configurations.  Attention is
counted by the pairs a query can see: its own block's earlier keys, and
the summaries of all earlier blocks.  ``eva_pair_counts`` and ``eva_cost``
are the benchmark's own copies of the program's
``observability/cost_model`` functions, so that no later PR can move the
yardstick.
"""

from __future__ import annotations


def sizes(config: dict) -> dict:
    c = config
    return {
        "L": c["num_hidden_layers"], "d": c["hidden_size"],
        "f": c["intermediate_size"], "V": c["vocab_size"],
        "H": c["num_attention_heads"],
        "D": c["hidden_size"] // c["num_attention_heads"],
        "P": c["num_pred_heads"], "window": c["window_size"],
        "chunk": c["chunk_size"],
    }


def param_count(config: dict) -> int:
    """Embedding (V d), the untied head of P x V columns and a final norm;
    per layer q, k, v, o (4 d^2), the pooling's two vectors a head (2 H D),
    the gated MLP (3 d f) and two norms."""
    z = sizes(config)
    layer = (4 * z["d"] * z["H"] * z["D"] + 2 * z["H"] * z["D"]
             + 3 * z["d"] * z["f"] + 2 * z["d"])
    return (z["V"] * z["d"] + z["d"] * z["P"] * z["V"] + z["d"]
            + z["L"] * layer)


def matmul_weights_per_token(config: dict) -> int:
    """Weights a token is multiplied by: q, k, v, o, the MLP's three, and
    the head's P x V columns."""
    z = sizes(config)
    return (z["L"] * (4 * z["d"] * z["H"] * z["D"] + 3 * z["d"] * z["f"])
            + z["d"] * z["P"] * z["V"])


def eva_pair_counts(seq: int, window: int, chunk: int) -> tuple[int, int]:
    """``(local, remote)`` (query, key) and (query, summary) pairs of one
    head and one sequence: a query sees its own block's keys up to itself,
    and one summary a chunk of every earlier block.  At 16,384 positions
    in blocks of 2,048 and chunks of 16: 16,785,408 and 7,340,032."""
    if seq <= window:
        return seq * (seq + 1) // 2, 0
    n = seq // window
    return (n * window * (window + 1) // 2,
            window * (window // chunk) * n * (n - 1) // 2)


def eva_cost(batch: int, seq: int, heads: int, head_dim: int, window: int,
             chunk: int) -> dict:
    """FLOPs and least HBM bytes of one layer's attention over the
    summaries (``ops.eva``'s remote part) in one train step, from shapes
    alone — whatever implements it.  The score and the value product over
    the (query, summary) pairs, times 3 for forward and backward; bytes:
    the queries past the first block, their result and the gradients of
    both (bf16), the summaries of all blocks but the last, keys and
    values, and their gradients (bf16), the row statistic and its
    gradient (float32), once each."""
    _, pairs = eva_pair_counts(seq, window, chunk)
    rows = max(seq - window, 0)
    kept = rows // chunk
    return {
        "flops": 3 * 2 * 2 * batch * heads * head_dim * pairs,
        "bytes": batch * heads * (
            2 * head_dim * (4 * rows + 4 * kept) + 4 * 2 * rows),
    }


def attention_flops(config: dict, batch: int, seq_len: int) -> int:
    """Score and value products over the visible pairs, local and
    summarised, and the pooling (a chunk's logits and its two weighted
    sums: 6 D a key a head), all layers, forward."""
    z = sizes(config)
    local, remote = eva_pair_counts(seq_len, z["window"], z["chunk"])
    pooled = seq_len if seq_len > z["window"] else 0
    return z["L"] * batch * z["H"] * z["D"] * (
        2 * 2 * (local + remote) + 6 * pooled)


def local_cost(config: dict, batch: int, seq_len: int,
               dtype_bytes: int = 2) -> dict:
    """``moe_flops.flash_attention_cost`` for the block-local part: the
    three flash kernels' 2 + 3 + 4 = 9 products a head over the causal
    pairs inside the blocks; the least bytes with q, o, do, dq (fwd 2, dq
    4, dk/dv 3 tensors), k, v, dk, dv (2, 2, 4) and the float32 row
    statistics (1, 2, 2).  The forward is counted once."""
    z = sizes(config)
    local, _ = eva_pair_counts(seq_len, z["window"], z["chunk"])
    rows = batch * seq_len * dtype_bytes * z["D"] * z["H"]
    return {
        "flops": z["L"] * batch * z["H"] * 9 * 2 * z["D"] * local,
        "bytes": z["L"] * (17 * rows + 5 * batch * seq_len * z["H"] * 4),
    }


def remote_cost(config: dict, batch: int, seq_len: int) -> dict:
    """``eva_cost`` of all the configuration's layers."""
    z = sizes(config)
    one = eva_cost(batch, seq_len, z["H"], z["D"], z["window"], z["chunk"])
    return {k: z["L"] * v for k, v in one.items()}


def forward_flops(config: dict, batch: int, seq_len: int) -> int:
    return (2 * batch * seq_len * matmul_weights_per_token(config)
            + attention_flops(config, batch, seq_len))


def train_step_flops(config: dict, batch: int, seq_len: int) -> int:
    """Forward plus backward (2 x forward), nothing recomputed."""
    return 3 * forward_flops(config, batch, seq_len)
